"""Shared kernel-geometry autotuner with per-shape cached choices.

``bench_fused_autotune`` proved (VERDICT r4) that the fused gather-scatter
kernel's ``(window, block_edges)`` geometry is worth real throughput — and
then threw the answer away every round. This module generalizes that sweep
into ONE autotuner for the whole ops/ kernel library (``fused_scatter``,
``fused_softmax``, ``fused_cell_list``, ``quant_matmul``):

* **candidates** are enumerated per kernel and filtered by that kernel's own
  static-fit and certificate rules BEFORE anything is timed — a geometry the
  wrapper would statically reject, or whose layout certificate cannot be
  established, never enters the sweep;
* **timing** uses the repo's ABBA paired-window discipline
  (``utils.abtest.abba_verdict`` — the exact verdict function every bench
  A/B row uses): each candidate is interleaved against the current incumbent
  in alternating windows after an untimed burn-in pair, and it is adopted
  only when it is faster beyond the host's own noise floor. Ties and
  inconclusive measurements keep the incumbent — the hard-coded default can
  only ever be replaced by a measured win;
* **choices** are keyed per ``(kernel, backend, shape-signature)`` and
  persisted to a small JSON cache NEXT TO the persistent XLA compile cache
  (``ops_autotune.json`` in ``utils/compile_cache.cache_dir()``), so
  steady-state runs pay zero sweep cost: a warm lookup is one in-memory dict read at trace time.
  The backend is part of the key because CPU windows time interpret-mode
  kernels — tuning data for the MECHANISM, never for the TPU. Bump
  ``_SCHEMA_VERSION`` when a kernel's cert rules change: a version mismatch
  discards the whole file (stale geometry certificates must not outlive the
  proof they were filtered by).

Sweeps run ONLY through the explicit ``autotune_*`` entry points (bench
rows, operator tooling) — never implicitly inside a training step. The
wrappers' side of the contract is ``tuned_*`` lookups gated on
``HYDRAGNN_OPS_AUTOTUNE``: a cached choice is honored only when the
collate-side layout certificate provably transfers to it (see
``gs_cert_compatible``), otherwise the default geometry stands.
"""

from __future__ import annotations

import json
import os
import time
from typing import Callable

import jax
import numpy as np

Array = jax.Array

# Bump when candidate filters / certificate-transfer rules change: cached
# choices are only as sound as the rules that admitted them.
_SCHEMA_VERSION = 2

_MEM: dict | None = None  # lazy-loaded {key: record} view of the disk cache
_SWEEPS_RUN = 0  # observability for the zero-sweep-cost-on-warm-cache gate


def enabled() -> bool:
    """Whether wrappers may consult the cache (``HYDRAGNN_OPS_AUTOTUNE``)."""
    from ..utils import flags

    return bool(flags.get(flags.OPS_AUTOTUNE))


def cache_path() -> str | None:
    """The on-disk cache file, next to the persistent XLA compile cache;
    None when the compile cache is disabled (in-memory only)."""
    from ..utils.compile_cache import cache_dir

    directory = cache_dir()
    return None if directory is None else os.path.join(directory, "ops_autotune.json")


def _load() -> dict:
    global _MEM
    if _MEM is not None:
        return _MEM
    _MEM = {}
    path = cache_path()
    if path is not None and os.path.exists(path):
        try:
            with open(path) as f:
                blob = json.load(f)
            if blob.get("version") == _SCHEMA_VERSION:
                _MEM = dict(blob.get("choices", {}))
        except (OSError, ValueError):
            pass  # unreadable cache = cold cache, never a failure
    return _MEM


def _persist() -> None:
    path = cache_path()
    if path is None:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"version": _SCHEMA_VERSION, "choices": _load()}, f,
                      indent=1)
        os.replace(tmp, path)
    except OSError:
        pass  # a read-only cache dir degrades to in-memory tuning


def reset_cache(forget_disk: bool = False) -> None:
    """Drop the in-memory view (tests; cross-process invalidation). With
    ``forget_disk`` also remove the persisted file."""
    global _MEM
    _MEM = None
    if forget_disk:
        path = cache_path()
        if path is not None and os.path.exists(path):
            try:
                os.remove(path)
            except OSError:
                pass


def shape_signature(**dims) -> str:
    """Canonical shape signature: sorted ``k=v`` pairs."""
    return ",".join(f"{k}={v}" for k, v in sorted(dims.items()))


def _key(kernel: str, sig: str) -> str:
    return f"{kernel}|{jax.default_backend()}|{sig}"


def lookup(kernel: str, sig: str) -> dict | None:
    """Cached choice for (kernel, this backend, sig), or None."""
    return _load().get(_key(kernel, sig))


def record(kernel: str, sig: str, geometry, evidence: dict | None = None) -> dict:
    """Persist a chosen geometry (+ the sweep evidence that earned it)."""
    rec = {"geometry": list(geometry) if isinstance(geometry, (tuple, list))
           else geometry, "evidence": evidence or {}}
    _load()[_key(kernel, sig)] = rec
    _persist()
    return rec


# ---------------------------------------------------------------------------
# Timing: ABBA paired windows, shared verdict discipline
# ---------------------------------------------------------------------------


def _time_window(fn, args, reps: int) -> float:
    out = fn(*args)
    jax.block_until_ready(out)  # compile outside the window
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / max(reps, 1) * 1e3


def _abba_pairs(build_a: Callable, build_b: Callable, reps: int, pairs: int):
    """Interleaved A/B windows (untimed burn-in pair first): the autotuner's
    timing loop. Both callables are built ONCE and reused — the jitted
    candidates compile before their first timed window, never inside one."""
    fa, aa = build_a()
    fb, ab = build_b()
    _time_window(fa, aa, reps)  # burn-in: post-compile allocator settle
    _time_window(fb, ab, reps)
    a_ms, b_ms = [], []
    for w in range(max(pairs, 1)):
        if w % 2 == 0:
            a_ms.append(_time_window(fa, aa, reps))
            b_ms.append(_time_window(fb, ab, reps))
        else:
            b_ms.append(_time_window(fb, ab, reps))
            a_ms.append(_time_window(fa, aa, reps))
    return a_ms, b_ms


def sweep(kernel: str, sig: str, builds: dict, default, *,
          reps: int = 8, pairs: int = 4, force: bool = False) -> dict:
    """The generic sweep: ``builds`` maps geometry -> ``() -> (fn, args)``
    for every candidate that survived the kernel's fit/cert filters
    (``default`` must be among them). Returns the cache record augmented
    with ``cache``/``swept`` bookkeeping; a warm cache returns instantly
    (``swept=False``) unless ``force``.

    Adoption is deliberately conservative: candidate B replaces the
    incumbent A only when the paired-window verdict says B is faster even
    pessimistically (median paired diff + noise floor < 0). Anything the
    host cannot resolve keeps the incumbent."""
    global _SWEEPS_RUN
    cached = lookup(kernel, sig)
    if cached is not None and not force:
        return {**cached, "cache": "hit", "swept": False, "sweep_s": 0.0}
    from ..utils.abtest import abba_verdict

    t0 = time.perf_counter()
    _SWEEPS_RUN += 1
    if default not in builds:
        raise ValueError(f"default geometry {default!r} not in candidates "
                         f"{sorted(map(str, builds))}")
    incumbent = default
    trials = {}
    built: dict = {}

    def built_pair(geom):
        # one build (and one jit compile) per geometry for the WHOLE sweep:
        # without the memo the incumbent would re-jit on every trial, ~
        # doubling sweep compile cost (tens of seconds each on TPU)
        if geom not in built:
            built[geom] = builds[geom]()
        return built[geom]

    for geom in builds:
        if geom == default:
            continue
        a_ms, b_ms = _abba_pairs(
            lambda g=incumbent: built_pair(g), lambda g=geom: built_pair(g),
            reps, pairs,
        )
        overhead_pct, noise_pct, verdict = abba_verdict(a_ms, b_ms,
                                                        budget_pct=0.0)
        adopted = overhead_pct + noise_pct < 0  # faster even pessimistically
        trials[str(geom)] = {
            "vs": str(incumbent),
            "overhead_pct": round(overhead_pct, 2),
            "noise_pct": round(noise_pct, 2),
            "verdict": verdict,
            "adopted": bool(adopted),
        }
        if adopted:
            incumbent = geom
    evidence = {
        "default": str(default),
        "candidates": sorted(map(str, builds)),
        "trials": trials,
        "reps": reps,
        "pairs": pairs,
        "backend": jax.default_backend(),
    }
    rec = record(kernel, sig, incumbent, evidence)
    return {**rec, "cache": "miss", "swept": True,
            "sweep_s": round(time.perf_counter() - t0, 3)}


def sweeps_run() -> int:
    return _SWEEPS_RUN


# ---------------------------------------------------------------------------
# fused_scatter: the (window, block_edges) axis — the proven sweep
# ---------------------------------------------------------------------------

# the candidate grid bench_fused_autotune swept by hand, plus the hard-coded
# default; every entry still passes through fit + certificate filters below
GS_CANDIDATES = ((128, 128), (128, 256), (256, 256), (256, 512), (512, 256))


def gs_signature(num_nodes: int, num_edges: int, channels: int, dtype) -> str:
    return shape_signature(n=int(num_nodes), e=int(num_edges),
                           c=int(channels), dtype=str(dtype))


def gs_static_candidates(num_nodes: int, channels: int) -> list[tuple[int, int]]:
    """GS_CANDIDATES filtered by the wrapper's own static route
    (``fused_scatter.scatter_route`` per geometry: window fits the node
    count, 8-aligned nodes, resident h+out inside the VMEM budget)."""
    from .fused_scatter import scatter_route

    h = jax.ShapeDtypeStruct((num_nodes, channels), np.float32)
    return [
        (window, block_edges)
        for window, block_edges in GS_CANDIDATES
        if scatter_route(h, block_edges, num_nodes, window) is None
    ]


def gs_cert_compatible(window: int, block_edges: int, num_nodes: int) -> bool:
    """Whether collate's DEFAULT-geometry certificate (``BatchMeta.gs_fits``,
    checked at ``(GS_CERT_WINDOW, GS_CERT_BLOCK)``) provably transfers to
    this geometry: same blocks (``block_edges == GS_CERT_BLOCK``) and a
    window at least as wide — a block whose span fits the 256 window from
    its aligned clamped start (``GS_CERT_ALIGN``) also fits any wider window
    (a multiple of that alignment wider) from the (≤)
    clamped start, provided the array is at least window wide so the clamp
    argument holds (the ``fused_softmax`` 128→256 implication, generalized
    upward). Narrower windows or different blockings need a fresh host
    certificate and are sweep-only."""
    from .fused_scatter import GS_CERT_BLOCK, GS_CERT_WINDOW

    return (
        block_edges == GS_CERT_BLOCK
        and window >= GS_CERT_WINDOW
        and num_nodes >= window
    )


def autotune_gather_scatter(
    h: Array, senders: Array, receivers: Array, num_nodes: int,
    weight: Array | None = None, *, reps: int = 8, pairs: int = 4,
    force: bool = False, interpret: bool | None = None,
) -> dict:
    """Sweep the fused gather-scatter geometries on a REAL staged batch
    (ids host-certified per candidate via ``window_fits_host``) and cache
    the per-shape winner. The hard-coded default ``(256, 256)`` is the
    incumbent; candidates whose layout certificate cannot be established
    on this batch are filtered out before timing."""
    import jax.numpy as jnp

    from .fused_scatter import (
        GS_CERT_ALIGN,
        GS_CERT_BLOCK,
        GS_CERT_WINDOW,
        fused_gather_scatter,
        window_fits_host,
    )

    n = int(num_nodes)
    c = int(h.shape[1])
    sig = gs_signature(n, senders.shape[0], c, h.dtype)
    default = (GS_CERT_WINDOW, GS_CERT_BLOCK)
    cached = lookup("fused_scatter", sig)
    if cached is not None and not force:
        return {**cached, "cache": "hit", "swept": False, "sweep_s": 0.0}

    if weight is None:
        weight = jnp.ones(senders.shape[0], dtype=h.dtype)
    snd_np, rcv_np = np.asarray(senders), np.asarray(receivers)
    certified = []
    for window, block_edges in gs_static_candidates(n, c):
        if window_fits_host(snd_np, n, window, block_edges,
                            exempt_pad_id=True, align=GS_CERT_ALIGN,
                            ) and window_fits_host(
                rcv_np, n, window, block_edges, exempt_pad_id=True,
                align=GS_CERT_ALIGN):
            certified.append((window, block_edges))
    if default not in certified:
        # the staged batch cannot certify even the default: nothing to tune
        rec = record("fused_scatter", sig, default,
                     {"default": str(default), "candidates": [],
                      "note": "default geometry not certifiable on the "
                              "staged batch; kept uncontested"})
        return {**rec, "cache": "miss", "swept": False, "sweep_s": 0.0}

    def build(geom):
        window, block_edges = geom

        def make():
            fn = jax.jit(
                lambda h_, s_, r_, w_, _win=window, _be=block_edges:
                fused_gather_scatter(
                    h_, s_, r_, n, w_, window=_win, block_edges=_be,
                    fits=True, cert_geometry=(_win, _be),
                    interpret=interpret,
                )
            )
            return fn, (h, senders, receivers, weight)

        return make

    builds = {geom: build(geom) for geom in certified}
    return sweep("fused_scatter", sig, builds, default,
                 reps=reps, pairs=pairs, force=force)


def tuned_gather_scatter_geometry(
    num_nodes: int, num_edges: int, channels: int, dtype
) -> tuple[int, int] | None:
    """Wrapper hook (``gather_scatter_sum``): the cached geometry for this
    shape, or None to keep the default. Only returned when the default-
    geometry collate certificate provably transfers (``gs_cert_compatible``)
    — the wrapper passes it straight through ``cert_geometry=`` and keeps
    its static, cond-free program."""
    if not enabled():
        return None
    rec = lookup("fused_scatter",
                 gs_signature(num_nodes, num_edges, channels, dtype))
    if rec is None:
        return None
    from .fused_scatter import GS_CERT_BLOCK, GS_CERT_WINDOW

    geom = rec.get("geometry")
    if not isinstance(geom, (list, tuple)) or len(geom) != 2:
        return None
    window, block_edges = int(geom[0]), int(geom[1])
    if (window, block_edges) == (GS_CERT_WINDOW, GS_CERT_BLOCK):
        return None  # the default; nothing to override
    if not gs_cert_compatible(window, block_edges, num_nodes):
        return None
    return window, block_edges


# ---------------------------------------------------------------------------
# quant_matmul: the row-block axis
# ---------------------------------------------------------------------------

QM_ROW_BLOCKS = (8, 16, 32)


def qm_signature(m: int, k: int, n: int) -> str:
    return shape_signature(m=int(m), k=int(k), n=int(n))


def qm_static_candidates(m: int, k: int, n: int) -> list[int]:
    """Row blocks the quant kernel's own VMEM/shape rules admit (mirrors
    ``quant_matmul.quant_dense``'s eligibility per row block)."""
    from .quant_matmul import _VMEM_LIMIT

    out = []
    for rb in QM_ROW_BLOCKS:
        if m < rb:
            continue
        if (k * n + rb * (k + 2 * n)) * 4 > _VMEM_LIMIT:
            continue
        out.append(rb)
    return out


def autotune_quant_dense(
    x: Array, w_q: Array, s_w: Array, s_x: float,
    bias: Array | None = None, *, reps: int = 8, pairs: int = 4,
    force: bool = False, interpret: bool | None = None,
) -> dict:
    """Sweep the int8 dense kernel's row block per activation shape. The
    quant kernel has no layout certificate (dense rows are layout-free), so
    every statically-admissible row block is timeable."""
    from .quant_matmul import _ROW_BLOCK, quant_dense

    m, k = int(x.shape[0]), int(x.shape[1])
    n = int(w_q.shape[1])
    sig = qm_signature(m, k, n)
    default = _ROW_BLOCK
    candidates = qm_static_candidates(m, k, n)
    if default not in candidates:
        rec = record("quant_matmul", sig, default,
                     {"default": str(default), "candidates": [],
                      "note": "kernel statically ineligible at this shape; "
                              "XLA route only"})
        return {**rec, "cache": "miss", "swept": False, "sweep_s": 0.0}

    def build(rb):
        def make():
            fn = jax.jit(
                lambda x_, _rb=rb: quant_dense(
                    x_, w_q, s_w, s_x, bias, kernel=True, interpret=interpret,
                    row_block=_rb,
                )
            )
            return fn, (x,)

        return make

    return sweep("quant_matmul", sig, {rb: build(rb) for rb in candidates},
                 default, reps=reps, pairs=pairs, force=force)


def tuned_quant_row_block(m: int, k: int, n: int) -> int | None:
    """Wrapper hook (``quant_dense``): cached row block for this activation
    shape, or None for the default. Dense rows carry no layout certificate,
    so the only refusals are stale/corrupt records (non-multiples of the
    base block, blocks the shape's own eligibility rules reject)."""
    if not enabled():
        return None
    from .quant_matmul import _ROW_BLOCK

    rec = lookup("quant_matmul", qm_signature(m, k, n))
    if rec is None:
        return None
    try:
        rb = int(rec.get("geometry"))
    except (TypeError, ValueError):
        return None
    if rb == _ROW_BLOCK or rb < _ROW_BLOCK or rb % _ROW_BLOCK:
        return None
    if rb not in qm_static_candidates(m, k, n):
        return None
    return rb


# ---------------------------------------------------------------------------
# fused_softmax / fused_cell_list: cert-pinned axes
# ---------------------------------------------------------------------------


def autotune_softmax(num_segments: int, heads: int) -> dict:
    """The segment-softmax geometry axis after its cert rules: pinned to the
    singleton ``(SM_CERT_WINDOW, SM_CERT_BLOCK)``. GAT's appended self-loop
    arange is block-aligned by ``self_loop_pad`` at exactly ``SM_CERT_BLOCK``
    and spans exactly one window per block, so any other blocking breaks the
    collate certificate, and the window must equal the block to cover the
    arange section — the filter leaves nothing to time, which the record
    states explicitly rather than timing an empty sweep."""
    from .fused_softmax import SM_CERT_BLOCK, SM_CERT_WINDOW

    sig = shape_signature(n=int(num_segments), h=int(heads))
    default = (SM_CERT_WINDOW, SM_CERT_BLOCK)
    cached = lookup("fused_softmax", sig)
    if cached is not None:
        return {**cached, "cache": "hit", "swept": False, "sweep_s": 0.0}
    rec = record("fused_softmax", sig, default, {
        "default": str(default), "candidates": [str(default)],
        "pinned_by": "cert rules: self_loop_pad aligns the GAT self-loop "
                     "arange to SM_CERT_BLOCK and the window must cover a "
                     "full arange block (window == block)",
    })
    return {**rec, "cache": "miss", "swept": False, "sweep_s": 0.0}


def cl_signature(n_atoms: int, n_cells: int, capacity: int) -> str:
    return shape_signature(n=int(n_atoms), cells=int(n_cells),
                           cap=int(capacity))


def cl_static_candidates(n_atoms: int, n_cells: int, capacity: int) -> list[int]:
    """Cell-list window candidates: the minimal 8-aligned capacity window
    plus wider alignments, filtered by the kernel's own static rules. The
    in-kernel exact membership check makes ANY window >= cell_window(cap)
    correct; wider windows trade VMEM/FLOPs for nothing, which the sweep is
    free to prove."""
    from .fused_cell_list import cell_list_route, cell_window

    base = cell_window(capacity)
    return [w for w in (base, base + 8, base + 16)
            if cell_list_route(n_atoms, n_cells, w) is None]


def autotune_cell_list(
    pos: Array, cutoff: float, max_edges: int, cell, pbc,
    grid: tuple[int, int, int], capacity: int, *, reps: int = 4,
    pairs: int = 2, force: bool = False, interpret: bool | None = None,
) -> dict:
    """Sweep the cell-list kernel's window width (alignment slack above the
    exact-membership minimum) per (atoms, cells, capacity) shape."""
    from .fused_cell_list import cell_window, fused_binned_radius_graph

    n = int(pos.shape[0])
    gx, gy, gz = (int(g) for g in grid)
    n_cells = gx * gy * gz
    sig = cl_signature(n, n_cells, capacity)
    default = cell_window(int(capacity))
    candidates = cl_static_candidates(n, n_cells, int(capacity))
    if default not in candidates:
        rec = record("fused_cell_list", sig, default,
                     {"default": str(default), "candidates": [],
                      "note": "kernel statically ineligible at this shape; "
                              "XLA route only"})
        return {**rec, "cache": "miss", "swept": False, "sweep_s": 0.0}

    def build(w):
        def make():
            # time the FULL build (mask kernel + decode epilogue): the
            # epilogue's nonzero/decode cost grows with the window, and a
            # truncated program that dead-code-eliminates it would bias
            # the sweep toward wide windows production then pays for
            fn = jax.jit(
                lambda p, _w=w: fused_binned_radius_graph(
                    p, cutoff, max_edges, cell, pbc, grid, capacity,
                    interpret=interpret, window=_w,
                )
            )
            return fn, (pos,)

        return make

    return sweep("fused_cell_list", sig, {w: build(w) for w in candidates},
                 default, reps=reps, pairs=pairs, force=force)


def tuned_cell_list_window(n_atoms: int, n_cells: int, capacity: int) -> int | None:
    """Wrapper hook (``fused_binned_radius_graph``): cached window for this
    shape, or None for the capacity-derived default. Any cached window below
    the exact-membership minimum is ignored (stale-cache guard)."""
    if not enabled():
        return None
    from .fused_cell_list import cell_window

    rec = lookup("fused_cell_list", cl_signature(n_atoms, n_cells, capacity))
    if rec is None:
        return None
    try:
        w = int(rec.get("geometry"))
    except (TypeError, ValueError):
        return None
    base = cell_window(int(capacity))
    if w < base or w % 8 or w == base:
        return None
    return w


__all__ = [
    "autotune_cell_list",
    "autotune_gather_scatter",
    "autotune_quant_dense",
    "autotune_softmax",
    "cache_path",
    "enabled",
    "gs_cert_compatible",
    "gs_static_candidates",
    "lookup",
    "record",
    "reset_cache",
    "shape_signature",
    "sweep",
    "sweeps_run",
    "tuned_cell_list_window",
    "tuned_gather_scatter_geometry",
    "tuned_quant_row_block",
]
