"""Fused gather→scale→scatter-add Pallas kernels: the message-passing hot op.

The role of torch_scatter in the reference (``hydragnn/models/Base.py:23``,
EGNN's ``unsorted_segment_sum``): every conv stack computes

    out[r] += weight[e] * h[s]          for each edge e = (s, r)

XLA's ``segment_sum`` lowering materializes the gathered messages ``[E, C]``
in HBM and scatters them; these kernels turn the gather and the scatter into
small *windowed* one-hot matmuls on the MXU:

* edges arrive sorted by receiver (``radius_graph`` emits them sorted, and
  ``collate`` preserves per-sample order under increasing node offsets), so
  each block of consecutive edges touches only a narrow, contiguous window of
  node rows — for both endpoints, since molecular edges never cross graph
  boundaries;
* per block, gather = ``onehot[s_local] @ h[window]`` and scatter-add =
  ``onehot[r_local].T @ msgs`` with a static window width — O(E · window · C)
  MXU FLOPs instead of O(E · N · C) for a full one-hot.

What runs, by the static route (:func:`scatter_route` and
:func:`gather_scatter_route`; dtype, rank, N, C, E and collate's certificate
decide, nothing of the batch is read):

* ``fused_segment_sum``, the row sum ``[E, C] -> [N, C]``, in two forms.
  RESIDENT while ``[N, C]`` fits the budget (10 MiB for both blocks) AND the
  layout certificate holds (``BatchMeta``) or was not stated: the whole
  accumulator stays in VMEM, one window a block, window starts by Pallas
  *scalar prefetch* (SMEM); without a stated certificate a same-program
  ``lax.cond`` keeps XLA's sum beside it. TILED everywhere else its route
  admits (C a multiple of 128, N >= 128 and a multiple of 8): past the
  resident budget (PaiNN's ``[E, 384] -> [21512, 384]`` sums) and, since
  PR 38, where the certificate is stated as NOT held (``fits=False``). The
  output stays in HBM and a VMEM accumulator slides over it with the edge
  blocks, so the VMEM need follows C and not N. Exact for any id order (an
  unsorted one only moves the accumulator more often): no certificate, no
  ``lax.cond``, no XLA branch. XLA's ``segment_sum`` is what is left: narrow
  or ragged rows, N under a window.
* ``fused_gather_scatter``, gather, product and sum in one resident kernel,
  for a certified batch whose rows the tiled sum does NOT admit
  (:func:`gather_scatter_route`). Every other call of ``gather_scatter_sum``
  with the kernels on is written on the declared pair
  (:func:`pair_gather_scatter`): ``segment.gather`` x weight ->
  ``segment.segment_sum`` with the certificate stated as not held, so the
  forward sum and every gather's transpose in every derivative pass is the
  tiled sum (SchNet's nineteen ``[E, 256] -> [N, 256]`` sums a step).

Derivatives. ``fused_gather_scatter`` is linear in ``h``: its VJP is the same
kernel with the endpoints swapped, and the filter's cotangent reads its two
operands through ``segment.gather``. ``fused_segment_sum``'s VJP (both forms)
is ``graphs.segment.gather``, the row gather whose own VJP is
``segment_sum`` again UNDER THE SAME CERTIFICATE (the tiled form's chain
states ``fits=False``, so it never re-enters the resident kernel): one pair of
mutually transposed operations, each rule calling the WRAPPED other, so any
order of differentiation (MLIP training: forces, then the parameter gradient
of the force loss) stays on the kernel it started on and never meets a raw
``pallas_call``.

A/B switch: ``HYDRAGNN_FUSED_SCATTER=0|1`` (env) or the ``fused`` argument;
default is on for TPU backends, off (but testable via ``interpret=True``)
elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import routing
from .fused_tensor_product import _BF16_PASS, _split3

Array = jax.Array

# VMEM budget for the resident h + out blocks (bytes); above this the wrapper
# statically falls back to the XLA path rather than risk a VMEM OOM.
_VMEM_RESIDENT_LIMIT = 10 * 1024 * 1024

# -- the tiled form of fused_segment_sum (below): sized from C alone
_TILE_WINDOW = 128  # rows a one-hot product; the MXU's tile
_TILE_VMEM_LIMIT = 48 * 1024 * 1024


def _tile_geometry(num_segments: int, channels: int) -> tuple[int, int]:
    """(edges a block, accumulator rows) of the tiled form: from C alone but
    for the cap at N. ~2 MiB an edge block and an accumulator of at most
    1,024 rows, both in whole 128s."""
    row_bytes = routing.lane_padded(channels) * 4
    rows = max(128, min(512, (2 << 20) // row_bytes // 128 * 128))
    span = min(2 * rows, num_segments // _TILE_WINDOW * _TILE_WINDOW)
    return rows, span


def _tile_vmem_bytes(num_segments: int, channels: int) -> int:
    """Accumulator, double-buffered edge blocks, the bf16 terms, and as much
    again for the compiler's temporaries."""
    block, span = _tile_geometry(num_segments, channels)
    row_bytes = routing.lane_padded(channels) * 4
    return 2 * ((span + _TILE_WINDOW) + 2 * block + 2 * block) * row_bytes


# The (window, block_edges) geometry collate's host-side layout certificate
# (BatchMeta.gs_fits) is checked against; a certificate is only honored for
# exactly this geometry.
GS_CERT_WINDOW = 256
GS_CERT_BLOCK = 256

# Row alignment the gather-scatter certificate (``BatchMeta.gs_fits``) is
# checked at: the kernel slices its window out of ``h`` itself, so the start
# must suit the widest row tile a compute dtype needs (bf16: 16). A block
# that fits from a 16-aligned start also fits from the fp32 kernel's
# 8-aligned one (which lies between the 16-aligned start and the block's
# lowest id), so one certificate serves both compute dtypes. The
# scatter-only and softmax kernels slice fp32 accumulators only and certify
# at 8.
GS_CERT_ALIGN = 16


def row_align(dtype) -> int:
    """Rows per sublane tile of ``dtype``: 8 for 32-bit, 16 for bf16 (two
    rows pack into one sublane). Mosaic only lowers a dynamic row slice whose
    start is provably a multiple of this, so window starts are built aligned
    to it and declared so with ``pl.multiple_of``."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _auto_enabled() -> bool:
    from ..utils import flags

    return routing.default_on(flags.FUSED_SCATTER)


def reference_gather_scatter(
    h: Array, senders: Array, receivers: Array, num_nodes: int, weight: Array | None
) -> Array:
    """The XLA baseline: gather, scale, segment_sum (fp32 accumulate)."""
    msgs = jnp.take(h, senders, axis=0).astype(jnp.float32)
    if weight is not None:
        w = weight if weight.ndim == 2 else weight[:, None]
        msgs = msgs * w.astype(jnp.float32)
    return jax.ops.segment_sum(msgs, receivers, num_segments=num_nodes)


def _kernel(
    s_starts_ref,  # SMEM [G] scalar-prefetch: per-block sender window start
    r_starts_ref,  # SMEM [G] scalar-prefetch: per-block receiver window start
    h_ref,  # VMEM [N, C] resident input features
    sl_ref,  # VMEM [1, 1, BE] sender ids local to the block's sender window
    rl_ref,  # VMEM [1, 1, BE] receiver ids local to the block's receiver window
    w_ref,  # VMEM [1, 1, BE] or [1, BE, C] edge weights (mask folded in)
    out_ref,  # VMEM [N, C] fp32 accumulator, resident across the grid
    *,
    window: int,
    block_edges: int,
    w_per_channel: bool,
):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    dtype = h_ref.dtype
    s0 = pl.multiple_of(s_starts_ref[k], row_align(dtype))
    r0 = pl.multiple_of(r_starts_ref[k], row_align(dtype))
    # bf16 inputs: default MXU passes are exact (one operand is 0/1). fp32
    # inputs: default precision would round h/msgs to bf16 inside the MXU —
    # force the full-precision multi-pass mode to keep fp32 parity with the
    # XLA segment_sum path.
    prec = (
        jax.lax.Precision.HIGHEST
        if dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )

    hw = h_ref[pl.ds(s0, window), :]  # [W, C]
    sl = sl_ref[0, 0, :]  # [BE]
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_edges, window), 1)
    onehot_s = (lane == sl[:, None]).astype(dtype)
    msgs = jnp.dot(
        onehot_s, hw, preferred_element_type=jnp.float32, precision=prec
    )  # [BE, C]

    if w_per_channel:
        msgs = msgs * w_ref[0, :, :].astype(jnp.float32)
    else:
        msgs = msgs * w_ref[0, 0, :].astype(jnp.float32)[:, None]

    rl = rl_ref[0, 0, :]
    onehot_r = (lane == rl[:, None]).astype(jnp.float32)
    partial = jnp.dot(
        onehot_r.T, msgs, preferred_element_type=jnp.float32, precision=prec
    )  # [W, C]
    out_ref[pl.ds(r0, window), :] += partial


def _last_start(n: int, window: int, align: int) -> int:
    """Largest ``align``-aligned window start that keeps the window inside
    ``n`` rows."""
    return max(n - window, 0) // align * align


def _window_starts(
    ids: Array, n_blocks: int, block_edges: int, window: int, n: int, align: int
):
    """Per-block window start (``align``-aligned, clamped) + whether every
    block fits."""
    blocks = ids.reshape(n_blocks, block_edges)
    lo = blocks.min(axis=1)
    hi = blocks.max(axis=1)
    start = jnp.clip(
        (lo // align) * align, 0, _last_start(n, window, align)
    ).astype(jnp.int32)
    fits = jnp.all(hi - start < window)
    return start, blocks - start[:, None], fits


def _pallas_gather_scatter(
    h: Array,
    senders: Array,
    receivers: Array,
    weight: Array,
    num_nodes: int,
    window: int,
    block_edges: int,
    interpret: bool,
) -> tuple[Array, Array]:
    """Returns (out_fp32 [N, C], fits) — caller selects vs fallback on fits."""
    n, c = num_nodes, h.shape[1]
    e = senders.shape[0]
    g = e // block_edges

    align = row_align(h.dtype)
    s_starts, s_local, s_fits = _window_starts(senders, g, block_edges, window, n, align)
    r_starts, r_local, r_fits = _window_starts(receivers, g, block_edges, window, n, align)
    fits = jnp.logical_and(s_fits, r_fits)

    # TPU tiling rule: the last two dims of every block shape must divide
    # (8, 128) or equal the array's dims — so per-block 1-D payloads ride a
    # leading grid axis with the block covering the trailing dims entirely.
    w_per_channel = weight.ndim == 2
    if w_per_channel:
        w_blocked = weight.reshape(g, block_edges, c)
        w_spec = pl.BlockSpec((1, block_edges, c), lambda k, *_: (k, 0, 0))
    else:
        w_blocked = weight.reshape(g, 1, block_edges)
        w_spec = pl.BlockSpec((1, 1, block_edges), lambda k, *_: (k, 0, 0))

    kernel = functools.partial(
        _kernel, window=window, block_edges=block_edges, w_per_channel=w_per_channel
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((n, c), lambda k, *_: (0, 0)),  # h resident
            pl.BlockSpec((1, 1, block_edges), lambda k, *_: (k, 0, 0)),
            pl.BlockSpec((1, 1, block_edges), lambda k, *_: (k, 0, 0)),
            w_spec,
        ],
        out_specs=pl.BlockSpec((n, c), lambda k, *_: (0, 0)),  # out resident
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, c), jnp.float32),
        interpret=interpret,
        name="fused_gather_scatter",
    )(
        s_starts,
        r_starts,
        h,
        s_local.reshape(g, 1, block_edges),
        r_local.reshape(g, 1, block_edges),
        w_blocked,
    )
    return out, fits


def segment_window(num_segments: int) -> int:
    """The window ``fused_segment_sum`` picks for a given segment count —
    exposed so host-side certification (collate → BatchMeta) uses the exact
    same value."""
    return 128 if num_segments >= 128 else num_segments


def window_fits_host(
    ids: np.ndarray, num_nodes: int, window: int, block_edges: int,
    exempt_pad_id: bool = False, align: int = 8,
) -> bool:
    """Host (numpy) replica of the kernel's per-block window-fit check, with
    the same pad-to-``block_edges`` convention ``fused_gather_scatter`` /
    ``fused_segment_sum`` apply. Collate uses this to certify the layout
    contract STATICALLY (``BatchMeta``), so the in-program ``lax.cond``
    fallback — which ``vmap`` would turn into executing both branches —
    never enters the traced program. Kept adjacent to ``_window_starts`` so
    the two stay in lockstep (tests assert they agree at equal ``align``,
    and that a ``GS_CERT_ALIGN`` certificate implies the fit at the fp32
    alignment too).

    ``exempt_pad_id``: ignore ids equal to ``num_nodes - 1`` — collate's
    reserved zero-contribution slot (pad edges carry mask weight 0; pad
    nodes feed the masked dummy graph). Without the exemption, the ONE
    boundary block mixing real edges with trailing pad edges always spans
    the whole array and vetoes certification for every production-size
    batch. Soundness: an out-of-window id matches no lane in the kernel's
    one-hot comparison, so its edge contributes exactly 0 on that side —
    identical to the XLA fallback everywhere except possibly the reserved
    dummy row itself, which collate guarantees is never read unmasked."""
    ids = np.asarray(ids, np.int64)
    e = ids.shape[0]
    if e == 0:
        return True
    last = _last_start(num_nodes, window, align)
    e_pad = -e % block_edges
    if e_pad:
        ids = np.concatenate([ids, np.full(e_pad, num_nodes - 1, np.int64)])
    blocks = ids.reshape(-1, block_edges)
    if exempt_pad_id:
        real = blocks != num_nodes - 1
        if not real.any():
            return True
        lo = np.where(real, blocks, np.int64(num_nodes)).min(axis=1)
        hi = np.where(real, blocks, np.int64(-1)).max(axis=1)
        has_real = real.any(axis=1)
        start = np.clip((lo // align) * align, 0, last)
        return bool(np.all(~has_real | (hi - start < window)))
    lo = blocks.min(axis=1)
    hi = blocks.max(axis=1)
    start = np.clip((lo // align) * align, 0, last)
    return bool(np.all(hi - start < window))


def scatter_route(
    data, num_rows: int, num_segments: int, window: int, tiled: bool = False
) -> str | None:
    """Static route shared by ``fused_gather_scatter`` (``data`` = ``h``,
    ``num_rows`` edges) and ``fused_segment_sum``: ``None`` when the call
    runs the Mosaic kernel, else the reason it takes the XLA path
    (``ops/routing.py``). Evaluated on Python ints and dtypes only.

    ``tiled``: the route of ``fused_segment_sum``'s tiled form, the same rule
    with the budget counted for an accumulator that slides over the segments
    (C alone decides it) in place of the resident ``[N, C]`` blocks."""
    reason = routing.preflight(data.dtype)
    if reason is not None:
        return reason
    if data.ndim != 2:
        return f"rank-{data.ndim} operand"
    n, c = num_segments, data.shape[1]
    if num_rows == 0:
        return "no rows to reduce"
    if n < window:
        return f"{n} segments < window {window}"
    if n % 8:
        return f"{n} segments not a multiple of 8"
    if tiled:
        if c % routing.LANES:  # the accumulator's rows move by DMA in whole lanes
            return f"{c} channels not a multiple of {routing.LANES}"
        return routing.over_budget(
            "accumulator and edge blocks", _tile_vmem_bytes(n, c), _TILE_VMEM_LIMIT
        )
    # resident h + fp32 out blocks (h counted at 4 B: the conservative bound
    # the budget was sized with), each row occupying full lanes
    return routing.over_budget(
        "resident blocks", 2 * n * routing.lane_padded(c) * 4, _VMEM_RESIDENT_LIMIT
    )


def _gather_scatter_or_ref(
    h, senders, receivers, num_nodes, weight, window, block_edges, interpret, fits_static
):
    out, fits = _pallas_gather_scatter(
        h, senders, receivers, weight, num_nodes, window, block_edges, interpret
    )
    if fits_static:
        # layout certified host-side (BatchMeta.gs_fits): kernel output is
        # exact, no fallback in the program at all
        return out.astype(h.dtype)
    ref = lambda: reference_gather_scatter(h, senders, receivers, num_nodes, weight)
    return jax.lax.cond(fits, lambda: out, ref).astype(h.dtype)


# The VJP rules below call the WRAPPED op, never the raw ``pallas_call``:
# an outer differentiation (MLIP training takes the parameter gradient of
# forces = -dE/dpos) then meets a custom-VJP call it has a rule for, instead
# of a scalar-prefetch ``pallas_call`` it would have to JVP (unimplemented).
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 5, 6, 7, 8))
def _fused(
    h, senders, receivers, num_nodes, weight, window, block_edges, interpret, fits_static
):
    return _gather_scatter_or_ref(
        h, senders, receivers, num_nodes, weight, window, block_edges, interpret,
        fits_static,
    )


def _fused_fwd(
    h, senders, receivers, num_nodes, weight, window, block_edges, interpret, fits_static
):
    out = _fused(
        h, senders, receivers, num_nodes, weight, window, block_edges, interpret,
        fits_static,
    )
    return out, routing.saved((h, senders, receivers, weight))


def _fused_bwd(num_nodes, window, block_edges, interpret, fits_static, res, dout):
    h, senders, receivers, weight = res
    # out is linear in h: dh is the same fused op with endpoints swapped
    # (gather rows of dout by receiver, scale, scatter-add onto senders).
    # fits_static covers this transposed call too: the fit check is per-array
    # and role-independent, and the fwd certified BOTH senders and receivers.
    dh = _fused(
        dout.astype(h.dtype), receivers, senders, num_nodes, weight,
        window, block_edges, interpret, fits_static,
    )
    from ..graphs import segment

    # dw[e] = <h[s_e], dout[r_e]> (summed over C for scalar weights). The
    # declared gathers: their transposes (the next differentiation's) are the
    # tiled sum where its route admits; gs_fits says nothing of that sum's
    # geometry, so the certificate is stated as not held
    hs = segment.gather(h, senders, fits=False).astype(jnp.float32)
    dr = segment.gather(dout, receivers, fits=False).astype(jnp.float32)
    dw = hs * dr if weight.ndim == 2 else (hs * dr).sum(axis=-1)
    return dh, None, None, dw.astype(weight.dtype)


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_gather_scatter(
    h: Array,
    senders: Array,
    receivers: Array,
    num_nodes: int,
    weight: Array | None = None,
    *,
    window: int = 256,
    block_edges: int = 256,
    interpret: bool | None = None,
    fits: bool | None = None,
    cert_geometry: tuple[int, int] | None = None,
) -> Array:
    """``segment_sum(weight * h[senders], receivers, num_nodes)`` fused in one
    Pallas kernel. ``fits`` is the host-certified layout guarantee
    (``BatchMeta.gs_fits``): True → kernel only, False → the pair only
    (:func:`pair_gather_scatter`, as where :func:`scatter_route` refuses the
    kernel), None → in-program ``lax.cond`` fallback (correctness never
    depends on edge layout, but the dynamic cond costs both branches under
    ``vmap``). The conv stacks enter through :func:`gather_scatter_sum`,
    which places a call here or on the pair (:func:`gather_scatter_route`).

    A ``fits`` certificate is only sound for the (window, block_edges) it was
    checked against — collate certifies the defaults
    (``GS_CERT_WINDOW``/``GS_CERT_BLOCK``); any other geometry drops the
    certificate and re-enters the dynamic in-program check rather than
    silently trusting an uncertified layout. A caller that ran
    ``window_fits_host`` itself against a non-default geometry states that
    via ``cert_geometry=(window, block_edges)`` to keep its certificate
    (the autotune sweep's path)."""
    if (window, block_edges) not in ((GS_CERT_WINDOW, GS_CERT_BLOCK),
                                     cert_geometry):
        fits = None
    if interpret is None:
        interpret = routing.interpret_default()
    if fits is False or scatter_route(h, senders.shape[0], num_nodes, window):
        return pair_gather_scatter(h, senders, receivers, num_nodes, weight)
    if weight is None:
        weight = jnp.ones(senders.shape[0], dtype=h.dtype)
    e = senders.shape[0]
    e_pad = -e % block_edges
    if e_pad:
        # zero-weight pad edges wired to the last node; jnp.pad is
        # differentiable, so gradients are un-padded by autodiff.
        senders = jnp.pad(senders, (0, e_pad), constant_values=num_nodes - 1)
        receivers = jnp.pad(receivers, (0, e_pad), constant_values=num_nodes - 1)
        weight = jnp.pad(weight, ((0, e_pad),) + ((0, 0),) * (weight.ndim - 1))
    return _fused(
        h, senders, receivers, num_nodes, weight, window, block_edges, interpret,
        bool(fits),
    )


def gather_scatter_route(
    h, num_rows: int, num_nodes: int, fits: bool | None, window: int = GS_CERT_WINDOW
) -> str | None:
    """Where :func:`gather_scatter_sum` places a call, from shapes, dtype and
    the certificate alone: ``None`` for the ``fused_gather_scatter`` kernel,
    else the reason the call is written on the pair
    (:func:`pair_gather_scatter`). The order is the probe's
    (``run-scripts/probe_row_sum.py``; PERF.md section 6, PR 38): where the
    tiled sum admits the ``[E, C]`` message rows the pair runs WHATEVER the
    certificate says."""
    rows = jax.ShapeDtypeStruct((num_rows, h.shape[1]), jnp.float32)
    if scatter_route(rows, num_rows, num_nodes, _TILE_WINDOW, tiled=True) is None:
        return "the tiled sum takes these rows"
    if fits is False:
        return "no layout certificate"
    return scatter_route(h, num_rows, num_nodes, window)


def pair_gather_scatter(
    h: Array, senders: Array, receivers: Array, num_nodes: int, weight: Array | None
) -> Array:
    """The gather-multiply-sum on the declared pair (``graphs/segment.py``),
    the certificate stated as not held: the sum by ``receivers`` and, in the
    derivative passes, each gather's transpose are ``fused_segment_sum``'s
    tiled form where its route admits the rows (exact for any id order), and
    XLA's ``segment_sum`` elsewhere. fp32 messages, as the baseline's."""
    from ..graphs import segment

    msgs = segment.gather(h, senders, fits=False).astype(jnp.float32)
    if weight is not None:
        w = weight if weight.ndim == 2 else weight[:, None]
        msgs = msgs * w.astype(jnp.float32)
    return segment.segment_sum(msgs, receivers, num_nodes, fits=False).astype(h.dtype)


def _scatter_kernel(
    r_starts_ref,  # SMEM [G] scalar-prefetch: per-block receiver window start
    data_ref,  # VMEM [BE, C] message block
    rl_ref,  # VMEM [1, 1, BE] receiver ids local to the window
    out_ref,  # VMEM [N, C] fp32 accumulator, resident across the grid
    *,
    window: int,
    block_edges: int,
):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    r0 = pl.multiple_of(r_starts_ref[k], row_align(out_ref.dtype))
    rl = rl_ref[0, 0, :]
    prec = (
        jax.lax.Precision.HIGHEST
        if data_ref.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_edges, window), 1)
    onehot_r = (lane == rl[:, None]).astype(jnp.float32)
    partial = jnp.dot(
        onehot_r.T, data_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32, precision=prec,
    )
    out_ref[pl.ds(r0, window), :] += partial


# jitted for its trace cache alone, as ``_tiled_call`` below: an EGNN step
# holds the call 76 times at four shapes (each row read's transpose in each
# pass), traced once a shape and certificate and inlined
@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6), inline=True)
def _scatter_or_ref(
    data, segment_ids, num_segments, window, block_edges, interpret, fits_static
):
    n, c = num_segments, data.shape[1]
    e = data.shape[0]
    g = e // block_edges
    r_starts, r_local, fits = _window_starts(
        segment_ids, g, block_edges, window, n, row_align(jnp.float32)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((block_edges, c), lambda k, *_: (k, 0)),
            pl.BlockSpec((1, 1, block_edges), lambda k, *_: (k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((n, c), lambda k, *_: (0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, window=window, block_edges=block_edges),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, c), jnp.float32),
        interpret=interpret,
        name="fused_segment_sum",
    )(r_starts, data, r_local.reshape(g, 1, block_edges))
    if fits_static:
        return out.astype(data.dtype)
    ref = lambda: jax.ops.segment_sum(
        data.astype(jnp.float32), segment_ids, num_segments=n
    )
    return jax.lax.cond(fits, lambda: out, ref).astype(data.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _fused_scatter(
    data, segment_ids, num_segments, window, block_edges, interpret, fits_static
):
    return _scatter_or_ref(
        data, segment_ids, num_segments, window, block_edges, interpret, fits_static
    )


def _fused_scatter_fwd(
    data, segment_ids, num_segments, window, block_edges, interpret, fits_static
):
    # the wrapped op (see _fused): closed under outer differentiation
    out = _fused_scatter(
        data, segment_ids, num_segments, window, block_edges, interpret, fits_static
    )
    return out, routing.saved(segment_ids)


def _fused_scatter_bwd(
    num_segments, window, block_edges, interpret, fits_static, segment_ids, dout
):
    from ..graphs import segment

    # the row gather whose own transpose is this sum again, under the same
    # certificate (a False here was None at the call: the in-program check)
    return segment._gather(
        dout, segment_ids, num_segments, True if fits_static else None
    ), None


_fused_scatter.defvjp(_fused_scatter_fwd, _fused_scatter_bwd)


# -- the tiled form: VMEM need independent of the segment count ---------------------------
#
# Same sum, same arithmetic idea (a windowed one-hot product on the MXU), for
# calls whose [N, C] accumulator does not fit the resident budget. The output
# stays in HBM; a VMEM accumulator of ``span`` rows slides over it with the
# edge blocks: each block visits the 128-row windows from its lowest id to its
# highest (one almost always), and when a window lies outside the rows the
# accumulator holds, the accumulator is written back and re-read at the new
# place (read-modify-write, so a window met again later keeps what it had).
# Any id order gives the exact sum, an unsorted one only moves the accumulator
# more often: no layout certificate, no ``lax.cond``, no XLA branch.
#
# Arithmetic: the one-hot operand is exact in bf16, so fp32 data is split into
# three bf16 terms (8 + 8 + 8 mantissa bits) whose products accumulate in
# fp32: what ``Precision.HIGHEST`` computes for a 0/1 operand, at half its
# passes (``ops/fused_tensor_product.py``, whose split this is). bf16 data is
# one term.


def _tiled_kernel(
    first_ref,  # SMEM [G] scalar-prefetch: first row of the block's lowest window
    count_ref,  # SMEM [G] scalar-prefetch: windows up to the block's highest id
    ids_ref,  # VMEM [1, 1, BE] segment ids of the block
    data_ref,  # VMEM [BE, C] rows of the block (the last block may overrun E)
    zeros_ref,  # HBM [N, C]: aliased to out_ref, so the output starts zeroed
    out_ref,  # HBM [N, C] fp32
    acc_ref,  # VMEM [span + 128, C] fp32: out's rows [base, base + span)
    terms_ref,  # VMEM [T, BE, C] bf16: the block's rows as bf16 terms
    base_ref,  # SMEM [1]: first row the accumulator holds
    sem,  # DMA semaphore
    *,
    num_rows: int,
    num_segments: int,
    span: int,
):
    del zeros_ref
    k = pl.program_id(0)
    block = data_ref.shape[0]
    last_base = num_segments - span  # a multiple of 8 (route: N % 8 == 0)

    def move(to_hbm: bool, base):
        held = acc_ref.at[pl.ds(0, span), :]
        rows = out_ref.at[pl.ds(pl.multiple_of(base, 8), span), :]
        copy = pltpu.make_async_copy(*((held, rows) if to_hbm else (rows, held)), sem)
        copy.start()
        copy.wait()

    @pl.when(k == 0)
    def _first():
        acc_ref[...] = jnp.zeros_like(acc_ref)  # rows past span stay zero
        base_ref[0] = jnp.minimum(first_ref[0], last_base)

    x = data_ref[...]
    if num_rows % block:  # rows past E hold whatever the buffer held
        row = jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        x = jnp.where(row < num_rows - k * block, x, jnp.zeros_like(x))
    terms = _split3(x) if x.dtype == jnp.float32 else (x.astype(jnp.bfloat16),)
    for t, term in enumerate(terms):
        terms_ref[t] = term
    ids = ids_ref[0]  # [1, BE]
    first = first_ref[k]
    end = jnp.minimum(first + count_ref[k] * _TILE_WINDOW, num_segments)
    per_pass = span // _TILE_WINDOW

    def accumulate(j, carry):
        """The windows of one accumulator's worth of the block's range."""
        lo = first + j * span
        hi = jnp.minimum(lo + span, end)
        base = base_ref[0]

        @pl.when((lo < base) | (hi > base + span))
        def _slide():
            move(True, base)
            base_ref[0] = jnp.minimum(lo, last_base)
            move(False, base_ref[0])

        base = base_ref[0]

        def window(w, carry):
            w0 = lo + w * _TILE_WINDOW
            rows = jax.lax.broadcasted_iota(jnp.int32, (_TILE_WINDOW, block), 0) + w0
            onehot = (rows == ids).astype(jnp.bfloat16)
            at = pl.ds(pl.multiple_of(w0 - base, 8), _TILE_WINDOW)
            acc_ref[at, :] += sum(
                jnp.dot(onehot, terms_ref[t], preferred_element_type=jnp.float32,
                        precision=_BF16_PASS)
                for t in range(len(terms)))
            return carry

        return jax.lax.fori_loop(0, pl.cdiv(hi - lo, _TILE_WINDOW), window, carry)

    jax.lax.fori_loop(0, pl.cdiv(count_ref[k], per_pass), accumulate, 0)

    @pl.when(k == pl.num_programs(0) - 1)
    def _last():
        move(True, base_ref[0])


# jitted for its trace cache alone: an MLIP step holds the call a dozen times
# (each gather's transpose in each pass), traced once a (shapes) and inlined,
# so every call site keeps its own scope and pass tag on the device operation
@functools.partial(jax.jit, static_argnums=(2, 3), inline=True)
def _tiled_call(data, segment_ids, num_segments: int, interpret: bool):
    e, c = data.shape
    n = num_segments
    block, span = _tile_geometry(n, c)
    g = -(-e // block)
    ids = jnp.pad(segment_ids.astype(jnp.int32), (0, g * block - e), mode="edge")
    blocks = ids.reshape(g, block)
    # an id outside [0, N) matches no row (``jax.ops.segment_sum`` drops it
    # too) and must not steer a window, which is an address
    first = jnp.clip(blocks.min(axis=1), 0, n - 1) // _TILE_WINDOW
    count = jnp.clip(blocks.max(axis=1), 0, n - 1) // _TILE_WINDOW - first + 1
    n_terms = 3 if data.dtype == jnp.float32 else 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, 1, block), lambda k, *_: (k, 0, 0)),
            pl.BlockSpec((block, c), lambda k, *_: (k, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((span + _TILE_WINDOW, c), jnp.float32),
            pltpu.VMEM((n_terms, block, c), jnp.bfloat16),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_tiled_kernel, num_rows=e, num_segments=n, span=span),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, c), jnp.float32),
        input_output_aliases={4: 0},  # after the two scalar-prefetch operands
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_TILE_VMEM_LIMIT),
        interpret=interpret,
        name="fused_segment_sum",
    )(first * _TILE_WINDOW, count, blocks[:, None, :], data, jnp.zeros((n, c), jnp.float32))
    return out.astype(data.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _tiled_sum(data, segment_ids, num_segments, interpret):
    return _tiled_call(data, segment_ids, num_segments, interpret)


def _tiled_sum_fwd(data, segment_ids, num_segments, interpret):
    # the wrapped op (see _fused): closed under outer differentiation
    return _tiled_sum(data, segment_ids, num_segments, interpret), routing.saved(segment_ids)


def _tiled_sum_bwd(num_segments, interpret, segment_ids, dout):
    from ..graphs import segment

    # the row gather whose own transpose is this sum again: stated as
    # uncertified, so at a shape the resident rule admits too the chain stays
    # on this form (no resident kernel, no in-program ``lax.cond``)
    return segment._gather(dout, segment_ids, num_segments, False), None


_tiled_sum.defvjp(_tiled_sum_fwd, _tiled_sum_bwd)


def fused_segment_sum(
    data: Array, segment_ids: Array, num_segments: int, fits: bool | None = None
) -> Array:
    """Windowed Pallas scatter-add: drop-in for ``jax.ops.segment_sum`` on 2D
    float data with (near-)sorted ids — the layout every collated batch has
    for edge→node and node→graph reductions.

    One algorithm, the accumulator placed by the budget and the certificate:
    where ``[N, C]`` fits the resident rule and ``fits`` (as in
    ``fused_gather_scatter``, host-certified via ``BatchMeta``) is not False,
    the whole accumulator stays in VMEM; elsewhere the accumulator slides over
    the segments (the tiled form above: exact for any id order, so it reads no
    certificate) where that form's route admits the rows; else XLA's sum."""
    e = data.shape[0]
    if fits is not False and scatter_route(data, e, num_segments, _TILE_WINDOW) is None:
        return _resident_sum(data, segment_ids, num_segments, fits)
    if scatter_route(data, e, num_segments, _TILE_WINDOW, tiled=True) is None:
        return _tiled_sum(data, segment_ids, num_segments, routing.interpret_default())
    return jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)


def _resident_sum(data, segment_ids, num_segments, fits):
    block_edges = 256
    e_pad = -data.shape[0] % block_edges
    if e_pad:
        data = jnp.pad(data, ((0, e_pad), (0, 0)))
        segment_ids = jnp.pad(
            segment_ids, (0, e_pad), constant_values=num_segments - 1
        )
    return _fused_scatter(
        data, segment_ids, num_segments, segment_window(num_segments), block_edges,
        routing.interpret_default(), bool(fits),
    )


def gather_scatter_sum(
    h: Array,
    senders: Array,
    receivers: Array,
    num_nodes: int,
    weight: Array | None = None,
    fused: bool | None = None,
    hints=None,
) -> Array:
    """Conv-stack entry point: the kernels when enabled (flag/env/backend
    auto), XLA gather+``segment_sum`` otherwise. ``hints`` is the source
    ``GraphBatch``: its collate-certified ``BatchMeta.gs_fits`` makes the
    choice between ``fused_gather_scatter`` and the pair trace-time static
    (:func:`gather_scatter_route`; no cond under vmap).

    With ``HYDRAGNN_OPS_AUTOTUNE`` set, a cached per-shape geometry from
    the shared autotuner replaces the default — but only when the default
    certificate provably transfers to it (``autotune.gs_cert_compatible``:
    same block, wider window), so the certified static path survives the
    geometry swap. The lookup is one in-memory dict read at trace time."""
    if fused is None:
        fused = _auto_enabled()
    if fused:
        fits = None
        if hints is not None and hints.meta is not None:
            if senders is hints.senders and receivers is hints.receivers:
                fits = hints.meta.gs_fits
            elif senders is hints.receivers and receivers is hints.senders:
                fits = hints.meta.gs_fits  # transposed flow: same certificate
        if gather_scatter_route(h, senders.shape[0], num_nodes, fits) is not None:
            return pair_gather_scatter(h, senders, receivers, num_nodes, weight)
        from .autotune import tuned_gather_scatter_geometry

        tuned = tuned_gather_scatter_geometry(
            num_nodes, senders.shape[0], h.shape[1], h.dtype
        )
        if tuned is not None:
            window, block_edges = tuned
            return fused_gather_scatter(
                h, senders, receivers, num_nodes, weight, fits=fits,
                window=window, block_edges=block_edges,
                cert_geometry=(window, block_edges),
            )
        return fused_gather_scatter(h, senders, receivers, num_nodes, weight, fits=fits)
    out = reference_gather_scatter(h, senders, receivers, num_nodes, weight)
    return out.astype(h.dtype)
