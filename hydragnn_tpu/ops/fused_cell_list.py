"""Fused cell-list neighbor build: the MD graph-rebuild hot op.

``md.binned_radius_graph`` (the on-device vesin role) is pure XLA today: it
gathers every atom's 27-cell candidate set into a ``[n, 27*capacity]`` id
matrix, gathers candidate positions ``[n, C, 3]``, and materializes
displacement/shift/distance matrices of the same extent in HBM before the
distance filter — ~20+ bytes per candidate round-tripped per MD step. This
kernel runs the candidate walk → min-image displacement → distance filter
INSIDE one Pallas pass over cell-sorted atoms, so the only candidate-extent
array that ever reaches HBM is the final 1-byte hit mask.

Geometry (the ``fused_scatter`` playbook, adapted to cells):

* atoms are sorted by cell id (XLA prelude — sort stays outside the kernel);
  every cell's atoms then form one contiguous run of the sorted array;
* grid = one program per cell. The program's central atoms and each of its
  27 neighbor-cell candidate runs are fixed-width ``W`` windows into the
  sorted position array (``W`` = capacity rounded for 8-aligned starts);
  the 27 × (start, first, count) window descriptors ride scalar prefetch,
  and exact run membership is recovered in-kernel by comparing window
  offsets against (first, count) — clamping/alignment can therefore never
  admit a wrong atom or drop a real one;
* the kernel emits the ``[cells, 27, W, W]`` int8 hit mask; a thin XLA
  epilogue decodes hit coordinates back to sorted indices arithmetically
  (cell/slot/window math — no candidate id matrix is ever built), maps them
  through the sort order, and recomputes the per-edge PBC shift for just the
  selected pairs.

Semantics are edge-for-edge identical to the XLA build (same binning, same
min-image formula, same self-exclusion, same ``max_edges`` truncation
telltale and capacity-overflow poisoning of ``n_edges``) except EDGE ORDER:
hits stream out cell-major instead of atom-major. Every consumer
(``energy_fn`` segment sums) is order-insensitive up to fp association, and
the parity tests compare edge SETS plus end-to-end energies.

The build's outputs carry no useful position gradients (ids are integers;
shifts are piecewise-constant in ``pos``, gradient 0 — same as the XLA
path), so kernel inputs are ``stop_gradient``-wrapped and the epilogue's
differentiable shift recompute preserves the XLA path's (zero) gradient
structure exactly.

A/B switch: ``HYDRAGNN_FUSED_CELL_LIST=0|1``; default on for TPU backends,
off (but testable via ``interpret=True``) elsewhere. Statically ineligible
geometries (tiny systems, VMEM/SMEM budget) return ``None`` and the caller
keeps the XLA path — correctness never depends on the kernel running.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import routing

Array = jax.Array

# resident sorted positions + per-j [W, W, 3] displacement block budget
_VMEM_RESIDENT_LIMIT = 10 * 1024 * 1024
# the 6 scalar-prefetch descriptor arrays are O(cells·27) SMEM ints; cap the
# cell count so their footprint stays bounded (beyond this the XLA path is
# memory-bound anyway and atoms should shard over the mesh first)
_MAX_CELLS = 8192


def _auto_enabled() -> bool:
    from ..utils import flags

    return routing.default_on(flags.FUSED_CELL_LIST)


def cell_window(capacity: int) -> int:
    """Window width per cell run: ``capacity`` atoms plus slack for the
    8-aligned start (a clamped-down start can sit up to 7 rows early)."""
    return int(-(-(capacity + 7) // 8) * 8)


# lane width positions are padded to: xyz sit in lanes 0..2 of a full vreg row
_LANES = routing.LANES


def _cell_kernel(
    cstart_ref,   # SMEM [cells] central window start (8-aligned, clamped)
    cfirst_ref,   # SMEM [cells] first sorted index of the central run
    ccount_ref,   # SMEM [cells] central run length
    nstart_ref,   # SMEM [cells*27] neighbor window starts
    nfirst_ref,   # SMEM [cells*27] neighbor run firsts
    ncount_ref,   # SMEM [cells*27] neighbor run lengths (0 = invalid cell)
    geom_ref,     # SMEM [21] fp32: cell matrix (9), its inverse (9), pbc (3)
    spos_ref,     # VMEM [n, _LANES] cell-sorted positions, resident
    out_ref,      # VMEM [1, 27, W, W] int8 hit mask block for this cell
    *,
    window: int,
    cutoff2: float,
):
    # Rank-2 throughout: Mosaic lowers no [W, W, 3] displacement block and no
    # i1 reshape, so each Cartesian component is its own [W, W] plane built
    # from a [W, 1] column (central atoms) and a [1, W] row (candidates).
    c = pl.program_id(0)
    w = window
    cellm = [[geom_ref[3 * k + x] for x in range(3)] for k in range(3)]
    inv = [[geom_ref[9 + 3 * x + k] for k in range(3)] for x in range(3)]
    pbcf = [geom_ref[18 + k] for k in range(3)]

    c0 = pl.multiple_of(cstart_ref[c], 8)
    catoms = spos_ref[pl.ds(c0, w), :]  # [W, L]
    ccol = [catoms[:, x:x + 1] for x in range(3)]  # 3 x [W, 1]
    cidx = c0 + jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0)
    cvalid = (cidx >= cfirst_ref[c]) & (cidx < cfirst_ref[c] + ccount_ref[c])
    # candidates are needed lane-major ([1, W] rows); the window arrives
    # sublane-major, so transpose it on the MXU: row x of ``pick`` selects
    # lane x (exact at HIGHEST — one operand is 0/1)
    pick = (
        jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 1)
    ).astype(jnp.float32)

    for j in range(27):
        s0 = pl.multiple_of(nstart_ref[c * 27 + j], 8)
        f0 = nfirst_ref[c * 27 + j]
        ct = ncount_ref[c * 27 + j]
        watoms = spos_ref[pl.ds(s0, w), :]  # [W, L]
        wrow = jax.lax.dot_general(
            pick, watoms, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )  # [8, W]; rows 0..2 = x, y, z of the candidates
        ridx = s0 + jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
        rvalid = (ridx >= f0) & (ridx < f0 + ct)
        disp = [wrow[x:x + 1, :] - ccol[x] for x in range(3)]  # 3 x [W, W]
        # min-image: wrap = round(disp @ inv) * pbc; shift = -(wrap @ cell)
        wrap = [
            jnp.round(sum(disp[x] * inv[x][k] for x in range(3))) * pbcf[k]
            for k in range(3)
        ]
        d2 = jnp.zeros((w, w), jnp.float32)
        for x in range(3):
            dx = disp[x] - sum(wrap[k] * cellm[k][x] for k in range(3))
            d2 = d2 + dx * dx
        within = (d2 <= cutoff2) & cvalid & rvalid & (cidx != ridx)
        out_ref[0, j, :, :] = within.astype(jnp.int8)


def cell_list_route(n: int, n_cells: int, window: int) -> str | None:
    """``None`` when ``fused_binned_radius_graph`` runs its Mosaic kernel
    for this geometry, else the static reason the caller keeps the XLA
    build (``ops/routing.py``)."""
    reason = routing.preflight()
    if reason is not None:
        return reason
    if n < window:
        return f"{n} atoms < window {window}"
    if n_cells > _MAX_CELLS:
        return f"{n_cells} cells > {_MAX_CELLS}"
    if n_cells * 27 * window * window >= 2**31:  # flat nonzero index space
        return "hit mask overflows int32 flat indices"
    # resident lane-padded positions + a few dozen live [W, W] fp32 planes +
    # the int8 output block
    vmem = n * _LANES * 4 + 32 * window * window * 4 + 27 * window * window
    return routing.over_budget("resident positions", vmem, _VMEM_RESIDENT_LIMIT)


def fused_binned_radius_graph(
    pos: Array,
    cutoff: float,
    max_edges: int,
    cell: Array,
    pbc: Array,
    grid: tuple[int, int, int],
    capacity: int,
    pad_id: int = 0,
    interpret: bool | None = None,
    window: int | None = None,
):
    """Fused-kernel twin of ``md.binned_radius_graph`` — same arguments,
    same ``(senders, receivers, shifts, edge_mask, n_edges)`` contract (edge
    ORDER differs: cell-major, documented above). Returns ``None`` when the
    static geometry checks rule the kernel out; the caller then runs the
    XLA path. ``grid``/``capacity`` come from ``md.plan_cell_grid``.

    ``window`` overrides the per-cell window width (autotuner axis; default
    ``cell_window(capacity)``). Any 8-aligned width at or above that minimum
    is exact — the in-kernel (first, count) membership check means window
    slack can never admit or drop an atom; when ``HYDRAGNN_OPS_AUTOTUNE`` is
    set, a cached per-shape choice from ``ops/autotune.py`` is used."""
    n = pos.shape[0]
    gx, gy, gz = (int(g) for g in grid)
    n_cells = gx * gy * gz
    base = cell_window(int(capacity))
    w = base
    if window is not None:
        w = int(window)
        if w < base or w % 8:
            raise ValueError(
                f"window must be an 8-aligned width >= cell_window(capacity)"
                f"={base}, got {w}"
            )
    else:
        from .autotune import tuned_cell_list_window

        tuned = tuned_cell_list_window(n, n_cells, int(capacity))
        if tuned is not None:
            w = tuned
    if cell_list_route(n, n_cells, w):
        return None
    if interpret is None:
        interpret = routing.interpret_default()

    from ..md import _CELL_OFFSETS, _geom_dot, _inv3

    g = jnp.asarray([gx, gy, gz], jnp.int32)
    cellm = jnp.asarray(cell, jnp.float32).reshape(3, 3)
    inv = _inv3(cellm)
    pbc_b = jnp.asarray(pbc, bool).reshape(3)

    # ---- prelude (XLA): binning + sort + per-cell run/window descriptors.
    # Bit-identical binning to the XLA build: same wrapped/clamped fractional
    # coordinates, same cell linearization.
    posf = pos.astype(jnp.float32)
    frac = _geom_dot(posf, inv)
    fw = jnp.where(pbc_b, frac % 1.0, jnp.clip(frac, 0.0, 1.0 - 1e-9))
    idx3 = jnp.clip((fw * g).astype(jnp.int32), 0, g - 1)
    cid = (idx3[:, 0] * gy + idx3[:, 1]) * gz + idx3[:, 2]
    order = jnp.argsort(cid).astype(jnp.int32)
    spos = posf[order]
    cs = cid[order]
    cell_ids = jnp.arange(n_cells, dtype=cid.dtype)
    cell_start = jnp.searchsorted(cs, cell_ids, side="left").astype(jnp.int32)
    occ = jax.ops.segment_sum(
        jnp.ones(n, jnp.int32), cid, num_segments=n_cells
    )
    max_occ = occ.max()

    coords = jnp.stack([
        cell_ids // (gy * gz), (cell_ids // gz) % gy, cell_ids % gz,
    ], axis=-1)  # [cells, 3]
    offs = jnp.asarray(_CELL_OFFSETS)
    nbr3 = coords[:, None, :] + offs[None, :, :]  # [cells, 27, 3]
    wrapped = nbr3 % g
    valid = (pbc_b | ((nbr3 >= 0) & (nbr3 < g))).all(-1)  # [cells, 27]
    ncid = (wrapped[..., 0] * gy + wrapped[..., 1]) * gz + wrapped[..., 2]

    firsts = cell_start[ncid]  # [cells, 27]
    counts = jnp.where(valid, occ[ncid], 0).astype(jnp.int32)
    hi = max(n - w, 0)
    starts8 = jnp.clip((firsts // 8) * 8, 0, hi).astype(jnp.int32)
    cstart8 = jnp.clip((cell_start // 8) * 8, 0, hi).astype(jnp.int32)

    # ---- kernel: the candidate walk + distance filter, nothing but the
    # int8 hit mask leaves the chip. The build carries no position gradient
    # (ids + piecewise-constant shifts), so kernel inputs are detached —
    # pallas_call never enters the autodiff graph.
    sg = jax.lax.stop_gradient
    geom = jnp.concatenate([
        cellm.reshape(-1), inv.reshape(-1), jnp.where(pbc_b, 1.0, 0.0),
    ]).astype(jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(n_cells,),
        in_specs=[
            pl.BlockSpec((n, _LANES), lambda c, *_: (0, 0)),  # spos resident
        ],
        out_specs=pl.BlockSpec((1, 27, w, w), lambda c, *_: (c, 0, 0, 0)),
    )
    within = pl.pallas_call(
        functools.partial(
            _cell_kernel, window=w, cutoff2=float(cutoff) ** 2
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_cells, 27, w, w), jnp.int8),
        interpret=interpret,
    )(
        cstart8, cell_start, occ.astype(jnp.int32),
        starts8.reshape(-1), firsts.reshape(-1).astype(jnp.int32),
        counts.reshape(-1), sg(geom),
        sg(jnp.pad(spos, ((0, 0), (0, _LANES - 3)))),
    )

    # ---- epilogue (XLA): decode hit coordinates arithmetically, map
    # through the sort, recompute shifts for selected pairs only.
    hits = within.reshape(-1) != 0
    n_real = hits.sum()
    flat_idx = jnp.nonzero(hits, size=max_edges, fill_value=0)[0]
    c_of = (flat_idx // (27 * w * w)).astype(jnp.int32)
    rem = flat_idx % (27 * w * w)
    j_of = (rem // (w * w)).astype(jnp.int32)
    a_of = ((rem % (w * w)) // w).astype(jnp.int32)
    i_of = (rem % w).astype(jnp.int32)
    sidx = cstart8[c_of] + a_of
    ridx = starts8[c_of, j_of] + i_of
    senders = order[sidx]
    receivers = order[ridx]
    edge_mask = (jnp.arange(max_edges) < n_real).astype(pos.dtype)

    disp = pos[receivers] - pos[senders]
    wrap = jnp.round(_geom_dot(disp, inv.astype(pos.dtype))) * jnp.where(
        pbc_b, 1.0, 0.0
    )
    shift = -_geom_dot(wrap, cellm.astype(pos.dtype))
    shifts = shift * edge_mask[:, None]
    senders = jnp.where(edge_mask > 0, senders, pad_id)
    receivers = jnp.where(edge_mask > 0, receivers, pad_id)
    # same overflow poison as the XLA build: a cell past capacity means
    # candidates were (or could have been) dropped — trip the caller's
    # n_edges telltale rather than silently missing edges
    n_edges = jnp.where(max_occ > capacity, max_edges + max_occ, n_real)
    return senders, receivers, shifts, edge_mask, n_edges


__all__ = ["cell_list_route", "cell_window", "fused_binned_radius_graph"]
