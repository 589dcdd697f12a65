"""Fused segment-softmax Pallas kernels: the attention-normalization hot op.

``graphs.segment.segment_softmax`` (GAT attention, reference PyG
``softmax(src, index)``) lowers to FOUR segment ops — ``segment_max`` →
gather → ``exp`` → ``segment_sum`` → gather → divide — with three HBM
round-trips of ``[E, H]`` intermediates. This module collapses the chain
into ONE windowed Pallas pass, following the ``fused_scatter`` playbook:

* edges arrive (near-)sorted by receiver (collate layout), so each block of
  ``block_edges`` consecutive edges touches a narrow node window; per-block
  window starts ride scalar prefetch (SMEM);
* the kernel runs the grid THREE phases over the same blocks (grid =
  ``(3, G)``, phase-major): phase 0 accumulates per-segment maxima into a
  VMEM-resident ``[N, H]`` stats buffer, phase 1 accumulates
  ``sum(exp(x - max))`` (one-hot MXU gathers/scatters against the stats
  window), phase 2 writes the normalized outputs — logits are read from HBM
  but no ``[E, H]`` intermediate is ever written back;
* a same-program ``lax.cond`` falls back to the XLA reference chain when a
  block's span exceeds the window, unless the caller supplies a host-side
  layout certificate (``fits``, from collate's ``BatchMeta``) that makes
  the choice trace-time static.

Out-of-window ids (collate's reserved dummy slot under the pad exemption,
see ``fused_scatter.window_fits_host``) get output 0 — they only ever feed
masked dummy rows; the XLA reference gives them a finite nonzero value
instead, so parity holds exactly for every certified-in-window entry.

The op's custom VJP uses the saved output directly
(``ds = s * (dy - Σ_seg s·dy)``) — one segment reduction instead of
differentiating through the four-op chain.

``fused_masked_softmax`` is the dense sibling for GPS's per-graph attention
blocks: rows are independent, so mask → max → exp → sum → divide fuses into
a single one-pass kernel with no stats buffer and no fallback (exact for
every layout). Its grid follows the call's shape (``_rows_per_step``): a step
moves as many of one graph's ``H x N_max`` rows as the VMEM budget holds
double buffered (1,856 of GPS's 232-wide rows: 18 steps for a
``[9, 16, 232, 232]`` call). A key mask ``[G, 1, 1, N_max]`` is read one
``[1, 1, N_max]`` block a graph, saved for the backward pass and applied
there at that size; only a mask with rows of its own is broadcast to the
logits' shape.

A/B switch: ``HYDRAGNN_FUSED_SOFTMAX=0|1`` (env); default on for TPU
backends, off (but testable via ``interpret=True``) elsewhere.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from . import routing
from .fused_scatter import _window_starts, row_align

Array = jax.Array

# The (window, block_edges) geometry the collate-side attention certificate
# (BatchMeta.attn_fits) is checked against. Window == block: GAT's appended
# self-loop section is a strictly increasing arange whose 256-blocks span
# exactly 256 ids — a 128 window could never certify it.
SM_CERT_WINDOW = 256
SM_CERT_BLOCK = 256

# VMEM budget for the resident stats + per-block broadcast intermediates.
_VMEM_RESIDENT_LIMIT = 10 * 1024 * 1024
_MAX_HEADS = 16  # phase-0 builds a [BE, W, H] broadcast; cap its VMEM bill

# empty-segment sentinel for the resident max stats. Finite on purpose:
# Mosaic (Pallas TPU) has no is_finite lowering, and -inf would turn the
# one-hot stats gather into 0·(-inf) = NaN. Any real logit is far above the
# threshold (GAT's mask fill is -1e9), so sentinel detection is exact.
_NEG_INIT = -3.0e38
_NEG_THRESH = -1.0e38


def self_loop_pad(num_edges: int) -> int:
    """Alignment padding GAT inserts between the real-edge section and the
    appended self-loop arange, so the arange section starts on a
    ``SM_CERT_BLOCK`` boundary (its blocks then span exactly the certified
    window). The SINGLE source for both the model-side layout
    (``models/gat.py``) and the host-side certificate
    (``graphs.batching._batch_meta``) — they must describe the same array."""
    return -num_edges % SM_CERT_BLOCK


def _auto_enabled() -> bool:
    from ..utils import flags

    return routing.default_on(flags.FUSED_SOFTMAX)


def reference_segment_softmax(
    logits: Array, segment_ids: Array, num_segments: int
) -> Array:
    """The XLA baseline: the exact ``graphs.segment.segment_softmax`` chain
    (kept in lockstep by tests — parity gates compare against THIS)."""
    seg_max = jax.ops.segment_max(
        jax.lax.stop_gradient(logits), segment_ids, num_segments=num_segments
    )
    seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, jnp.zeros_like(seg_max))
    shifted = logits - seg_max[segment_ids]
    exp = jnp.exp(shifted)
    denom = jax.ops.segment_sum(exp, segment_ids, num_segments=num_segments)
    denom = jnp.maximum(denom, 1e-12)
    return exp / denom[segment_ids]


def _softmax_kernel(
    starts_ref,  # SMEM [G] scalar-prefetch: per-block segment-window start
    logits_ref,  # VMEM [1, BE, H] logits block (edges on sublanes)
    logits_t_ref,  # VMEM [1, H, BE] the same block transposed (edges on lanes)
    rl_ref,  # VMEM [1, 1, BE] segment ids local to the block's window
    out_ref,  # VMEM [BE, H] output block
    max_ref,  # VMEM [N, H] fp32 per-segment max, resident across the grid
    sum_ref,  # VMEM [N, H] fp32 per-segment exp-sum, resident across the grid
    *,
    window: int,
    block_edges: int,
):
    p = pl.program_id(0)  # phase: 0 = max, 1 = exp-sum, 2 = normalize
    k = pl.program_id(1)  # edge block

    @pl.when(jnp.logical_and(p == 0, k == 0))
    def _init():
        max_ref[...] = jnp.full_like(max_ref, _NEG_INIT)
        sum_ref[...] = jnp.zeros_like(sum_ref)

    r0 = pl.multiple_of(starts_ref[k], row_align(max_ref.dtype))
    rl = rl_ref[0, 0, :]  # [BE]
    logits = logits_ref[0].astype(jnp.float32)  # [BE, H]
    n_heads = logits.shape[1]
    # the block's edge -> window-row one-hot in both orientations, each a
    # 2-D iota compare (Mosaic lowers no rank-3 mask and no i1 reshape)
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (block_edges, window), 1)
        == rl[:, None]
    ).astype(jnp.float32)  # [BE, W]
    onehot_t = (
        jax.lax.broadcasted_iota(jnp.int32, (window, block_edges), 0)
        == rl[None, :]
    )  # [W, BE] bool
    # out-of-window entries (pad-exempt ids): contribute nothing, output 0
    inw = ((rl >= 0) & (rl < window)).astype(jnp.float32)  # [BE]
    prec = jax.lax.Precision.HIGHEST

    @pl.when(p == 0)
    def _phase_max():
        logits_t = logits_t_ref[0].astype(jnp.float32)  # [H, BE]
        head = jax.lax.broadcasted_iota(jnp.int32, (window, n_heads), 1)
        blockmax = jnp.full((window, n_heads), _NEG_INIT, jnp.float32)
        for h in range(n_heads):  # static, <= _MAX_HEADS
            row_max = jnp.where(
                onehot_t, logits_t[h:h + 1, :], _NEG_INIT
            ).max(axis=1, keepdims=True)  # [W, 1]
            blockmax = jnp.where(head == h, row_max, blockmax)
        cur = max_ref[pl.ds(r0, window), :]
        max_ref[pl.ds(r0, window), :] = jnp.maximum(cur, blockmax)
        out_ref[...] = jnp.zeros_like(out_ref)

    # phases 1/2 share the gather of this block's per-segment stats: a
    # one-hot MXU matmul against the stats window (exact — one operand is
    # 0/1 and fp32 HIGHEST forbids bf16 rounding). Empty window rows still
    # hold the _NEG_INIT sentinel; sanitize to 0 (the reference's
    # isfinite→0 rule) BEFORE the dot, where a huge-negative times a
    # one-hot zero would lose precision against real accumulands. (A finite
    # sentinel, not -inf: Mosaic has no is_finite lowering and 0·(-inf)
    # would manufacture NaN in the matmul.)
    maxw = max_ref[pl.ds(r0, window), :]  # [W, H]
    maxw = jnp.where(maxw > _NEG_THRESH, maxw, jnp.zeros_like(maxw))
    sel_max = jnp.dot(onehot, maxw, preferred_element_type=jnp.float32,
                      precision=prec)  # [BE, H]
    # in-window entries have shifted <= 0 exactly (their max dominates), so
    # the clamp is a no-op for them; it only bounds out-of-window garbage
    shifted = jnp.minimum(logits - sel_max, 0.0)
    e = jnp.exp(shifted) * inw[:, None]

    @pl.when(p == 1)
    def _phase_sum():
        part = jnp.dot(onehot_t.astype(jnp.float32), e,
                       preferred_element_type=jnp.float32,
                       precision=prec)  # [W, H]
        sum_ref[pl.ds(r0, window), :] += part
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(p == 2)
    def _phase_out():
        sumw = sum_ref[pl.ds(r0, window), :]
        sel_sum = jnp.dot(onehot, sumw, preferred_element_type=jnp.float32,
                          precision=prec)
        out = e / jnp.maximum(sel_sum, 1e-12)
        out_ref[...] = out.astype(out_ref.dtype)


def _pallas_softmax(
    logits: Array, segment_ids: Array, num_segments: int,
    window: int, block_edges: int, interpret: bool,
) -> tuple[Array, Array]:
    """Returns (out [E, H], fits) — caller selects vs fallback on fits."""
    n, h = num_segments, logits.shape[1]
    e = logits.shape[0]
    g = e // block_edges
    starts, local, fits = _window_starts(
        segment_ids, g, block_edges, window, n, row_align(jnp.float32)
    )
    blocked = logits.reshape(g, block_edges, h)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(3, g),
        in_specs=[
            pl.BlockSpec((1, block_edges, h), lambda p, k, *_: (k, 0, 0)),
            pl.BlockSpec((1, h, block_edges), lambda p, k, *_: (k, 0, 0)),
            pl.BlockSpec((1, 1, block_edges), lambda p, k, *_: (k, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_edges, h), lambda p, k, *_: (k, 0)),
            pl.BlockSpec((n, h), lambda p, k, *_: (0, 0)),  # max resident
            pl.BlockSpec((n, h), lambda p, k, *_: (0, 0)),  # sum resident
        ],
    )
    out, _mx, _sm = pl.pallas_call(
        functools.partial(
            _softmax_kernel, window=window, block_edges=block_edges
        ),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((e, h), logits.dtype),
            jax.ShapeDtypeStruct((n, h), jnp.float32),
            jax.ShapeDtypeStruct((n, h), jnp.float32),
        ),
        interpret=interpret,
    )(starts, blocked, blocked.transpose(0, 2, 1),
      local.reshape(g, 1, block_edges))
    return out, fits


def segment_softmax_route(logits, num_segments: int) -> str | None:
    """``None`` when ``fused_segment_softmax`` runs its Mosaic kernel for
    this call, else the static reason it takes the XLA chain
    (``ops/routing.py``)."""
    reason = routing.preflight(logits.dtype)
    if reason is not None:
        return reason
    if logits.ndim != 2:
        return f"rank-{logits.ndim} logits"
    n, h = num_segments, logits.shape[1]
    if logits.shape[0] == 0 or h == 0:
        return "empty logits"
    if h > _MAX_HEADS:
        return f"{h} heads > {_MAX_HEADS}"
    if n < SM_CERT_WINDOW:
        return f"{n} segments < window {SM_CERT_WINDOW}"
    if n % 8:
        return f"{n} segments not a multiple of 8"
    # the two resident [N, H] stats (each row occupying full lanes) + the
    # per-block [BE, W] one-hots and masks
    resident = (
        2 * n * routing.lane_padded(h) + 8 * SM_CERT_BLOCK * SM_CERT_WINDOW
    ) * 4
    return routing.over_budget("resident stats", resident, _VMEM_RESIDENT_LIMIT)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _fused(logits, segment_ids, num_segments, window, block_edges, interpret,
           fits_static):
    out, fits = _pallas_softmax(
        logits, segment_ids, num_segments, window, block_edges, interpret
    )
    if fits_static:
        return out.astype(logits.dtype)
    ref = lambda: reference_segment_softmax(logits, segment_ids, num_segments)
    return jax.lax.cond(fits, lambda: out, ref).astype(logits.dtype)


def _fused_fwd(logits, segment_ids, num_segments, window, block_edges,
               interpret, fits_static):
    # the wrapped op, not the raw pallas_call, so an outer differentiation
    # meets a custom-VJP call it has a rule for (fused_scatter._fused)
    out = _fused(
        logits, segment_ids, num_segments, window, block_edges, interpret,
        fits_static,
    )
    return out, (out, routing.saved(segment_ids))


def _fused_bwd(num_segments, window, block_edges, interpret, fits_static,
               res, dout):
    # softmax VJP from the saved output: ds_i = s_i (dy_i - Σ_{j∈seg(i)} s_j
    # dy_j) — valid for BOTH the kernel and the cond-fallback forward (they
    # compute the same function), so no cond is needed here.
    out, segment_ids = res
    g = out.astype(jnp.float32) * dout.astype(jnp.float32)
    t = jax.ops.segment_sum(g, segment_ids, num_segments=num_segments)
    ds = out.astype(jnp.float32) * (dout.astype(jnp.float32) - t[segment_ids])
    return ds.astype(out.dtype), None


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_segment_softmax(
    logits: Array,
    segment_ids: Array,
    num_segments: int,
    fits: bool | None = None,
    interpret: bool | None = None,
) -> Array:
    """Numerically-stable per-segment softmax of 2D ``[E, H]`` logits in one
    Pallas pass. ``fits`` is the host-certified layout guarantee: True →
    kernel only, False → XLA chain only, None → in-program ``lax.cond``
    fallback (correct for any layout, but the dynamic cond costs both
    branches under ``vmap``).

    Certificate compatibility: the kernel's geometry is
    ``(SM_CERT_WINDOW=256, SM_CERT_BLOCK=256)``. ``BatchMeta.attn_fits`` is
    checked at exactly this geometry. The 128-window scatter certificates
    (``recv_fits``/``send_fits``, same 256 block) are STRONGER: a block that
    fits an 8-aligned 128 window from its clamped start also fits the 256
    window from the (≤) 256-clamped start — if the 256 start is unclamped it
    equals the 128 one (span < 128 < 256); if clamped to ``n-256`` the
    window reaches ``n`` and covers any id. So both certificate families are
    accepted here (``num_segments >= 256`` is required by the static check,
    keeping the clamp argument valid)."""
    window, block_edges = SM_CERT_WINDOW, SM_CERT_BLOCK
    if fits is False or segment_softmax_route(logits, num_segments):
        return reference_segment_softmax(logits, segment_ids, num_segments)
    if interpret is None:
        interpret = routing.interpret_default()
    e = logits.shape[0]
    e_pad = -e % block_edges
    if e_pad:
        # pad entries point at the reserved dummy segment; their (sliced-off)
        # outputs and their contribution to that segment's stats follow the
        # same pad-exemption soundness as the scatter kernels
        logits = jnp.pad(logits, ((0, e_pad), (0, 0)))
        segment_ids = jnp.pad(
            segment_ids, (0, e_pad), constant_values=num_segments - 1
        )
    out = _fused(
        logits, segment_ids, num_segments, window, block_edges, interpret,
        bool(fits),
    )
    return out[:e] if e_pad else out


# ---------------------------------------------------------------------------
# Dense masked row softmax (GPS per-graph attention blocks)
# ---------------------------------------------------------------------------

_MASK_FILL = -1e9  # the GPS dense path's mask fill — matched exactly


def _rows_per_step(rows: int, m: int, mask_rows: int, dtype) -> int:
    """Rows of a ``[G, rows, m]`` view one grid step moves: the most the VMEM
    budget holds (logits and output blocks, and the mask's where it has a row
    a logits row, each double buffered, a row at its lane-padded float32
    size), the group's rows spread evenly over the fewest steps that allows.
    The cell's ``[9, 16, 232, 232]``: 2,560 at most, so 3,712 rows a graph go
    in two steps of 1,856 (18 grid steps where eight-row blocks took 4,176).
    Whole sublane tiles of the dtype (``masked_softmax_route`` admits a call
    only where the budget holds one); a last block that overhangs the rows is
    Pallas's to clip (rows are independent: what the overhang computes is
    never written)."""
    tile = row_align(dtype)
    blocks = 2 * (2 + (mask_rows > 1))
    most = _VMEM_RESIDENT_LIMIT // (blocks * routing.lane_padded(m) * 4)
    most = most // tile * tile
    per_step = -(-rows // -(-rows // most))
    return -(-per_step // tile) * tile


def _row_softmax_kernel(x_ref, m_ref, o_ref):
    # x_ref / o_ref [1, rows, m]; m_ref [1, rows, m], or [1, 1, m] where one
    # key mask serves every row of the group (the grid's first index chose
    # it) and broadcasts over the block here.
    # no stop_gradient: kernels are never differentiated (the custom VJP
    # below owns the gradient), and Mosaic has no lowering for it anyway
    # compare in fp32: the v5e vector unit has no bf16 compare
    x = jnp.where(
        m_ref[0].astype(jnp.float32) > 0,
        x_ref[0].astype(jnp.float32),
        _MASK_FILL,
    )
    e = jnp.exp(x - x.max(axis=-1, keepdims=True))
    o_ref[0] = (e / e.sum(axis=-1, keepdims=True)).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fused_rows(x, mask, interpret):
    # x [G, R, m]; mask float32 [G, 1, m] or [G, R, m]
    g, r, m = x.shape
    mask_rows = mask.shape[1]
    rb = _rows_per_step(r, m, mask_rows, x.dtype)
    if mask_rows == 1:
        mask_spec = pl.BlockSpec((1, 1, m), lambda i, k: (i, 0, 0))
    else:
        mask_spec = pl.BlockSpec((1, rb, m), lambda i, k: (i, k, 0))
    return pl.pallas_call(
        _row_softmax_kernel,
        grid=(g, pl.cdiv(r, rb)),
        in_specs=[pl.BlockSpec((1, rb, m), lambda i, k: (i, k, 0)), mask_spec],
        out_specs=pl.BlockSpec((1, rb, m), lambda i, k: (i, k, 0)),
        out_shape=jax.ShapeDtypeStruct((g, r, m), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, mask)


def _fused_rows_fwd(x, mask, interpret):
    out = _fused_rows(x, mask, interpret)  # wrapped op: see _fused_fwd
    return out, (out, routing.saved(mask))


def _fused_rows_bwd(interpret, res, dout):
    out, mask = res
    s = out.astype(jnp.float32)
    dy = dout.astype(jnp.float32)
    ds = s * (dy - (s * dy).sum(axis=-1, keepdims=True))
    # the reference's `where(mask, x, -1e9)` routes no gradient to a masked
    # x. Masked positions of a row with any valid key have s == 0 already;
    # the explicit mask covers all-masked rows (padding graphs), whose s is
    # uniform. The mask broadcasts inside this fusion, at the size it has
    ds = jnp.where(mask > 0, ds, 0.0)
    return ds.astype(out.dtype), None


_fused_rows.defvjp(_fused_rows_fwd, _fused_rows_bwd)


def masked_softmax_route(logits) -> str | None:
    """``None`` when ``fused_masked_softmax`` runs its Mosaic kernel for
    this call, else the static reason it takes the XLA expression
    (``ops/routing.py``)."""
    reason = routing.preflight(logits.dtype)
    if reason is not None:
        return reason
    if logits.size == 0:
        return "empty logits"
    # the smallest block the kernel moves, with a mask block of its own size
    tile_bytes = 6 * row_align(logits.dtype) * routing.lane_padded(logits.shape[-1]) * 4
    return routing.over_budget("row block", tile_bytes, _VMEM_RESIDENT_LIMIT)


def _row_groups(shape: tuple, mask_shape: tuple) -> int:
    """How many leading groups of ``shape``'s rows share one key mask each:
    the product of the leading axes the mask has in full, where every axis
    between them and the keys is 1 in the mask (``[G, 1, 1, m]`` against
    ``[G, H, n, m]``: G). 0 where the mask has no such form, or a row of its
    own for every row of logits."""
    lead = (1,) * (len(shape) - len(mask_shape)) + tuple(mask_shape)
    if len(lead) != len(shape) or lead[-1] != shape[-1]:
        return 0
    k = len(lead) - 1
    while k and lead[k - 1] == 1:
        k -= 1
    if lead[:k] != tuple(shape[:k]):
        return 0
    groups = math.prod(shape[:k])
    # a mask with a row a logits row is no smaller for being read by group
    return groups if groups < math.prod(shape[:-1]) or k == 0 else 0


def fused_masked_softmax(
    logits: Array, mask: Array, interpret: bool | None = None
) -> Array:
    """``jax.nn.softmax(where(mask, logits, -1e9), axis=-1)`` fused into one
    row-local Pallas pass — the GPS dense-attention normalization
    (``[G, H, n, m]`` blocks). Rows are independent, so there is no window
    contract and no fallback path: the kernel is exact for every input;
    oversized/degenerate shapes take the XLA expression below instead.

    The logits are viewed as ``[G, R, m]`` and a grid step moves as many of a
    group's rows as the VMEM budget holds (:func:`_rows_per_step`). A mask
    that is constant over the axes between its leading ones and the keys
    (``[G, 1, 1, m]``, what GPS hands over) is read, saved for the backward
    pass and applied there at that size: G groups, one ``[1, 1, m]`` mask
    block each. Any other mask is broadcast to the logits' shape first and
    read block by block beside them (one group)."""
    if masked_softmax_route(logits):
        return jax.nn.softmax(jnp.where(mask, logits, _MASK_FILL), axis=-1)
    if interpret is None:
        interpret = routing.interpret_default()
    m = logits.shape[-1]
    groups = _row_groups(logits.shape, jnp.shape(mask))
    if groups:
        mask = jnp.reshape(mask, (groups, 1, m))
    else:
        groups = 1
        mask = jnp.broadcast_to(mask, logits.shape).reshape(1, -1, m)
    out = _fused_rows(
        logits.reshape(groups, -1, m), mask.astype(jnp.float32), interpret
    )
    return out.reshape(logits.shape)


__all__ = [
    "SM_CERT_BLOCK",
    "SM_CERT_WINDOW",
    "fused_masked_softmax",
    "fused_segment_softmax",
    "masked_softmax_route",
    "reference_segment_softmax",
    "segment_softmax_route",
    "self_loop_pad",
]
