"""Segment reductions — the TPU-native replacement for torch_scatter.

The reference's message-passing hot loop bottoms out in ``scatter_add`` over edges
(PyG ``MessagePassing.propagate``; see reference ``hydragnn/models/Base.py`` and
EGNN's ``unsorted_segment_sum`` at ``hydragnn/models/EGCLStack.py:294-300``).
On TPU the idiomatic equivalent is ``jax.ops.segment_sum`` with a *static*
``num_segments``, which XLA lowers to a one-hot matmul or sorted-scatter that
tiles onto the MXU/VPU. All ops here require static segment counts — that is the
contract that keeps every train step a single compiled XLA program.

Padding convention (see ``hydragnn_tpu.graphs.graph``): padded elements carry a
segment id pointing at a dedicated dummy segment (the last one), so reductions
over real segments are unaffected; masks are only needed when *reading* results.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Array = jax.Array


def _zero_empty(out: Array, identity: Array) -> Array:
    """Replace untouched (empty-segment) entries, which jax.ops fills with the
    reduction identity (±inf for floats, iinfo extremes for ints), with zeros."""
    if jnp.issubdtype(out.dtype, jnp.floating):
        return jnp.where(jnp.isfinite(out), out, jnp.zeros_like(out))
    return jnp.where(out == identity, jnp.zeros_like(out), out)


def segment_sum(
    data: Array, segment_ids: Array, num_segments: int, hints=None,
    fits: bool | None = None,
) -> Array:
    """Sum ``data`` rows into ``num_segments`` buckets by ``segment_ids``.

    2D float data routes through the Pallas windowed scatter-add kernel
    (``hydragnn_tpu.ops.fused_scatter``) when enabled — collated batches keep
    segment ids near-sorted, so each edge block touches a narrow node window.
    A/B switch: ``HYDRAGNN_FUSED_SCATTER=0|1`` (default: on for TPU).

    ``hints``: the ``GraphBatch`` the ids came from, if available. Its static
    ``BatchMeta`` (collate-certified window fits) turns the kernel-vs-XLA
    choice into a trace-time decision — no ``lax.cond`` that would execute
    both paths under ``vmap`` (the SPMD per-device step). ``fits`` is an
    explicit certificate for id arrays collate certifies nothing about (as
    ``segment_softmax``'s). What each value runs (``ops/fused_scatter.py::
    fused_segment_sum``): ``True`` the resident kernel alone; ``None`` the
    resident kernel with XLA's sum beside it behind a ``lax.cond``; ``False``
    (a certificate that failed, or stated so by the caller) keeps both out and
    runs the TILED kernel, which is exact for any id order and reads no
    certificate, wherever its route admits the rows (C a multiple of 128,
    N >= 128 and a multiple of 8), and XLA's sum elsewhere. Past the resident
    budget every value runs the tiled kernel."""
    if fits is None:
        fits = _certificate(hints, segment_ids, data)
    return _sum(data, segment_ids, num_segments, fits)


def _kernel_enabled(data: Array) -> bool:
    from ..ops import fused_scatter

    return data.ndim == 2 and fused_scatter._auto_enabled()


def _certificate(hints, ids: Array, data: Array) -> bool | None:
    """Collate's window-fit certificate for ``ids``, asked only where the
    kernel could run (``SegHintStats`` counts what the kernels were given)."""
    if hints is None or not _kernel_enabled(data):
        return None
    return hints.seg_hint(ids)


def _sum(data: Array, segment_ids: Array, num_segments: int, fits: bool | None) -> Array:
    if _kernel_enabled(data):
        from ..ops import fused_scatter

        return fused_scatter.fused_segment_sum(data, segment_ids, num_segments, fits)
    return jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)


def gather(x: Array, ids: Array, hints=None, fits: bool | None = None) -> Array:
    """Rows ``x[ids]``: the transpose of :func:`segment_sum`, and declared so.

    Forward is XLA's gather, as plain indexing emits it (it fuses into its
    consumers). The VJP is ``segment_sum(ct, ids, x.shape[0], hints)`` in
    place of the scatter-add autodiff would emit, so in the derivative passes
    of an MLIP step (forces, then the parameter gradient of the force loss)
    a gather's transpose reaches the same kernel as an explicit sum; and that
    sum's VJP is this gather again under the same ``fits``, so the pair is
    closed under any order of differentiation and a chain that started on the
    tiled kernel (``fits=False``) stays on it. ``fits`` as in
    :func:`segment_sum`."""
    if fits is None:
        fits = _certificate(hints, ids, x)
    return _gather(x, ids, x.shape[0], fits)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gather(x, ids, num_rows, fits):
    return x[ids]


def _gather_fwd(x, ids, num_rows, fits):
    from ..ops import routing

    return _gather(x, ids, num_rows, fits), routing.saved(ids)


def _gather_bwd(num_rows, fits, ids, ct):
    return _sum(ct, ids, num_rows, fits), None


_gather.defvjp(_gather_fwd, _gather_bwd)


def segment_count(segment_ids: Array, num_segments: int, weights: Array | None = None) -> Array:
    """Number of (optionally weighted) elements per segment, shape [num_segments]."""
    ones = jnp.ones(segment_ids.shape[0], dtype=jnp.float32) if weights is None else weights
    return jax.ops.segment_sum(ones, segment_ids, num_segments=num_segments)


def segment_mean(
    data: Array, segment_ids: Array, num_segments: int, eps: float = 1e-12, hints=None
) -> Array:
    """Mean per segment; empty segments yield zeros (matches torch_scatter 'mean')."""
    total = segment_sum(data, segment_ids, num_segments, hints)
    count = segment_count(segment_ids, num_segments)
    count = jnp.maximum(count, eps).astype(total.dtype)
    return total / count.reshape((-1,) + (1,) * (total.ndim - 1))


def segment_max(data: Array, segment_ids: Array, num_segments: int, hints=None) -> Array:
    """Max per segment; empty segments yield 0 (PyG ``global_max_pool`` on empty
    graphs is undefined — we pick 0 so padded dummy graphs stay finite)."""
    out = jax.ops.segment_max(data, segment_ids, num_segments=num_segments)
    identity = None
    if not jnp.issubdtype(out.dtype, jnp.floating):
        identity = jnp.iinfo(out.dtype).min
    return _zero_empty(out, identity)


def segment_min(data: Array, segment_ids: Array, num_segments: int, hints=None) -> Array:
    out = jax.ops.segment_min(data, segment_ids, num_segments=num_segments)
    identity = None
    if not jnp.issubdtype(out.dtype, jnp.floating):
        identity = jnp.iinfo(out.dtype).max
    return _zero_empty(out, identity)


def segment_std(
    data: Array, segment_ids: Array, num_segments: int, eps: float = 1e-5, hints=None
) -> Array:
    """Per-segment standard deviation (biased, matching PyG ``StdAggregation``
    used by PNA's 'std' aggregator)."""
    mean = segment_mean(data, segment_ids, num_segments, hints=hints)
    mean_sq = segment_mean(data * data, segment_ids, num_segments, hints=hints)
    var = jnp.maximum(mean_sq - mean * mean, 0.0)
    return jnp.sqrt(var + eps)


def segment_softmax(
    logits: Array, segment_ids: Array, num_segments: int, hints=None,
    fits: bool | None = None,
) -> Array:
    """Numerically-stable softmax within each segment (GAT attention weights).

    Returns an array the same shape as ``logits``; padded entries (pointing at
    the dummy segment) get well-defined finite values and must be masked by the
    caller if they would otherwise contribute.

    2D ``[E, H]`` logits route through the fused Pallas kernel
    (``hydragnn_tpu.ops.fused_softmax``) when enabled — one windowed pass
    instead of the four-segment-op chain below. A/B switch:
    ``HYDRAGNN_FUSED_SOFTMAX=0|1`` (default: on for TPU). ``fits`` is an
    explicit layout certificate for id arrays the caller built itself (GAT's
    self-loop-extended receivers carry ``BatchMeta.attn_fits``); otherwise
    ``hints.seg_hint`` resolves collate's certificate for the batch's own id
    arrays. The fused kernel's out-of-window (pad-exempt dummy) entries get
    0 instead of this chain's finite nonzero value — both are defined only
    up to the caller's mask."""
    from ..ops import fused_softmax

    if logits.ndim == 2 and fused_softmax._auto_enabled():
        if fits is None and hints is not None:
            fits = hints.seg_hint(segment_ids)
        return fused_softmax.fused_segment_softmax(
            logits, segment_ids, num_segments, fits=fits
        )
    seg_max = jax.ops.segment_max(
        jax.lax.stop_gradient(logits), segment_ids, num_segments=num_segments
    )
    seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, jnp.zeros_like(seg_max))
    shifted = logits - seg_max[segment_ids]
    exp = jnp.exp(shifted)
    denom = segment_sum(exp, segment_ids, num_segments, hints)
    denom = jnp.maximum(denom, 1e-12)
    return exp / denom[segment_ids]


def segment_normalize(
    data: Array, segment_ids: Array, num_segments: int, eps: float = 1e-12, hints=None
) -> Array:
    """Divide each element by its segment's sum (degree-normalized aggregation)."""
    denom = segment_sum(data, segment_ids, num_segments, hints)
    denom = jnp.where(jnp.abs(denom) < eps, jnp.ones_like(denom), denom)
    return data / denom[segment_ids]


_POOL_FNS = {
    "add": segment_sum,
    "sum": segment_sum,
    "mean": segment_mean,
    "max": segment_max,
    "min": segment_min,
}


def global_pool(
    kind: str, data: Array, segment_ids: Array, num_segments: int, hints=None
) -> Array:
    """Graph-level readout: the reference's ``global_{mean,add,max}_pool``
    (``hydragnn/models/Base.py:147-170``) as one masked segment reduction."""
    try:
        fn = _POOL_FNS[kind]
    except KeyError:
        raise ValueError(f"Unknown pooling '{kind}'; expected one of {sorted(_POOL_FNS)}")
    return fn(data, segment_ids, num_segments, hints=hints)


def scatter_degree(
    segment_ids: Array, num_segments: int, dtype=jnp.float32
) -> Array:
    """In-degree per receiver node — used by PNA degree scalers and SAGE/MFC
    normalization. Shape [num_segments]."""
    return segment_count(segment_ids, num_segments).astype(dtype)
