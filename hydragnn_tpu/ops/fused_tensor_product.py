"""Fused channel-wise tensor product + receiver sum: MACE's interaction
without the per-edge ``[E, S C]`` slab.

The map (``models/mace.py::TensorProduct``; S path outputs q = (path, m3) a
channel, M_in sender components a, P paths, C channels):

    out[n, q, c] = sum_{e -> n} R[e, p(q), c] * sum_a K[e, a, q] * hs[e, a, c]

is the gradient with respect to ``g`` of the four-linear form

    T(g, hs, K, R) = sum_{e, q, c} g[rcv_e, q, c] R[e, p(q), c] sum_a K[e, a, q] hs[e, a, c]

and the other three gradients are what its derivatives need, so four
functions close it under any order of differentiation, each a
``jax.custom_vjp`` whose backward calls the other three:

    tp_out(rcv, hs, kt, rt)  -> [N, S C]      dT/dg   (the forward map)
    tp_dhs(rcv, g,  kt, rt)  -> [E, M_in C]   dT/dhs
    tp_dk (rcv, g,  hs, rt)  -> [n_k, E]      dT/dK
    tp_dr (rcv, g,  hs, kt)  -> [P C, E]      dT/dR

Layout. Inside a kernel everything is EDGE-MINOR: channels on sublanes, a
block of ``BLOCK`` consecutive edges on the lanes. A per-edge scalar
(``K``) is then a ``[1, BLOCK]`` row that broadcasts over sublanes for free,
where the edge-major form needs a lane broadcast a coupling entry; and both
one-hot products are plain MXU shapes: the receiver sum is
``msg[S C, BLOCK] @ onehot[WINDOW, BLOCK]^T`` into a 128-lane window of the
resident ``[S C, N]`` accumulator, the cotangent's gather is
``g[S C, window] @ onehot[WINDOW, BLOCK]``. So the small per-edge operands
travel transposed: ``kt`` ``[n_k, E]`` (one row a nonzero (a, q) pair of the
couplings) and ``rt`` ``[P C, E]`` come out of their matmuls that way for
nothing; ``hs`` and ``dhs`` stay ``[E, M_in C]`` rows (a gather's output, a
scatter's input) and are transposed in VMEM, four tiles a block; ``g`` and
``out`` are ``[N, S C]`` outside and transposed by XLA (N rows, not E).

Windows. Edges arrive sorted by receiver, so a block's receivers span a few
nodes; each block visits the 128-node windows from its lowest receiver to
its highest (start and count by scalar prefetch; one window almost always).
Any edge order is correct, an unsorted one only visits more windows: no
layout certificate, no ``lax.cond``, no XLA branch in the program.

Arithmetic. fp32 in and out. The one-hot operand is exact in bf16, so the
other operand is split into three bf16 terms (8 + 8 + 8 mantissa bits) and
the three products accumulate in fp32: what ``Precision.HIGHEST`` computes
for a 0/1 operand, at half its passes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import routing

Array = jax.Array

BLOCK = 128  # edges a grid step (lanes); collate pads edge slots to 128s
WINDOW = 128  # nodes a window (lanes of the resident accumulator)
_ROWS = 1024  # rows of the [S C, .] operands a one-hot matmul
# a v5e core has 128 MiB of VMEM (16 MiB is only the default scoped limit);
# the resident [S C, N] block is what grows with the batch
_VMEM_LIMIT = 100 * 1024 * 1024
# the kernels' matmuls are single bf16 passes over operands that ARE bf16
# (see "Arithmetic"), whatever ``jax_default_matmul_precision`` the caller set
_BF16_PASS = jax.lax.Precision.DEFAULT


class Plan(NamedTuple):
    """The static structure of one layer's product: hashable, from shapes and
    the couplings' zero pattern only."""

    channels: int
    m_in: int  # sender components
    n_paths: int
    outputs: tuple  # per q: (path p, ((a, j), ...)) with j the row of kt

    @property
    def slab(self) -> int:
        return len(self.outputs)

    @property
    def n_k(self) -> int:
        return sum(len(pairs) for _, pairs in self.outputs)


def make_plan(nonzero: np.ndarray, path_of: tuple, channels: int) -> Plan:
    """``nonzero[a, q]``: whether sender component a couples into output q;
    ``path_of[q]``: q's path. Rows of ``kt`` are the nonzero pairs in
    (q, a) order: ``plan_pairs`` lists them."""
    m_in, slab = nonzero.shape
    outputs, j = [], 0
    for q in range(slab):
        pairs = []
        for a in range(m_in):
            if nonzero[a, q]:
                pairs.append((a, j))
                j += 1
        outputs.append((int(path_of[q]), tuple(pairs)))
    return Plan(int(channels), int(m_in), int(max(path_of)) + 1, tuple(outputs))


def plan_pairs(plan: Plan) -> list:
    """(a, q) of each row of ``kt``."""
    return [(a, q) for q, (_, pairs) in enumerate(plan.outputs) for a, _ in pairs]


def reference_tensor_product(plan: Plan, rcv: Array, hs: Array, kt: Array, rt: Array,
                             num_nodes: int) -> Array:
    """The same map in plain XLA on the same operands (builds the slab):
    what the kernels are tested against, derivatives included."""
    C, cols = plan.channels, []
    for p, pairs in plan.outputs:
        acc = sum(kt[j][:, None] * hs[:, a * C:(a + 1) * C] for a, j in pairs)
        cols.append(acc * rt[p * C:(p + 1) * C].T)
    return jax.ops.segment_sum(jnp.concatenate(cols, axis=1), rcv, num_segments=num_nodes)


def enabled() -> bool:
    """``HYDRAGNN_FUSED_TENSOR_PRODUCT`` when set, else on a TPU backend."""
    from ..utils import flags

    return routing.default_on(flags.FUSED_TENSOR_PRODUCT)


def _padded_nodes(num_nodes: int) -> int:
    return -(-num_nodes // WINDOW) * WINDOW


def _vmem_bytes(plan: Plan, num_nodes: int) -> int:
    """What the largest of the four kernels keeps in VMEM: the resident
    ``[S C, N]`` block (two buffers), the fp32 ``[S C, BLOCK]`` scratch (the
    messages, or the gathered cotangent), double-buffered edge blocks, and
    twice the scratch again for the compiler's temporaries (the bf16 terms)."""
    sc, C = plan.slab * plan.channels, plan.channels
    resident = 2 * sc * _padded_nodes(num_nodes) * 4
    scratch = sc * BLOCK * 4 + plan.m_in * C * BLOCK * 4
    blocks = 2 * 4 * BLOCK * (2 * plan.m_in * C + 2 * plan.n_paths * C + 2 * plan.n_k)
    return resident + 3 * scratch + blocks


def tensor_product_route(plan: Plan, num_edges: int, num_nodes: int, dtype,
                         interpret: bool | None = None) -> str | None:
    """``None`` when the product runs the kernels, else the reason it takes
    the XLA path: from dtype and shapes only (``ops/routing.py``). Mosaic's
    lane rule on the channels does not bind the interpreter."""
    if interpret is None:
        interpret = routing.interpret_default()
    reason = routing.preflight(dtype)
    if reason is not None:
        return reason
    if jnp.dtype(dtype) != jnp.float32:
        return f"dtype {jnp.dtype(dtype).name}: the kernels are fp32"
    if plan.channels % routing.LANES and not interpret:
        return f"{plan.channels} channels not a multiple of {routing.LANES}"
    if num_edges == 0 or num_edges % BLOCK:
        return f"{num_edges} edge slots not a multiple of {BLOCK}"
    return routing.over_budget("resident accumulator and scratch",
                               _vmem_bytes(plan, num_nodes), _VMEM_LIMIT)


# -- kernel pieces ----------------------------------------------------------------------


def _split3(x: Array):
    """fp32 -> three bf16 terms whose sum is x to 24 bits."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _onehot_t(rcv_row: Array, w0) -> Array:
    """``[WINDOW, BLOCK]``: node ``w0 + i`` (sublane) receives edge e (lane)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (WINDOW, rcv_row.shape[1]), 0) + w0
    return (rows == rcv_row).astype(jnp.bfloat16)


def _row_chunks(total: int):
    return [(r, min(_ROWS, total - r)) for r in range(0, total, _ROWS)]


def _for_windows(start_ref, count_ref, body) -> None:
    """``body(w0)`` for each 128-node window the block's receivers reach."""
    k = pl.program_id(0)

    def step(w, carry):
        body(pl.multiple_of(start_ref[k] + w * WINDOW, WINDOW))
        return carry

    jax.lax.fori_loop(0, count_ref[k], step, 0)


def _transpose_in(hs_ref, hst_ref, plan: Plan) -> None:
    C = plan.channels
    for a in range(plan.m_in):
        hst_ref[a * C:(a + 1) * C, :] = hs_ref[:, a * C:(a + 1) * C].T


def _gather_cotangent(start_ref, count_ref, rcv_ref, gt_ref, ge_ref) -> None:
    """``ge[S C, BLOCK]`` = the cotangent's rows at the block's receivers,
    read through the windows of the resident ``gt`` ``[S C, N]``."""
    ge_ref[...] = jnp.zeros_like(ge_ref)
    rcv = rcv_ref[0]

    def window(w0):
        onehot = _onehot_t(rcv, w0)
        for r, n in _row_chunks(ge_ref.shape[0]):
            terms = _split3(gt_ref[r:r + n, pl.ds(w0, WINDOW)])
            ge_ref[r:r + n, :] += sum(
                jnp.dot(t, onehot, preferred_element_type=jnp.float32, precision=_BF16_PASS)
                for t in terms)

    _for_windows(start_ref, count_ref, window)


def _by_path(plan: Plan):
    """(p, ((q, pairs), ...)) for each path: q runs path-major, so a path's
    radial weights and sender components are loaded once for all its q."""
    paths: dict = {}
    for q, (p, pairs) in enumerate(plan.outputs):
        paths.setdefault(p, []).append((q, pairs))
    return list(paths.items())


def _tiles(ref, rows, C: int) -> dict:
    """``{r: ref[r C:(r + 1) C, :]}``: ``[C, BLOCK]`` tiles by row index."""
    return {r: ref[r * C:(r + 1) * C, :] for r in sorted(rows)}


def _components(qs) -> set:
    return {a for _, pairs in qs for a, _ in pairs}


def _add(acc, term):
    return term if acc is None else acc + term


def _coupled(kt_ref, tiles: dict, pairs) -> Array:
    """sum_a K[a, q] tile[a]: ``[C, BLOCK]``; a row of K broadcasts over sublanes."""
    acc = None
    for a, j in pairs:
        acc = _add(acc, kt_ref[j:j + 1, :] * tiles[a])
    return acc


def _out_kernel(start_ref, count_ref, rcv_ref, hs_ref, kt_ref, rt_ref, out_ref,
                hst_ref, msg_ref, *, plan: Plan):
    C = plan.channels

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    _transpose_in(hs_ref, hst_ref, plan)
    for p, qs in _by_path(plan):
        weight = rt_ref[p * C:(p + 1) * C, :]
        weighted = {a: weight * tile for a, tile in _tiles(hst_ref, _components(qs), C).items()}
        for q, pairs in qs:
            msg_ref[q * C:(q + 1) * C, :] = _coupled(kt_ref, weighted, pairs)
    rcv = rcv_ref[0]

    def window(w0):
        onehot = _onehot_t(rcv, w0)
        for r, n in _row_chunks(out_ref.shape[0]):
            out_ref[r:r + n, pl.ds(w0, WINDOW)] += sum(
                jax.lax.dot_general(term, onehot, (((1,), (1,)), ((), ())),
                                    precision=_BF16_PASS, preferred_element_type=jnp.float32)
                for term in _split3(msg_ref[r:r + n, :]))

    _for_windows(start_ref, count_ref, window)


def _dhs_kernel(start_ref, count_ref, rcv_ref, gt_ref, kt_ref, rt_ref, dhs_ref,
                ge_ref, *, plan: Plan):
    C = plan.channels
    _gather_cotangent(start_ref, count_ref, rcv_ref, gt_ref, ge_ref)
    acc: dict = {}
    for p, qs in _by_path(plan):
        coupled: dict = {}  # a -> sum_q K[a, q] ge[q] over the path's q
        for q, pairs in qs:
            cotangent = ge_ref[q * C:(q + 1) * C, :]
            for a, j in pairs:
                coupled[a] = _add(coupled.get(a), kt_ref[j:j + 1, :] * cotangent)
        weight = rt_ref[p * C:(p + 1) * C, :]
        for a, term in coupled.items():
            acc[a] = _add(acc.get(a), weight * term)
    for a in range(plan.m_in):
        tile = acc.get(a)
        if tile is None:  # a component no path reads
            tile = jnp.zeros((C, dhs_ref.shape[0]), jnp.float32)
        dhs_ref[:, a * C:(a + 1) * C] = tile.T


def _dr_kernel(start_ref, count_ref, rcv_ref, gt_ref, hs_ref, kt_ref, drt_ref,
               ge_ref, hst_ref, *, plan: Plan):
    C = plan.channels
    _gather_cotangent(start_ref, count_ref, rcv_ref, gt_ref, ge_ref)
    _transpose_in(hs_ref, hst_ref, plan)
    for p, qs in _by_path(plan):
        tiles, acc = _tiles(hst_ref, _components(qs), C), None
        for q, pairs in qs:
            acc = _add(acc, ge_ref[q * C:(q + 1) * C, :] * _coupled(kt_ref, tiles, pairs))
        drt_ref[p * C:(p + 1) * C, :] = acc


def _dk_kernel(start_ref, count_ref, rcv_ref, gt_ref, hs_ref, rt_ref, dkt_ref,
               ge_ref, hst_ref, *, plan: Plan):
    C = plan.channels
    _gather_cotangent(start_ref, count_ref, rcv_ref, gt_ref, ge_ref)
    _transpose_in(hs_ref, hst_ref, plan)
    for p, qs in _by_path(plan):
        weight, tiles = rt_ref[p * C:(p + 1) * C, :], _tiles(hst_ref, _components(qs), C)
        for q, pairs in qs:
            weighted = weight * ge_ref[q * C:(q + 1) * C, :]
            for a, j in pairs:
                dkt_ref[j:j + 1, :] = jnp.sum(weighted * tiles[a], axis=0, keepdims=True)


# -- the four calls -----------------------------------------------------------------------


def _windows(rcv: Array):
    """Per block of ``BLOCK`` edges: first window's start and the number of
    windows to its highest receiver; the receivers as ``[G, 1, BLOCK]``."""
    blocks = rcv.astype(jnp.int32).reshape(-1, BLOCK)
    first = blocks.min(axis=1) // WINDOW
    count = blocks.max(axis=1) // WINDOW - first + 1
    return first * WINDOW, count, blocks[:, None, :]


# jitted for its trace cache alone: a step holds each kernel up to four times a
# layer (the rules call one another), and a kernel body of a thousand
# equations is traced once a (kind, plan, shapes) instead; inlined, so every
# call site keeps its own scope and pass tag on the device operations
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3), inline=True)
def _call(kind: str, plan: Plan, num_nodes: int, interpret: bool, rcv, *operands):
    """One ``pallas_call``. Operands by kind (``gt`` is ``[S C, N_pad]``):
    out: hs, kt, rt -> out_t; dhs: gt, kt, rt; dr: gt, hs, kt; dk: gt, hs, rt."""
    C, sc = plan.channels, plan.slab * plan.channels
    e = rcv.shape[0]
    n_pad = _padded_nodes(num_nodes)
    f32 = jnp.float32
    edge_rows = lambda width: pl.BlockSpec((BLOCK, width), lambda k, *_: (k, 0))
    edge_cols = lambda height: pl.BlockSpec((height, BLOCK), lambda k, *_: (0, k))
    resident = pl.BlockSpec((sc, n_pad), lambda k, *_: (0, 0))
    specs = {
        "hs": edge_rows(plan.m_in * C), "kt": edge_cols(plan.n_k),
        "rt": edge_cols(plan.n_paths * C), "gt": resident,
    }
    hst = pltpu.VMEM((plan.m_in * C, BLOCK), f32)
    ge = pltpu.VMEM((sc, BLOCK), f32)
    kernel, names, out_spec, out_shape, scratch = {
        "out": (_out_kernel, ("hs", "kt", "rt"), resident, (sc, n_pad), [hst, ge]),
        "dhs": (_dhs_kernel, ("gt", "kt", "rt"), specs["hs"], (e, plan.m_in * C), [ge]),
        "dr": (_dr_kernel, ("gt", "hs", "kt"), specs["rt"], (plan.n_paths * C, e), [ge, hst]),
        "dk": (_dk_kernel, ("gt", "hs", "rt"), specs["kt"], (plan.n_k, e), [ge, hst]),
    }[kind]
    start, count, rcv_blocks = _windows(rcv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(e // BLOCK,),
        in_specs=[pl.BlockSpec((1, 1, BLOCK), lambda k, *_: (k, 0, 0))]
        + [specs[name] for name in names],
        out_specs=out_spec,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(kernel, plan=plan),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=f"fused_tensor_product_{kind}",
    )(start, count, rcv_blocks, *operands)


def _to_gt(g: Array, num_nodes: int) -> Array:
    return jnp.pad(g.T, ((0, 0), (0, _padded_nodes(num_nodes) - num_nodes)))


# Each rule below calls the WRAPPED functions, never a raw ``pallas_call``
# (``fused_scatter._fused``): the outer differentiation of MLIP training then
# meets a call it has a rule for. ``static`` = (plan, num_nodes, interpret).


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def tp_out(static, rcv, hs, kt, rt):
    plan, num_nodes, interpret = static
    out_t = _call("out", plan, num_nodes, interpret, rcv, hs, kt, rt)
    return out_t[:, :num_nodes].T


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def tp_dhs(static, rcv, g, kt, rt):
    plan, num_nodes, interpret = static
    return _call("dhs", plan, num_nodes, interpret, rcv, _to_gt(g, num_nodes), kt, rt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def tp_dr(static, rcv, g, hs, kt):
    plan, num_nodes, interpret = static
    return _call("dr", plan, num_nodes, interpret, rcv, _to_gt(g, num_nodes), hs, kt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def tp_dk(static, rcv, g, hs, rt):
    plan, num_nodes, interpret = static
    return _call("dk", plan, num_nodes, interpret, rcv, _to_gt(g, num_nodes), hs, rt)


def _fwd(fn):
    def fwd(static, rcv, *operands):
        return fn(static, rcv, *operands), routing.saved((rcv, *operands))
    return fwd


def _out_bwd(s, res, g):
    rcv, hs, kt, rt = res
    return None, tp_dhs(s, rcv, g, kt, rt), tp_dk(s, rcv, g, hs, rt), tp_dr(s, rcv, g, hs, kt)


def _dhs_bwd(s, res, c):
    rcv, g, kt, rt = res
    return None, tp_out(s, rcv, c, kt, rt), tp_dk(s, rcv, g, c, rt), tp_dr(s, rcv, g, c, kt)


def _dr_bwd(s, res, c):
    rcv, g, hs, kt = res
    return None, tp_out(s, rcv, hs, kt, c), tp_dhs(s, rcv, g, kt, c), tp_dk(s, rcv, g, hs, c)


def _dk_bwd(s, res, c):
    rcv, g, hs, rt = res
    return None, tp_out(s, rcv, hs, c, rt), tp_dhs(s, rcv, g, c, rt), tp_dr(s, rcv, g, hs, c)


tp_out.defvjp(_fwd(tp_out), _out_bwd)
tp_dhs.defvjp(_fwd(tp_dhs), _dhs_bwd)
tp_dr.defvjp(_fwd(tp_dr), _dr_bwd)
tp_dk.defvjp(_fwd(tp_dk), _dk_bwd)


def fused_tensor_product(plan: Plan, rcv: Array, hs: Array, kt: Array, rt: Array,
                         num_nodes: int, interpret: bool | None = None) -> Array:
    """``[N, S C]``: the product summed at the receivers, no per-edge slab.
    The caller has checked :func:`tensor_product_route`."""
    if interpret is None:
        interpret = routing.interpret_default()
    return tp_out((plan, int(num_nodes), bool(interpret)), rcv, hs, kt, rt)
