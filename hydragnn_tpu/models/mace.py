"""MACE conv stack — higher-order equivariant message passing.

Batatia, Kovacs, Simm, Ortner, Csanyi, *MACE: Higher Order Equivariant Message
Passing Neural Networks for Fast and Accurate Force Fields* (arXiv:2206.07697),
at the sizes of MACE-MP-0 (arXiv:2401.00096); upstream HydraGNN's ``MACEStack``
(``hydragnn/models/MACEStack.py:74-577``). With C channels, Y_lm the real
spherical harmonics of the edge direction to ``max_ell``, R(r) a bias-free
radial MLP on Bessel functions x polynomial cutoff (p = 5), z_i the species:

1. h_i^(0) = W_embed[z_i]                                        (C x 0e)
2. interaction t:   h~ = Linear_up(h);  per edge j->i and even path (l1, l2, l3)
       m_ij[c, l3 m3] = R_path,c(r_ij) sum_{m1 m2} C^{l3 m3}_{l1 m1, l2 m2}
                        h~_j[c, l1 m1] Y_{l2 m2}(r^_ij)        (channel-wise)
   A_i = Linear_l3(sum_j m_ij / avg_num_neighbors)   over (paths x C), l3 <= max_ell
   sc_i = W_skip[z_i] h_i                            (per-element linear, the skip)
3. product basis:   B_i[c, LM] = sum_{nu <= correlation} sum_eta W^(nu)[z_i, eta, c]
       sum U^(nu)_{LM, eta; l1 m1 ... l_nu m_nu} prod_xi A_i[c, l_xi m_xi]
   L <= node_max_ell (L = 0 in the last layer); U^(nu) is
   ``harmonics.symmetric_basis``: as many eta as the symmetric subspace has
   dimensions.  h_i^(t+1) = Linear(B_i) + sc_i
4. readouts: every layer's scalars reach the heads
   (``collect_layer_outputs``); the ``layer_readout`` node head
   (``models/base.py``) is the published one, w_1 . h^(1) + MLP(h^(2)).

Layout. Every gather and every segment sum moves rank-2 rows ``[rows, M * C]``
(component index m major, channel minor): a rank-3 ``[E, 3, F]`` gather or sum
cost 3.5-3.85 x its rank-2 form inside PaiNN's step on a v5e (``T(4,128)``
tiling; 2.6-2.7 x jitted alone; PERF.md, PR 25). Sender features are gathered
as ``[E, M_in C]``. On a TPU the product of those rows with the couplings
``K^T`` ``[n_k, E]`` and the radial weights ``R^T`` ``[P C, E]`` and its sum at
the receivers are one Mosaic kernel a pass (``ops/fused_tensor_product.py``):
blocks of 128 receiver-sorted edges, EDGE-MINOR in VMEM (channels on sublanes,
edges on lanes, so a per-edge scalar broadcasts for nothing), summed through
128-node windows of a resident ``[S C, N]``; the per-edge path outputs
``[E, S C]`` (S = sum_paths (2 l3 + 1): 16, then 40 from 0e + 1o inputs at
``max_ell`` 3; 1.17 GB in the cell's worst-case bucket) exist in no pass
(PERF.md, PR 28). Off the TPU, and where the kernels' static route says so,
the XLA path builds that slab: the product on ``[E, S, C]`` with S a whole
number of 8-row tiles, one broadcast multiply-add a sender component (forty
lane-dense ``[E, C]`` blocks written one a coupling entry compiled 2.5 x longer
and traced twice as long; PERF.md, PR 27), then XLA's scatter. The
contraction runs component-major, ``[D D, N C]``, as one matmul with the
constant U and a multiply by the third copy.

Departures kept on purpose: no E0 table and no scale/shift of the energy (the
trainer's targets decide those); features between layers travel as
``[N, sum_l (2l+1), C]`` (``base.py``'s contract; reshaped on entry);
``W_embed``, ``W_skip`` and ``W^(nu)`` have a row for every Z <= 118 and are
indexed by the raw atomic number ``batch.z``, not by a table of the elements
present; Bessel frequencies are fixed at n pi.
"""

from __future__ import annotations

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..config.schema import ModelSpec
from ..graphs.graph import GraphBatch
from ..ops import fused_tensor_product as ftp
from .base import register_conv
from .harmonics import (
    coupling_paths,
    coupling_tensor,
    irreps_dim,
    spherical_harmonics,
    symmetric_basis,
)
from .radial import ChebyshevBasis, GaussianSmearing, polynomial_cutoff, sinc_expansion

NUM_ELEMENTS = 119  # Z in 0..118; index 0 absorbs non-integer/unknown types
RADIAL_MLP = (64, 64, 64)  # MACE's default at every channel count


def _normal(fan_in: int):
    return nn.initializers.normal(1.0 / math.sqrt(fan_in))


def _by_species(table: jax.Array, z: jax.Array) -> jax.Array:
    """Rows of a per-element table ``[Z, ...]`` by species -> ``[N, prod(...)]``:
    gathered as rank-2 rows."""
    return table.reshape(table.shape[0], -1)[z]


def _per_l(x: jax.Array, kernels: list, C: int) -> jax.Array:
    """Per-l channel mixing of ``[N, M * C]`` (m major): block l, rows
    (n, m), times ``kernels[l]`` ``[C, C_out]``."""
    n, out, off = x.shape[0], [], 0
    for l, w in enumerate(kernels):
        m = 2 * l + 1
        block = x[:, off * C : (off + m) * C].reshape(n * m, C)
        out.append((block @ w).reshape(n, m * w.shape[1]))
        off += m
    return jnp.concatenate(out, axis=1)


class Harmonics(nn.Module):
    """Edge vector -> (length ``[E]``, Y ``[E, (max_ell+1)^2]``)."""

    max_ell: int

    def __call__(self, batch: GraphBatch):
        vec = batch.pos[batch.receivers] - batch.pos[batch.senders] + batch.edge_shifts
        dist = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + 1e-18)
        return dist, jnp.concatenate(spherical_harmonics(vec, self.max_ell), axis=-1)


class Radial(nn.Module):
    """Edge length -> one weight a path a channel, ``[E, paths * C]``; exactly
    zero on padded edges (no bias anywhere, the basis is masked)."""

    spec: ModelSpec
    paths: int

    @nn.compact
    def __call__(self, dist: jax.Array, edge_mask: jax.Array) -> jax.Array:
        spec = self.spec
        C = spec.hidden_dim
        r_max = float(spec.radius or 5.0)
        num_radial = spec.num_radial or 8
        kind = (spec.radial_type or "bessel").lower()
        if kind == "bessel":
            rbf = math.sqrt(2.0 / r_max) * sinc_expansion(dist, num_radial, r_max)
        elif kind == "chebyshev":
            rbf = ChebyshevBasis(num_basis=num_radial, cutoff=r_max)(dist)
        elif kind == "gaussian":
            rbf = GaussianSmearing(stop=r_max, num_gaussians=num_radial)(dist)
        else:
            raise ValueError(f"unknown radial_type '{kind}'")
        h = rbf * (polynomial_cutoff(dist, r_max, p=5) * edge_mask)[:, None]
        for i, width in enumerate(RADIAL_MLP):
            h = nn.silu(nn.Dense(width, use_bias=False, name=f"dense_{i}")(h))
        return h @ self.param("dense_out", _normal(h.shape[1]), (h.shape[1], self.paths * C))


@functools.lru_cache(maxsize=None)
def couplings(paths: tuple, m_in: int, n_harmonics: int, channels: int):
    """``cmat[a, l2 m2, q]`` = C[m1, m2, m3] for sender component a = (l1, m1)
    and output q = (path, m3); the fused kernels' plan of its zero pattern
    (``ops/fused_tensor_product.py``); and ``cmat``'s nonzero (a, q) columns
    ``[n_k, l2 m2]``, one a row of the kernels' ``K^T``."""
    slab = sum(2 * l3 + 1 for _, _, l3 in paths)
    cmat, path_of, q = np.zeros((m_in, n_harmonics, slab)), [], 0
    for p, (l1, l2, l3) in enumerate(paths):
        cg = coupling_tensor(l1, l2, l3)
        for m1 in range(2 * l1 + 1):
            cmat[l1 * l1 + m1, l2 * l2 : (l2 + 1) ** 2, q : q + 2 * l3 + 1] = cg[m1]
        path_of += [p] * (2 * l3 + 1)
        q += 2 * l3 + 1
    plan = ftp.make_plan(np.abs(cmat).sum(axis=1) > 0, tuple(path_of), channels)
    return cmat, plan, np.stack([cmat[a, :, q] for a, q in ftp.plan_pairs(plan)])


class TensorProduct(nn.Module):
    """Sender features x edge harmonics, weighted by the radial MLP, summed at
    the receivers: ``[N, M_in C] -> [N, S C]``, the S path outputs a channel
    ordered (path, m3). With K_a = Y @ cmat[a] ``[E, S]``:

        out[n, q, c] = sum_{e -> n} R[e, path(q), c] sum_a K_a[e, q] h[snd_e, a, c]

    On a TPU (``ops/fused_tensor_product.py``'s static route) product and sum
    are one Mosaic kernel a pass over blocks of receiver-sorted edges and no
    array holds S C elements an edge; the gather, ``Y @ cmat`` and the radial
    MLP stay in XLA. Elsewhere the XLA path below: the product on
    ``[E, S, C]`` (S = 16 or 40, whole tiles of 8) as one broadcast
    multiply-add a sender component, then XLA's scatter."""

    paths: tuple
    channels: int

    def __call__(self, h: jax.Array, Y: jax.Array, R: jax.Array, batch: GraphBatch):
        C, paths = self.channels, self.paths
        m_in, e = h.shape[1] // C, Y.shape[0]
        cmat, plan, pair_rows = couplings(paths, m_in, Y.shape[1], C)
        hs = h[batch.senders]
        if ftp.enabled() and ftp.tensor_product_route(plan, e, batch.num_nodes, h.dtype) is None:
            # K^T [n_k, E] = [n_k, 16] @ [16, E]; R^T [P C, E]
            return ftp.fused_tensor_product(
                plan, batch.receivers, hs, jnp.asarray(pair_rows, Y.dtype) @ Y.T, R.T,
                batch.num_nodes)
        hs = hs.reshape(e, m_in, C)
        acc = 0.0
        for a in range(m_in):
            acc = acc + (Y @ jnp.asarray(cmat[a], Y.dtype))[:, :, None] * hs[:, a : a + 1, :]
        R = R.reshape(e, len(paths), C)
        weights = jnp.concatenate(
            [jnp.broadcast_to(R[:, p : p + 1, :], (e, 2 * l3 + 1, C))
             for p, (_, _, l3) in enumerate(paths)], axis=1)
        return jax.ops.segment_sum((acc * weights).reshape(e, plan.slab * C), batch.receivers,
                                   num_segments=batch.num_nodes)


class InteractionLinear(nn.Module):
    """The interaction's three channel mixings: ``up`` before the product,
    ``mix`` over (paths x C) after the sum, ``skip`` by species."""

    paths: tuple
    channels: int
    l_in: int
    l_skip: int
    out_channels: int

    def setup(self):
        C = self.channels
        self.up_w = [self.param(f"up_w{l}", _normal(C), (C, C)) for l in range(self.l_in + 1)]
        self.mix_w = [
            self.param(f"mix_w{l3}", _normal(n * C), (n * C, C))
            for l3, n in sorted(_paths_per_l3(self.paths).items())
        ]
        self.skip_w = [
            self.param(f"skip_w{l}", _normal(C), (NUM_ELEMENTS, C, self.out_channels))
            for l in range(self.l_skip + 1)
        ]

    def up(self, h):
        return _per_l(h, self.up_w, self.channels)

    def mix(self, agg):
        """``[N, S C]``, S ordered (path, m3) -> A ``[N, D C]`` (l3, m3)."""
        n, C = agg.shape[0], self.channels
        agg = agg.reshape(n, -1, C)
        starts, q = [], 0
        for _, _, l3 in self.paths:
            starts.append(q)
            q += 2 * l3 + 1
        out = []
        for l3, w in zip(sorted(_paths_per_l3(self.paths)), self.mix_w):
            m = 2 * l3 + 1
            block = jnp.concatenate(
                [agg[:, starts[p] : starts[p] + m] for p, path in enumerate(self.paths)
                 if path[2] == l3], axis=2)  # [N, m, paths_l3 C]
            out.append((block.reshape(n * m, -1) @ w).reshape(n, m * C))
        return jnp.concatenate(out, axis=1)

    def skip(self, h, z):
        n, C, out = h.shape[0], self.channels, []
        for l, table in enumerate(self.skip_w):
            m = 2 * l + 1
            w = _by_species(table, z).reshape(n, C, self.out_channels)
            block = h[:, l * l * C : (l + 1) ** 2 * C].reshape(n, m, C)
            out.append(jnp.einsum("nmc,ncd->nmd", block, w).reshape(n, -1))
        return jnp.concatenate(out, axis=1)


def _paths_per_l3(paths: tuple) -> dict:
    count = {}
    for _, _, l3 in paths:
        count[l3] = count.get(l3, 0) + 1
    return count


class Interaction(nn.Module):
    spec: ModelSpec
    paths: tuple
    l_in: int
    l_skip: int
    out_channels: int

    @nn.compact
    def __call__(self, h, z, Y, R, batch: GraphBatch):
        C = self.spec.hidden_dim
        linear = InteractionLinear(self.paths, C, self.l_in, self.l_skip,
                                   self.out_channels, name="linear")
        agg = TensorProduct(self.paths, C, name="tensor_product")(linear.up(h), Y, R, batch)
        A = linear.mix(agg / float(self.spec.avg_num_neighbors or 1.0))
        return A, linear.skip(h, z)


@functools.lru_cache(maxsize=None)
def contraction_constants(max_ell: int, correlation: int, out_ell: int):
    """The constants of the symmetric contraction over D = (max_ell+1)^2
    components, target rows (L, M) for L <= out_ell:

    ``lin`` [rows_1, D]      degree 1, rows (L, M, eta)
    ``quad`` [rows_2 + rows_3 D, D D]   on the products x_i x_j: degree 2 rows
          (L, M, eta), then degree 3 rows (L, M, eta, k) whose result is
          still to be multiplied by x_k and summed over k
    ``spread`` [rows, weights] 0/1: each row's weight (L, nu, eta)
    ``gather`` [sum_L (2L+1), rows] 0/1: each row's target component
    with rows = rows_1 + rows_2 + rows_3, and ``{L: weights}``."""
    D = irreps_dim(max_ell)
    n_w = {L: sum(symmetric_basis(max_ell, nu, L).shape[0] for nu in range(1, correlation + 1))
           for L in range(out_ell + 1)}
    w_off = {L: sum(n_w[l] for l in range(L)) for L in n_w}
    per_degree, spread, gather = [], [], []
    for nu in range(1, correlation + 1):
        rows = []
        for L in range(out_ell + 1):
            U = symmetric_basis(max_ell, nu, L)
            eta_off = sum(symmetric_basis(max_ell, k, L).shape[0] for k in range(1, nu))
            for M in range(2 * L + 1):
                for eta in range(U.shape[0]):
                    rows.append(U[eta, M])
                    s_row = np.zeros(sum(n_w.values()))
                    s_row[w_off[L] + eta_off + eta] = 1.0
                    g_col = np.zeros((out_ell + 1) ** 2)
                    g_col[L * L + M] = 1.0
                    spread.append(s_row)
                    gather.append(g_col)
        per_degree.append(np.stack(rows) if rows else np.zeros((0,) + (D,) * nu))
    lin = per_degree[0]
    quad = [d.reshape(d.shape[0], D * D) for d in per_degree[1:2]]
    if correlation >= 3:  # rows (row, k) over (i, j)
        quad.append(per_degree[2].transpose(0, 3, 1, 2).reshape(-1, D * D))
    quad = np.concatenate(quad) if quad else np.zeros((0, D * D))
    counts = tuple(d.shape[0] for d in per_degree)
    return lin, quad, np.stack(spread), np.stack(gather, axis=1), counts, n_w


class Contraction(nn.Module):
    """A ``[N, D C]`` -> B ``[sum_L (2L+1), N C]`` (component-major): one
    matmul of the constant U with the products x_i x_j, the third copy
    multiplied in after it."""

    max_ell: int
    correlation: int
    out_ell: int
    channels: int

    @nn.compact
    def __call__(self, A: jax.Array, z: jax.Array) -> jax.Array:
        n, C, D = A.shape[0], self.channels, irreps_dim(self.max_ell)
        lin, quad, spread, gather, counts, n_w = contraction_constants(
            self.max_ell, self.correlation, self.out_ell)
        total_w = sum(n_w.values())
        weights = self.param("weights", _normal(total_w), (NUM_ELEMENTS, total_w, C))
        const = lambda a: jnp.asarray(a, A.dtype)
        x = A.reshape(n, D, C).transpose(1, 0, 2).reshape(D, n * C)
        P = [const(lin) @ x]
        if self.correlation >= 2:
            pairs = (x[:, None, :] * x[None, :, :]).reshape(D * D, n * C)
            Q = const(quad) @ pairs
            P.append(Q[: counts[1]])
            if self.correlation >= 3:
                P.append(jnp.sum(Q[counts[1] :].reshape(counts[2], D, n * C) * x[None], axis=1))
        w = _by_species(weights, z).reshape(n, total_w, C).transpose(1, 0, 2)
        w = const(spread) @ w.reshape(total_w, n * C)
        return const(gather) @ (w * jnp.concatenate(P, axis=0))


class ProductLinear(nn.Module):
    """B ``[sum_L (2L+1), N C]`` -> ``[N, sum_L (2L+1) C_out]`` (node-major)."""

    out_ell: int
    channels: int
    out_channels: int

    @nn.compact
    def __call__(self, B: jax.Array) -> jax.Array:
        C, out = self.channels, []
        n = B.shape[1] // C
        for L in range(self.out_ell + 1):
            w = self.param(f"w{L}", _normal(C), (C, self.out_channels))
            block = B[L * L : (L + 1) ** 2].reshape((2 * L + 1) * n, C) @ w
            out.append(block.reshape(2 * L + 1, n, self.out_channels))
        return jnp.concatenate(out, axis=0).transpose(1, 0, 2).reshape(n, -1)


class ProductBasis(nn.Module):
    max_ell: int
    correlation: int
    out_ell: int
    channels: int
    out_channels: int

    @nn.compact
    def __call__(self, A, z):
        B = Contraction(self.max_ell, self.correlation, self.out_ell, self.channels,
                        name="contraction")(A, z)
        return ProductLinear(self.out_ell, self.channels, self.out_channels, name="linear")(B)


def _layer_sizes(spec: ModelSpec, layer: int, first: bool):
    """(max_ell, l_in, out_ell, correlation) of conv ``layer``."""
    max_ell = 1 if spec.max_ell is None else spec.max_ell
    node_ell = 1 if spec.node_max_ell is None else spec.node_max_ell
    correlation = 2 if spec.correlation is None else spec.correlation
    if isinstance(correlation, (list, tuple)):
        correlation = int(correlation[min(layer, len(correlation) - 1)])
    if not 1 <= correlation <= 3:
        raise ValueError(f"MACE correlation {correlation}: orders 1..3 are built")
    last = layer >= spec.num_conv_layers - 1
    return max_ell, 0 if first else node_ell, 0 if last else node_ell, correlation


@register_conv("MACE")
class MACEConv(nn.Module):
    spec: ModelSpec
    layer: int
    out_dim: int | None = None

    feature_norm = False  # reference: no batch norm between MACE layers
    stack_activation = False  # reference forward applies no activation either
    collect_layer_outputs = True  # heads see all layers' scalars

    @staticmethod
    def describe(spec: ModelSpec) -> str:
        """One line at model build: paths a layer, eta a (nu, L), species."""
        parts = []
        for layer in range(spec.num_conv_layers):
            max_ell, l_in, out_ell, corr = _layer_sizes(spec, layer, layer == 0)
            eta = {f"nu{nu}L{L}": symmetric_basis(max_ell, nu, L).shape[0]
                   for L in range(out_ell + 1) for nu in range(1, corr + 1)}
            parts.append(f"layer {layer}: {len(coupling_paths(l_in, max_ell, max_ell))} paths, "
                         f"eta {eta}")
        return (f"MACE {spec.hidden_dim} channels, {NUM_ELEMENTS} species rows; "
                + "; ".join(parts))

    @nn.compact
    def __call__(
        self, inv: jax.Array, equiv: jax.Array, batch: GraphBatch, train: bool = False
    ):
        spec = self.spec
        C = spec.hidden_dim
        out_c = self.out_dim or C
        # the first layer receives raw positions [N, 3]; later layers the
        # packed irreps [N, sum(2l+1), C] of the layer before
        first = equiv.ndim == 2
        max_ell, l_in, out_ell, correlation = _layer_sizes(spec, self.layer, first)
        n = inv.shape[0]
        # batch.z carries RAW atomic numbers captured before feature
        # normalization
        z = jnp.clip(batch.z.astype(jnp.int32), 0, NUM_ELEMENTS - 1)
        if not first:
            h = jnp.concatenate([inv, equiv.reshape(n, -1)], axis=1)
        elif self.layer == 0:
            h = nn.Embed(NUM_ELEMENTS, C, name="node_embedding")(z)
        else:  # a conv-type head reads the stack's scalars
            h = nn.Dense(C, use_bias=False, name="node_embedding")(inv)

        paths = tuple(coupling_paths(l_in, max_ell, max_ell))
        dist, Y = Harmonics(max_ell, name="harmonics")(batch)
        R = Radial(spec, len(paths), name="radial")(dist, batch.edge_mask)
        A, sc = Interaction(spec, paths, l_in, min(l_in, out_ell), out_c,
                            name="interaction")(h, z, Y, R, batch)
        out = ProductBasis(max_ell, correlation, out_ell, C, out_c,
                           name="product_basis")(A, z)
        out = jnp.concatenate([out[:, : sc.shape[1]] + sc, out[:, sc.shape[1] :]], axis=1)
        if out_ell == 0:
            return out, batch.pos  # scalars only (reference last layer)
        return out[:, :out_c], out[:, out_c:].reshape(n, -1, out_c)
