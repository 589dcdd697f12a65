"""PaiNN conv stack (reference ``hydragnn/models/PAINNStack.py:27-352``):
polarizable atom interaction network with scalar [N, F] + vector [N, 3, F]
channels.

Per layer (PainnMessage + PainnUpdate + output embeds, ``get_conv :76-120``):
  message: filter = W(sinc_rbf(d)) * cos_cutoff(d) (optionally * edge filter);
           (gate_v | gate_edge | msg_s) = split(filter * MLP(s)[other end]);
           v_msg = v[other] * gate_v + gate_edge * d_hat;  residual sum-agg.
  update:  Uv, Vv = channel linears on v; (a_vv | a_sv | a_ss) =
           MLP([||Vv||, s]); dv = a_vv * Uv; ds = a_sv * <Uv, Vv> + a_ss.
  embed:   s -> Linear-Tanh-Linear to output dim; v -> channel Linear
           (skipped on the last layer, which drops the vector update too).

Vector-channel linears are bias-free: the reference uses ``nn.Linear`` with
bias on [N, 3, F] tensors, which adds the same offset to every spatial
component and silently breaks rotation equivariance — a reference bug we do
not reproduce. Aggregation is at the edge *sender* (reference ``index_add_(0,
edge[:, 0], ...)``); v initializes to zeros at the first layer
(``_embedding :190``).

Axis order inside the message: between blocks ``v`` is [N, 3, F] (the stack's
``equiv`` contract, ``PainnUpdate``, checkpoints), but ``PainnMessage`` views
it as a component-major rank-2 slab [N, 3F] (lanes 0..F-1 hold x, F..2F-1 y,
2F..3F-1 z), gathers [E, 3F], builds the vector message in [E, 3F] and sums it
rank-2, so no gather, scatter-add or edge-sized array in any of the four AD
passes of an MLIP step has rank 3. On the TPU a rank-3 [E, 3, F] operand is
tiled T(4,128): 3 sublanes padded to 4, and a slow scatter. In one traced
step at E = 325,760, N = 21,512, F = 128 the scatter-add of [E, 3, 128] onto
[N, 3, 128] took 25.2 ms and the scatter-add of the same rows as [E, 384]
6.55 ms (PERF.md, PR 25). The [N, 3, F] view returns at the message's exit.

The exchanges themselves are one pair (``graphs/segment.py``): both sums are
``segment.segment_sum`` and both gathers ``segment.gather``, whose VJP is that
sum, so in all four passes every ``[E, 3F] -> [N, 3F]`` reduction (the two
explicit ones and each gather's transpose) is the ``fused_segment_sum`` kernel
on a TPU: at F = 128 its tiled form (``ops/fused_scatter.py``), and a
row-major operand, so XLA keeps the whole message row-major and the layout
copies of ``[E, 384]`` that sat between edge-minor elementwise fusions and
row-major scatters are gone with the scatters (PERF.md, PR 34). The first
block of a stack without an embedding runs at F = 1; its ``[E, 3]`` sums stay
XLA's (the tiled form moves whole 128-lane rows).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..config.schema import ModelSpec
from ..graphs.graph import GraphBatch
from ..graphs import segment
from .base import register_conv
from .radial import cosine_cutoff, sinc_expansion


class PainnMessage(nn.Module):
    node_size: int
    num_radial: int
    cutoff: float
    use_edge_attr: bool

    @nn.compact
    def __call__(self, s, v, batch: GraphBatch, dist, unit_vec):
        ns = self.node_size
        filter_w = nn.Dense(ns * 3, name="filter_layer")(
            sinc_expansion(dist, self.num_radial, self.cutoff)
        )
        filter_w = filter_w * cosine_cutoff(dist, self.cutoff)[:, None]
        if self.use_edge_attr and batch.edge_attr.shape[1]:
            ef = nn.Dense(ns, name="edge_filter_0")(batch.edge_attr)
            ef = nn.silu(ef)
            ef = nn.Dense(ns * 3, name="edge_filter_1")(ef)
            filter_w = filter_w * ef

        scalar_out = nn.Dense(ns, name="scalar_mlp_0")(s)
        scalar_out = nn.silu(scalar_out)
        scalar_out = nn.Dense(ns * 3, name="scalar_mlp_1")(scalar_out)
        # "other" end features; segment.gather: a gather whose transpose is the
        # kernel's row sum in every derivative pass
        filter_out = filter_w * segment.gather(scalar_out, batch.receivers, hints=batch)

        # padded edges carry mask 0: masking the gates once masks all three parts
        filter_out = filter_out * batch.edge_mask[:, None]
        gate_v, gate_edge, msg_s = jnp.split(filter_out, 3, axis=-1)

        # vector channel as a component-major [., 3F] slab (module docstring)
        v2 = v.reshape(v.shape[0], 3 * ns)
        v_msg = segment.gather(v2, batch.receivers, hints=batch) * jnp.concatenate(
            [gate_v] * 3, axis=-1)
        v_msg = v_msg + jnp.concatenate(
            [gate_edge * unit_vec[:, c : c + 1] for c in range(3)], axis=-1
        )

        ds = segment.segment_sum(msg_s, batch.senders, batch.num_nodes, hints=batch)
        dv = segment.segment_sum(v_msg, batch.senders, batch.num_nodes, hints=batch)
        return s + ds, (v2 + dv).reshape(v.shape)


class PainnUpdate(nn.Module):
    node_size: int
    last_layer: bool

    @nn.compact
    def __call__(self, s, v):
        ns = self.node_size
        # bias-free channel mixes keep rotation equivariance exact
        Uv = nn.Dense(ns, use_bias=False, name="update_U")(v)
        Vv = nn.Dense(ns, use_bias=False, name="update_V")(v)
        Vv_norm = jnp.sqrt(jnp.sum(Vv * Vv, axis=1) + 1e-16)
        h = jnp.concatenate([Vv_norm, s], axis=-1)
        h = nn.Dense(ns, name="update_mlp_0")(h)
        h = nn.silu(h)
        out_mult = 2 if self.last_layer else 3
        h = nn.Dense(ns * out_mult, name="update_mlp_1")(h)
        inner = jnp.sum(Uv * Vv, axis=1)  # [N, ns]
        if self.last_layer:
            a_sv, a_ss = jnp.split(h, 2, axis=-1)
            return s + a_sv * inner + a_ss, v
        a_vv, a_sv, a_ss = jnp.split(h, 3, axis=-1)
        return s + a_sv * inner + a_ss, v + a_vv[:, None, :] * Uv


@register_conv("PAINN")
class PaiNNConv(nn.Module):
    spec: ModelSpec
    layer: int
    out_dim: int | None = None

    feature_norm = False  # reference PAINNStack uses Identity feature layers

    @nn.compact
    def __call__(
        self, inv: jax.Array, equiv: jax.Array, batch: GraphBatch, train: bool = False
    ):
        spec = self.spec
        out_dim = self.out_dim or spec.hidden_dim
        ns = inv.shape[-1]
        last_layer = self.layer >= spec.num_conv_layers - 1

        # first layer receives positions as `equiv`; vector channel starts 0
        if equiv.ndim == 2:
            v = jnp.zeros((batch.num_nodes, 3, ns), inv.dtype)
        else:
            v = equiv

        vec = batch.pos[batch.receivers] - batch.pos[batch.senders] + batch.edge_shifts
        dist = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + 1e-18)
        unit_vec = vec / dist[:, None]

        s, v = PainnMessage(
            node_size=ns,
            num_radial=spec.num_radial or 6,
            cutoff=float(spec.radius or 5.0),
            use_edge_attr=bool(spec.edge_dim),
            name="message",
        )(inv, v, batch, dist, unit_vec)
        s, v = PainnUpdate(node_size=ns, last_layer=last_layer, name="update")(s, v)

        # size embeddings (reference node_embed_out / vec_embed_out)
        s = nn.Dense(out_dim, name="node_embed_0")(s)
        s = jnp.tanh(s)
        s = nn.Dense(out_dim, name="node_embed_1")(s)
        if not last_layer:
            v = nn.Dense(out_dim, use_bias=False, name="vec_embed")(v)
        return s, v
