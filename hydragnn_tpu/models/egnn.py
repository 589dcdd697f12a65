"""EGNN conv stack (reference ``hydragnn/models/EGCLStack.py:22-300``,
``E_GCL`` layer): E(n)-equivariant message passing.

Per layer:
    m_ij   = edge_mlp([h_i, h_j, ||d_ij||, e_ij])
    pos_i +=  mean_j( d_hat_ij * tanh(coord_mlp(m_ij)) )  [if equivariant,
              skipped on the last layer — EGCLStack.get_conv :46-70]
    h_i    = node_mlp([h_i, sum_j m_ij])

Parity notes: edge vectors are normalized with eps=1.0 (reference calls
``get_edge_vectors_and_lengths(..., normalize=True, eps=1.0)``); messages are
aggregated at the edge *sender* (row) like the reference's
``unsorted_segment_sum(edge_feat, row)``; PBC ``edge_shifts`` flow through the
geometry (EGCLStack supports them, ``:111-131``); feature layers are Identity
(no batch norm). Coordinate updates honor padding via edge masks.

Node rows reach the edges through ``segment.gather`` (``graphs/segment.py``),
four reads a layer: ``h`` at both endpoints (``[N, H] -> [E, H]``) and the
positions at both (``[N, 3] -> [E, 3]``). Forward that is XLA's gather, as
plain indexing emits it. Its transpose is ``segment._sum``, not the
scatter-add autodiff would emit, and an MLIP step transposes every read twice:
in the forces pass (but the first layer's feature reads, which no position
moves) and in the forward pass's parameter gradient (the layers after the
first). With the layer's explicit sums (messages, coordinate update), whose
transposes are ``segment.gather`` again, every node<->edge exchange of the
four passes is one pair. What a transposed read runs on a TPU, by the width of
the rows and collate's certificate for the id array (``batch.seg_hint``; the
route is ``ops/fused_scatter.py::fused_segment_sum``'s, nothing is chosen here):

  [E, H], certificate held    the resident kernel
  [E, H], certificate failed  the tiled kernel (H a multiple of 128), else XLA
  [E, 3], certificate held    the resident kernel, rows lane-padded
  [E, 3], certificate failed  XLA's scatter-add (the tiled form moves whole
                              128-lane rows)

Elsewhere (CPU, ``HYDRAGNN_FUSED_SCATTER=0``) ``_sum`` is
``jax.ops.segment_sum``: the same values as plain indexing. On
``egnn_mlip_mptrj.fill`` (H 128, 7 layers, 16,384 edge slots onto 520 nodes in
the typical bucket) plain indexing left 26 ``[N, 3]`` and 24 ``[N, 128]``
scatter-adds in every step program, 0.14-0.17 ms each of a 16.4 ms step; with
the reads declared the program holds 76 ``fused_segment_sum`` calls where both
certificates hold (26 before) and 63 where the receivers' failed, and runs
13.71 ms of device time a step where it ran 18.69, 1,124 graphs/s where it ran
829-835, a finish-interval p90 of 17.2 ms for 22.1 (one TPU v5e; PERF.md, PR 45).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..config.schema import ModelSpec
from ..graphs.graph import GraphBatch
from ..graphs import segment
from .base import register_conv
from .common import MLP, equivariant_coordinate_update


@register_conv("EGNN")
class EGNNConv(nn.Module):
    spec: ModelSpec
    layer: int
    out_dim: int | None = None

    feature_norm = False  # reference EGCLStack uses Identity feature layers
    # the coordinate gate at all-zero parameters moves nothing (tanh(0) on
    # every edge), which is what a layer without one does: the last layer of an
    # equivariant stack runs in a scanned body (``models/layer_scan.py``) with
    # zeros where the other layers have these subtrees
    inert_at_zero = ("coord_mlp_mlp_0", "coord_mlp_mlp_out")

    @nn.compact
    def __call__(
        self, inv: jax.Array, equiv: jax.Array, batch: GraphBatch, train: bool = False
    ):
        spec = self.spec
        hidden = spec.hidden_dim
        out_dim = self.out_dim or hidden
        last_layer = self.layer >= spec.num_conv_layers - 1
        # reference default: equivariance toggles coordinate updates, off on
        # the last layer (EGCLStack._init_conv :46-70)
        equivariant = bool(spec.equivariance) and not last_layer

        # node rows read through segment.gather (module docstring): the
        # transposes are the kernel's row sum in both derivative passes
        rows = lambda x, ids: segment.gather(x, ids, hints=batch)
        vec = rows(equiv, batch.receivers) - rows(equiv, batch.senders) + batch.edge_shifts
        lengths = jnp.sqrt(jnp.sum(vec * vec, axis=-1, keepdims=True) + 1e-18)
        coord_diff = vec / (lengths + 1.0)  # normalize=True, eps=1.0

        feats = [rows(inv, batch.senders), rows(inv, batch.receivers), lengths]
        if spec.edge_dim and batch.edge_attr.shape[1]:
            feats.append(batch.edge_attr)
        edge_in = jnp.concatenate(feats, axis=-1)
        m = MLP(
            features=(hidden, hidden),
            activation=spec.activation,
            act_last=True,
            name="edge_mlp",
        )(edge_in)

        if equivariant:
            equiv = equiv + equivariant_coordinate_update(
                m, coord_diff, batch.senders, batch.edge_mask, batch.num_nodes,
                hidden, tanh_bound=True, name_prefix="coord_mlp", hints=batch,
            )

        m_masked = m * batch.edge_mask[:, None]
        agg = segment.segment_sum(m_masked, batch.senders, batch.num_nodes, hints=batch)
        h = MLP(
            features=(hidden, out_dim),
            activation=spec.activation,
            name="node_mlp",
        )(jnp.concatenate([inv, agg], axis=-1))
        return h, equiv
