"""GPS global attention (reference ``hydragnn/globalAtt/gps.py:32-159``):
every conv layer becomes  local MPNN + per-graph multi-head self-attention,
each with residual + norm, combined and passed through an MLP block.

TPU redesign of the reference's ``to_dense_batch`` + ``nn.MultiheadAttention``
/ ``PerformerAttention`` pair (``gps.py:55-67,126-133``):

* ``multihead``: nodes scatter into static dense blocks ``[G, N_max, C]``
  (``N_max`` = ``spec.max_graph_nodes``, derived from the dataset at config
  time), attention runs per graph — O(Σ nᵢ²) like the reference, not O((ΣN)²)
  over the padded batch. Graphs that outgrow ``N_max`` at inference flip the
  whole batch, in-program, to an exact flat masked-attention fallback.
* ``performer``: FAVOR+ linear attention computed directly on the flat node
  array — the per-graph softmax-kernel statistics are two ``segment_sum``s,
  so cost is O(N · m · d) with zero densification. This is the option for
  graphs where even per-graph dense attention is too big.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

import dataclasses

from ..config.schema import EDGE_MODELS, ModelSpec
from ..graphs.graph import GraphBatch
from ..graphs import segment
from .base import CONV_REGISTRY
from .common import SYNC_BN_AXIS, MaskedBatchNorm, get_activation


def _positions_in_graph(batch: GraphBatch, n_max: int):
    """Per-node (graph_id, slot) coordinates for dense-block scatter/gather.
    Real nodes of a graph are contiguous, so slot = node_id − graph_start."""
    starts = jnp.cumsum(batch.n_node) - batch.n_node  # [G]
    slot = jnp.arange(batch.num_nodes) - starts[batch.batch]
    return jnp.clip(slot, 0, n_max - 1)


class GraphMultiheadAttention(nn.Module):
    """Self-attention restricted to nodes of the same graph.

    ``n_max > 0`` enables the dense-block path; otherwise (or when a graph
    exceeds ``n_max`` at runtime) the exact flat masked path runs.
    """

    channels: int
    heads: int
    n_max: int = 0
    ring: bool = False  # rotate K/V shards over the mesh (giant graphs)

    def _flat_attention(self, q, k, v, batch: GraphBatch):
        Dh = q.shape[-1]
        logits = jnp.einsum("nhd,mhd->hnm", q, k) / jnp.sqrt(float(Dh))
        same_graph = batch.batch[:, None] == batch.batch[None, :]
        valid = same_graph & (batch.node_mask[None, :] > 0)
        with jax.named_scope("softmax"):
            logits = jnp.where(valid[None, :, :], logits, -1e9)
            attn = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("hnm,mhd->nhd", attn, v)

    def _dense_attention(self, q, k, v, batch: GraphBatch):
        """Scatter to [G, n_max, H, Dh] blocks, per-graph softmax attention,
        gather back. Padded/overflow slots carry zero and are masked."""
        G = batch.num_graphs
        n_max = self.n_max
        Dh = q.shape[-1]
        slot = _positions_in_graph(batch, n_max)
        gid = batch.batch

        def to_dense(x):
            buf = jnp.zeros((G, n_max) + x.shape[1:], x.dtype)
            return buf.at[gid, slot].set(x * batch.node_mask[:, None, None])

        qd, kd, vd = to_dense(q), to_dense(k), to_dense(v)
        valid = jnp.arange(n_max)[None, :] < batch.n_node[:, None]  # [G, n_max]
        logits = jnp.einsum("gnhd,gmhd->ghnm", qd, kd) / jnp.sqrt(float(Dh))
        # the dense-block path itself is chosen at trace time off the
        # collate-certified bound (batch.meta.max_n_node below); the fused
        # kernel collapses its mask→max→exp→sum→divide per-row chain into
        # one Pallas pass (A/B: HYDRAGNN_FUSED_SOFTMAX, exact — rows are
        # independent, so no layout contract / fallback cond is needed). A
        # grid step takes a VMEM-sized block of one graph's H x n_max rows,
        # and the key mask is handed over, and kept, at [G, 1, 1, n_max]:
        # the kernel picks a graph's keys by the grid's graph index
        from ..ops import fused_softmax

        with jax.named_scope("softmax"):
            if fused_softmax._auto_enabled():
                attn = fused_softmax.fused_masked_softmax(
                    logits, valid[:, None, None, :]
                )
            else:
                logits = jnp.where(valid[:, None, None, :], logits, -1e9)
                attn = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("ghnm,gmhd->gnhd", attn, vd)
        return out[gid, slot] * batch.node_mask[:, None, None]

    @nn.compact
    def __call__(self, h: jax.Array, batch: GraphBatch, train: bool = False):
        N = h.shape[0]
        H = self.heads
        Dh = self.channels // H
        assert self.channels % H == 0, "hidden_dim must divide global_attn_heads"
        q = nn.Dense(self.channels, name="q")(h).reshape(N, H, Dh)
        k = nn.Dense(self.channels, name="k")(h).reshape(N, H, Dh)
        v = nn.Dense(self.channels, name="v")(h).reshape(N, H, Dh)
        if self.ring:
            # giant-graph path: K/V shards rotate around the mesh ring with
            # an online softmax — O(N/D) peak memory, exact results. The user
            # asked for ring explicitly, so never silently hand them the
            # O(N²) flat path that defeats the point: an indivisible N is an
            # error (pad the bucket node count to a mesh multiple), and a
            # missing mesh warns loudly before degrading.
            from ..parallel.ring_attention import get_global_mesh, ring_attention

            mesh = get_global_mesh()
            if mesh is not None:
                ring_dev = mesh.shape["data"]
                if N % ring_dev:
                    raise ValueError(
                        f"global_attn_type 'ring' needs the padded node count "
                        f"({N}) divisible by the mesh data axis ({ring_dev}); "
                        f"pad the bucket n_node to a multiple of {ring_dev}"
                    )
                out = ring_attention(
                    q, k, v, batch.batch, batch.node_mask, mesh
                )
                return nn.Dense(self.channels, name="out")(
                    out.reshape(N, self.channels)
                )
            import warnings

            warnings.warn(
                "global_attn_type 'ring' requested but no global mesh is "
                "published (parallel.ring_attention.set_global_mesh); falling "
                "back to flat O(N^2) masked attention",
                stacklevel=2,
            )
        # dense-block vs exact flat attention: decided AT TRACE TIME whenever
        # collate certified a per-graph size bound (BatchMeta.max_n_node) — a
        # data-dependent lax.cond here lowers to select under vmap (the SPMD
        # per-device step), which would compute BOTH attentions every step.
        bound = batch.meta.max_n_node if batch.meta is not None else None
        if self.n_max and self.n_max < N:
            if bound is not None:
                if bound <= self.n_max:
                    out = self._dense_attention(q, k, v, batch)
                else:
                    out = self._flat_attention(q, k, v, batch)
            else:
                fits = jnp.all(batch.n_node <= self.n_max)
                out = jax.lax.cond(
                    fits,
                    lambda: self._dense_attention(q, k, v, batch),
                    lambda: self._flat_attention(q, k, v, batch),
                )
        else:
            out = self._flat_attention(q, k, v, batch)
        return nn.Dense(self.channels, name="out")(out.reshape(N, self.channels))


class PerformerAttention(nn.Module):
    """FAVOR+ softmax-kernel linear attention per graph (the reference's
    ``PerformerAttention`` option, ``gps.py:62-67``), on flat node arrays:

        out_i = φ(q_i) · Σ_{j∈g(i)} φ(k_j) v_jᵀ  /  φ(q_i) · Σ_{j∈g(i)} φ(k_j)

    with φ the positive random-feature map exp(w·x − ‖x‖²/2). The per-graph
    sums are ``segment_sum``s over nodes — O(N·m·d), no densification.
    """

    channels: int
    heads: int
    num_features: int = 0  # default: Dh rounded up to 8

    @nn.compact
    def __call__(self, h: jax.Array, batch: GraphBatch, train: bool = False):
        N = h.shape[0]
        H = self.heads
        Dh = self.channels // H
        m = self.num_features or max(8, (Dh + 7) // 8 * 8)
        q = nn.Dense(self.channels, name="q")(h).reshape(N, H, Dh)
        k = nn.Dense(self.channels, name="k")(h).reshape(N, H, Dh)
        v = nn.Dense(self.channels, name="v")(h).reshape(N, H, Dh)

        # Fixed (non-trainable) projection, seeded per layer from the module
        # path: independent draws across depth keep the per-layer FAVOR+
        # estimates unbiased instead of compounding one shared error.
        import zlib

        seed = zlib.crc32("/".join(self.path).encode()) & 0x7FFFFFFF
        w = jax.random.normal(jax.random.PRNGKey(seed), (H, Dh, m), h.dtype)
        scale = float(Dh) ** -0.25

        def phi(x, stab):
            proj = jnp.einsum("nhd,hdm->nhm", x * scale, w)
            norm = 0.5 * jnp.sum((x * scale) ** 2, axis=-1, keepdims=True)
            return jnp.exp(proj - norm - stab) / jnp.sqrt(float(m))

        # stabilizers: per-row max for q (cancels in the ratio) and per-GRAPH
        # max for k — uniform within a graph so it cancels exactly in num/den,
        # and graph-local so no numerical coupling between graphs exists
        G = batch.num_graphs
        kproj = jnp.einsum("nhd,hdm->nhm", k * scale, w)
        per_node = jax.lax.stop_gradient(kproj.max(axis=-1))  # [N, H]
        per_graph = segment.segment_max(per_node, batch.batch, G)  # [G, H]
        k_stab = per_graph[batch.batch][:, :, None]
        qproj = jnp.einsum("nhd,hdm->nhm", q * scale, w)
        q_stab = jax.lax.stop_gradient(qproj.max(axis=-1, keepdims=True))

        qp = phi(q, q_stab)  # [N, H, m]
        kp = phi(k, k_stab) * batch.node_mask[:, None, None]

        kv = segment.segment_sum(
            (kp[:, :, :, None] * v[:, :, None, :]).reshape(N, H * m * Dh),
            batch.batch, G, hints=batch,
        ).reshape(G, H, m, Dh)
        z = segment.segment_sum(kp.reshape(N, H * m), batch.batch, G, hints=batch).reshape(G, H, m)

        num = jnp.einsum("nhm,nhmd->nhd", qp, kv[batch.batch])
        den = jnp.einsum("nhm,nhm->nh", qp, z[batch.batch])
        out = num / jnp.maximum(den, 1e-9)[..., None]
        out = out * batch.node_mask[:, None, None]
        return nn.Dense(self.channels, name="out")(out.reshape(N, self.channels))


class GPSConv(nn.Module):
    """One GPS layer wrapping the architecture's local MPNN conv."""

    spec: ModelSpec
    layer: int
    out_dim: int | None = None

    @nn.compact
    def __call__(
        self, inv: jax.Array, equiv: jax.Array, batch: GraphBatch, train: bool = False
    ):
        spec = self.spec
        C = spec.hidden_dim
        drop = nn.Dropout(rate=spec.dropout)
        act = get_activation(spec.activation)
        bn_axis = SYNC_BN_AXIS if spec.sync_batch_norm else None

        # the layer's parts carry their names into the profiler's trace
        # (``jax.named_scope``; the local conv and ``rel_pos_emb`` are flax
        # modules of those names already): ``local``, ``attention``,
        # ``feed_forward``, ``norm`` -- one name each under a scanned stack
        def norm(name, h):
            with jax.named_scope("norm"):
                return MaskedBatchNorm(name=name, axis_name=bn_axis)(h, batch.node_mask, train)

        inner_cls = CONV_REGISTRY[spec.mpnn_type]
        inner_spec = spec
        if spec.mpnn_type in EDGE_MODELS and batch.rel_pe.shape[1] > 0:
            # relative-PE edge encodings for edge-capable convs (reference
            # Base.py:210-215: rel_pos_emb fused with any edge features)
            e = nn.Dense(C, use_bias=False, name="rel_pos_emb")(batch.rel_pe)
            if spec.edge_dim and batch.edge_attr.shape[1]:
                ea = nn.Dense(C, use_bias=False, name="edge_emb")(batch.edge_attr)
                e = nn.Dense(C, use_bias=False, name="edge_lin")(
                    jnp.concatenate([ea, e], axis=-1)
                )
            batch = batch.replace(edge_attr=e)
            inner_spec = dataclasses.replace(spec, edge_dim=C)
        h_local, equiv = inner_cls(spec=inner_spec, layer=self.layer, name="local")(
            inv, equiv, batch, train
        )
        h_local = drop(h_local, deterministic=not train)
        if h_local.shape[-1] == inv.shape[-1]:
            h_local = h_local + inv  # residual
        h_local = norm("norm1", h_local)

        attn_type = spec.global_attn_type or "multihead"
        if attn_type == "performer":
            attn_mod = PerformerAttention(
                channels=inv.shape[-1], heads=max(spec.global_attn_heads, 1),
                name="attn",
            )
        else:
            attn_mod = GraphMultiheadAttention(
                channels=inv.shape[-1], heads=max(spec.global_attn_heads, 1),
                n_max=spec.max_graph_nodes or 0, ring=(attn_type == "ring"),
                name="attn",
            )
        with jax.named_scope("attention"):
            h_attn = attn_mod(inv, batch, train)
        h_attn = drop(h_attn, deterministic=not train)
        h_attn = h_attn + inv  # residual
        h_attn = norm("norm2", h_attn)

        if h_local.shape[-1] != h_attn.shape[-1]:
            h_local = nn.Dense(h_attn.shape[-1], name="local_proj")(h_local)
        with jax.named_scope("feed_forward"):
            out = h_local + h_attn
            mlp = nn.Dense(out.shape[-1] * 2, name="mlp_0")(out)
            mlp = act(mlp)
            mlp = drop(mlp, deterministic=not train)
            mlp = nn.Dense(out.shape[-1], name="mlp_1")(mlp)
            mlp = drop(mlp, deterministic=not train)
            out = out + mlp
        out = norm("norm3", out)
        return out, equiv
