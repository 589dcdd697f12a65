"""HydraModel — the multi-headed GNN skeleton (TPU-native Base).

Functional re-design of reference ``hydragnn/models/Base.py:36-909``:

* conv stack with per-layer masked BatchNorm + activation (``Base.py:446-463,
  697-728``), gradient checkpointing via ``nn.remat`` (``:714-721``);
* graph-level readout with mean/add/max pooling (``:147-170``);
* multi-head decoders: per-head graph MLPs with per-branch shared layers
  (``_multihead``, ``:590-691``), node heads of type mlp / mlp_per_node / conv
  (``:641-684`` + ``MLPNode :912-982``);
* multibranch (multidataset) routing by ``dataset_id``: the reference gathers
  rows per branch with boolean masks (``forward :747-841``) — data-dependent
  shapes that XLA cannot compile. Here every branch computes on the full batch
  and a ``where`` select keeps the right rows: branch count is small (<=14) and
  head MLPs are tiny, so redundant FLOPs are noise on the MXU while shapes stay
  static;
* weighted multi-task loss (``loss_hpweighted``, ``:879-906``) and GaussianNLL
  variance outputs (``var_output``, ``:108-112``) — masked for padding;
* targets are columnar (``graph_y``/``node_y`` column slices per head) instead
  of the reference's concatenated ``data.y`` + ``y_loc`` offsets
  (``get_head_indices``, ``train_validate_test.py:494-557``) — a static-shape
  redesign, not a port.

Conv layers follow one uniform contract (no PyG string signatures):
``conv(inv_node_feat, equiv_node_feat, batch) -> (inv_node_feat,
equiv_node_feat)`` where ``batch`` is the full ``GraphBatch``.
"""

from __future__ import annotations

from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..config.schema import HeadBranchSpec, ModelSpec
from ..graphs.graph import GraphBatch
from ..graphs import segment
from .common import (
    MLP,
    SYNC_BN_AXIS,
    MaskedBatchNorm,
    get_activation,
    get_loss,
    local_node_index,
)

Array = jax.Array

# Registered by each architecture module at import time (create.py imports them).
CONV_REGISTRY: dict[str, Callable[..., nn.Module]] = {}


def register_conv(name: str):
    def deco(cls):
        CONV_REGISTRY[name] = cls
        return cls

    return deco


def head_columns(spec: ModelSpec) -> list[tuple[str, int, int]]:
    """Per-head (kind, column_start, dim) into the columnar target arrays."""
    cols = []
    g_off = n_off = 0
    for dim, kind in zip(spec.output_dim, spec.output_type):
        if kind == "graph":
            cols.append(("graph", g_off, dim))
            g_off += dim
        else:
            cols.append(("node", n_off, dim))
            n_off += dim
    return cols


class PerNodeMLP(nn.Module):
    """``mlp_per_node`` head: a separate MLP per node *position* (fixed-size
    graphs only — reference ``MLPNode`` with ``num_mlp=num_nodes``).

    TPU design: one weight bank ``[num_nodes, in, out]`` per layer, gathered by
    each node's local index and applied as a batched matmul — one einsum instead
    of ``num_nodes`` tiny MLP calls.
    """

    num_nodes: int
    features: tuple[int, ...]
    activation: str = "relu"

    @nn.compact
    def __call__(self, x: Array, local_idx: Array) -> Array:
        act = get_activation(self.activation)
        n_layers = len(self.features)
        in_dim = x.shape[-1]
        for i, out_dim in enumerate(self.features):
            w = self.param(
                f"w_{i}",
                nn.initializers.lecun_normal(),
                (self.num_nodes, in_dim, out_dim),
            )
            b = self.param(f"b_{i}", nn.initializers.zeros, (self.num_nodes, out_dim))
            wn = w[local_idx]  # [N, in, out]
            bn = b[local_idx]  # [N, out]
            x = jnp.einsum("ni,nio->no", x, wn) + bn
            if i < n_layers - 1:
                x = act(x)
            in_dim = out_dim
        return x


class LayerReadout(nn.Module):
    """``layer_readout`` head: one bias-free readout a conv layer on that
    layer's features, summed (MACE, arXiv:2206.07697 eq. 13-14): linear for
    every layer but the last, an MLP (``features[:-1]`` hidden widths) for
    the last. For stacks whose conv class sets ``collect_layer_outputs``."""

    num_layers: int
    features: tuple[int, ...]
    activation: str = "silu"

    @nn.compact
    def __call__(self, x: Array) -> Array:
        act = get_activation(self.activation)
        *earlier, h = jnp.split(x, self.num_layers, axis=-1)
        for j, width in enumerate(self.features[:-1]):
            h = act(nn.Dense(width, use_bias=False, name=f"readout_{len(earlier)}_dense_{j}")(h))
        out = nn.Dense(self.features[-1], use_bias=False, name=f"readout_{len(earlier)}")(h)
        for t, h in enumerate(earlier):
            out = out + nn.Dense(self.features[-1], use_bias=False, name=f"readout_{t}")(h)
        return out


class HydraModel(nn.Module):
    """Multi-headed GNN over padded graph batches."""

    spec: ModelSpec

    def setup(self):
        spec = self.spec
        conv_cls = CONV_REGISTRY[spec.mpnn_type]
        # stack flags always come from the architecture's own conv class,
        # even when GPS wraps it (the reference keeps Identity feature layers
        # for SchNet/MACE/etc. with or without GPS)
        use_feature_norm = getattr(conv_cls, "feature_norm", True)
        if spec.global_attn_engine == "GPS":
            # wrap every conv layer in local-MPNN + global attention
            # (reference Base._apply_global_attn, Base.py:234-247)
            from .gps import GPSConv as conv_cls  # noqa: F811

            self.pos_emb = nn.Dense(spec.hidden_dim, use_bias=False, name="pos_emb")
            if spec.input_dim:
                self.node_emb = nn.Dense(
                    spec.hidden_dim, use_bias=False, name="node_emb"
                )
                self.node_lin = nn.Dense(
                    spec.hidden_dim, use_bias=False, name="node_lin"
                )
        if spec.conv_checkpointing:
            # trade recompute for HBM: rematerialize each conv block on backward
            # (reference uses torch checkpointing at Base.py:714-721).
            # `train` (argnum 4 counting the module receiver) must stay static:
            # convs branch on it in Python (dropout determinism).
            conv_cls = nn.remat(conv_cls, static_argnums=(4,))
        self.graph_convs = [
            conv_cls(spec=spec, layer=i) for i in range(spec.num_conv_layers)
        ]
        # some stacks (SchNet) use identity feature layers in the reference
        # SyncBatchNorm (reference distributed.py:415-416, config key
        # Architecture.SyncBatchNorm): stats pmean'd over the axis the SPMD
        # steps bind; requires running under a parallel step's vmap.
        # ``bn_sync_axis`` overrides with a MESH axis name instead: the
        # halo-partitioned step runs under shard_map where the node set is
        # split across devices, so feature-norm statistics are only correct
        # when the masked sums are psum'd over the data axis.
        bn_axis = spec.bn_sync_axis or (
            SYNC_BN_AXIS if spec.sync_batch_norm else None
        )
        self.feature_layers = [
            (
                MaskedBatchNorm(name=f"feature_norm_{i}", axis_name=bn_axis)
                if use_feature_norm
                else None
            )
            for i in range(spec.num_conv_layers)
        ]

        # graph-head shared layers + per-head MLPs, per branch
        # (num_sharedlayers == 0 -> no shared stack, heads read pooled features)
        self.graph_shared = {
            b.branch: (
                MLP(
                    features=(b.dim_sharedlayers,) * b.num_sharedlayers,
                    activation=spec.activation,
                    act_last=True,
                    name=f"graph_shared_{b.branch}",
                )
                if b.num_sharedlayers > 0 and b.dim_sharedlayers > 0
                else None
            )
            for b in spec.graph_heads
        }
        var_mult = 2 if spec.var_output else 1
        heads = []
        cols = head_columns(spec)
        node_local_needed = False
        for ihead, (kind, _, dim) in enumerate(cols):
            if kind == "graph":
                per_branch = {}
                for b in spec.graph_heads:
                    feats = tuple(b.dim_headlayers[: b.num_headlayers]) + (dim * var_mult,)
                    per_branch[b.branch] = MLP(
                        features=feats,
                        activation=spec.activation,
                        name=f"head{ihead}_{b.branch}",
                    )
                heads.append(per_branch)
            else:
                per_branch = {}
                for b in spec.node_heads:
                    node_type = b.node_type or "mlp"
                    feats = tuple(b.dim_headlayers[: b.num_headlayers]) + (dim * var_mult,)
                    if node_type == "mlp":
                        per_branch[b.branch] = MLP(
                            features=feats,
                            activation=spec.activation,
                            name=f"head{ihead}_{b.branch}",
                        )
                    elif node_type == "mlp_per_node":
                        if spec.num_nodes is None or spec.graph_size_variable:
                            raise ValueError(
                                "mlp_per_node requires fixed-size graphs (reference "
                                "config_utils.py:240-249)"
                            )
                        node_local_needed = True
                        per_branch[b.branch] = PerNodeMLP(
                            num_nodes=spec.num_nodes,
                            features=feats,
                            activation=spec.activation,
                            name=f"head{ihead}_{b.branch}",
                        )
                    elif node_type == "layer_readout":
                        if not getattr(CONV_REGISTRY[spec.mpnn_type],
                                       "collect_layer_outputs", False):
                            raise ValueError(
                                f"layer_readout heads need a stack that exposes every "
                                f"layer's features; {spec.mpnn_type} does not")
                        per_branch[b.branch] = LayerReadout(
                            num_layers=spec.num_conv_layers,
                            features=feats,
                            activation=spec.activation,
                            name=f"head{ihead}_{b.branch}",
                        )
                    elif node_type == "conv":
                        # conv-type node head: extra conv layers + output conv
                        # (reference _init_node_conv, Base.py:544-588)
                        conv_cls2 = CONV_REGISTRY[spec.mpnn_type]
                        layers = []
                        hidden = list(b.dim_headlayers[: b.num_headlayers])
                        for j, _h in enumerate(hidden):
                            layers.append(
                                conv_cls2(
                                    spec=spec,
                                    layer=spec.num_conv_layers + j,
                                    name=f"head{ihead}_{b.branch}_conv{j}",
                                )
                            )
                        layers.append(
                            conv_cls2(
                                spec=spec,
                                layer=spec.num_conv_layers + len(hidden),
                                out_dim=dim * var_mult,
                                name=f"head{ihead}_{b.branch}_convout",
                            )
                        )
                        per_branch[b.branch] = layers
                    else:
                        raise ValueError(
                            f"Unknown node head type '{node_type}'; support 'mlp', "
                            "'mlp_per_node', 'layer_readout', 'conv'"
                        )
                heads.append(per_branch)
        self.heads_NN = heads
        self._head_cols = cols
        self._node_local_needed = node_local_needed

        # graph-attribute conditioning (reference Base.py:249-444):
        # 'film'        — gamma/beta modulation of node features per layer
        # 'concat_node' — broadcast graph_attr to nodes, concat + project
        # 'fuse_pool'   — fuse into the pooled embedding before graph heads
        if spec.use_graph_attr_conditioning:
            mode = spec.graph_attr_conditioning_mode
            if mode not in ("film", "concat_node", "fuse_pool"):
                raise ValueError(
                    "graph_attr_conditioning_mode must be one of: "
                    "'film', 'concat_node', 'fuse_pool'"
                )
            if mode == "film":
                self.graph_conditioner = MLP(
                    features=(spec.hidden_dim, 2 * spec.hidden_dim),
                    activation=spec.activation,
                    name="graph_conditioner",
                )
            elif mode == "concat_node":
                self.graph_concat_projector = nn.Dense(
                    spec.hidden_dim, name="graph_concat_projector"
                )
            else:  # fuse_pool
                self.graph_pool_projector = MLP(
                    features=(spec.hidden_dim, spec.hidden_dim),
                    activation=spec.activation,
                    name="graph_pool_projector",
                )

    # -- encoder ------------------------------------------------------------
    def conv_block(self, i: int, inv: Array, equiv: Array, batch: GraphBatch,
                   train: bool = False):
        """One conv layer block: conv + graph-attr conditioning + feature
        norm + activation. Factored out so the pipeline-parallel runtime
        (``parallel/pipeline.py``) can scan it over per-layer params."""
        conv_cls = CONV_REGISTRY[self.spec.mpnn_type]
        stack_activation = getattr(conv_cls, "stack_activation", True)
        conv = self.graph_convs[i]
        norm = self.feature_layers[i]
        inv, equiv = conv(inv, equiv, batch, train)  # positional: remat statics
        inv = self._apply_graph_conditioning(inv, batch)
        if norm is not None:
            inv = norm(inv, batch.node_mask, train)
        if stack_activation:
            inv = get_activation(self.spec.activation)(inv)
        return inv, equiv

    def embed_block0(self, batch: GraphBatch, train: bool = False):
        """Input embedding + conv block 0 — the pipeline prologue (block 0
        lifts input_dim -> hidden_dim, so it is the one non-uniform layer)."""
        inv, equiv = self.embed(batch)
        return self.conv_block(0, inv, equiv, batch, train)

    def encode(self, batch: GraphBatch, train: bool = False, layer_hook=None):
        """Run the conv stack; returns (node_features, equiv_features).

        ``layer_hook(inv, equiv) -> (inv, equiv)`` runs BEFORE every conv
        layer after the first — the seam the halo-exchange route uses to
        refresh boundary-node features over the mesh (``parallel/halo.py``):
        layer 0 reads collate-time halo copies, every later layer reads rows
        re-fetched from their owner device. Single-device and replicated
        paths pass None and trace the exact historical program."""
        conv_cls = CONV_REGISTRY[self.spec.mpnn_type]
        # MACE: no inter-layer activation; heads read concatenated per-layer
        # scalars (a ``layer_readout`` head is the reference's summed per-layer
        # readout decoders, MACEStack.forward :375-421)
        collect = getattr(conv_cls, "collect_layer_outputs", False)

        inv, equiv = self.embed(batch)
        layer_outs = []
        for i in range(len(self.graph_convs)):
            if layer_hook is not None and i > 0:
                inv, equiv = layer_hook(inv, equiv)
            inv, equiv = self.conv_block(i, inv, equiv, batch, train)
            if collect:
                layer_outs.append(inv)
        if collect:
            inv = jnp.concatenate(layer_outs, axis=-1)
        return inv, equiv

    def _apply_graph_conditioning(self, inv: Array, batch: GraphBatch) -> Array:
        """Per-layer node-feature conditioning on graph attributes
        (reference ``_apply_graph_conditioning``, Base.py:346-420)."""
        spec = self.spec
        if not spec.use_graph_attr_conditioning or batch.graph_attr.shape[1] == 0:
            return inv
        mode = spec.graph_attr_conditioning_mode
        if mode == "film":
            gb = self.graph_conditioner(batch.graph_attr)  # [G, 2H]
            gamma, beta = jnp.split(gb, 2, axis=-1)
            h = min(inv.shape[-1], gamma.shape[-1])
            scaled = inv[:, :h] * (1.0 + gamma[batch.batch][:, :h]) + beta[
                batch.batch
            ][:, :h]
            return jnp.concatenate([scaled, inv[:, h:]], axis=-1)
        if mode == "concat_node":
            ga = batch.graph_attr[batch.batch]  # broadcast to nodes
            return self.graph_concat_projector(jnp.concatenate([inv, ga], axis=-1))
        return inv  # fuse_pool conditions at the pooled level instead

    def embed(self, batch: GraphBatch):
        """Input embedding. With GPS, node features and Laplacian positional
        encodings are embedded to hidden_dim and fused (reference Base.py
        :203-215); otherwise raw features + positions pass through (each
        stack's first conv layer does its own lifting)."""
        if self.spec.global_attn_engine == "GPS":
            if batch.pe.shape[1] == 0:
                raise ValueError(
                    "GPS needs Laplacian positional encodings; set pe_dim > 0 "
                    "and attach them in preprocessing (attach_lap_pe)"
                )
            x = self.pos_emb(batch.pe)
            if self.spec.input_dim:
                x = jnp.concatenate([self.node_emb(batch.x), x], axis=1)
                x = self.node_lin(x)
            return x, batch.pos
        return batch.x, batch.pos

    def pool(self, x: Array, batch: GraphBatch, pool_reduce=None) -> Array:
        pooled = segment.global_pool(
            self.spec.graph_pooling,
            x * batch.node_mask[:, None],
            batch.batch,
            batch.num_graphs,
            hints=batch,
        )
        if pool_reduce is not None:
            # partitioned node sets (halo route): each device pooled only its
            # owned rows — the hook merges the per-device partials into the
            # union-graph readout (psum/weighted-mean/pmax per pooling kind)
            # BEFORE any nonlinear head consumes them
            pooled = pool_reduce(pooled)
        if (
            self.spec.use_graph_attr_conditioning
            and self.spec.graph_attr_conditioning_mode == "fuse_pool"
            and batch.graph_attr.shape[1] > 0
        ):
            pooled = self.graph_pool_projector(
                jnp.concatenate([pooled, batch.graph_attr], axis=-1)
            )
        return pooled

    # -- full forward --------------------------------------------------------
    def __call__(self, batch: GraphBatch, train: bool = False,
                 layer_hook=None, pool_reduce=None):
        inv, equiv = self.encode(batch, train, layer_hook=layer_hook)
        return self.decode(inv, equiv, batch, train, pool_reduce=pool_reduce)

    def decode(self, inv: Array, equiv: Array, batch: GraphBatch,
               train: bool = False, pool_reduce=None):
        """Pooling + multi-head decoders on encoded node features — the
        pipeline epilogue (everything after the conv stack)."""
        spec = self.spec
        x_graph = self.pool(inv, batch, pool_reduce=pool_reduce)

        outputs = []
        outputs_var = []
        local_idx = None
        if self._node_local_needed:
            local_idx = local_node_index(batch.batch, batch.n_node, batch.num_nodes)

        for ihead, (kind, _, dim) in enumerate(self._head_cols):
            per_branch = self.heads_NN[ihead]
            if kind == "graph":
                out = jnp.zeros((batch.num_graphs, dim), inv.dtype)
                out_var = jnp.zeros((batch.num_graphs, dim), inv.dtype)
                for b in spec.graph_heads:
                    shared_mlp = self.graph_shared[b.branch]
                    shared = shared_mlp(x_graph) if shared_mlp is not None else x_graph
                    o = per_branch[b.branch](shared)
                    mu = o[:, :dim]
                    var = o[:, dim:] ** 2 if spec.var_output else out_var
                    if len(spec.graph_heads) == 1:
                        out, out_var = mu, var
                    else:
                        sel = (batch.dataset_id == int(b.branch.split("-")[1]))[:, None]
                        out = jnp.where(sel, mu, out)
                        out_var = jnp.where(sel, var, out_var)
                outputs.append(out)
                outputs_var.append(out_var)
            else:
                out = jnp.zeros((batch.num_nodes, dim), inv.dtype)
                out_var = jnp.zeros((batch.num_nodes, dim), inv.dtype)
                for b in spec.node_heads:
                    node_type = b.node_type or "mlp"
                    if node_type == "conv":
                        h, e = inv, equiv
                        for conv in per_branch[b.branch]:
                            h, e = conv(h, e, batch, train=train)
                        o = h
                    elif node_type == "mlp_per_node":
                        o = per_branch[b.branch](inv, local_idx)
                    else:
                        o = per_branch[b.branch](inv)
                    mu = o[:, :dim]
                    var = o[:, dim:] ** 2 if spec.var_output else out_var
                    if len(spec.node_heads) == 1:
                        out, out_var = mu, var
                    else:
                        bid = int(b.branch.split("-")[1])
                        sel = (batch.dataset_id[batch.batch] == bid)[:, None]
                        out = jnp.where(sel, mu, out)
                        out_var = jnp.where(sel, var, out_var)
                outputs.append(out)
                outputs_var.append(out_var)

        if spec.var_output:
            return outputs, outputs_var
        return outputs

    # -- loss ----------------------------------------------------------------
    def loss(self, pred, batch: GraphBatch, loss_axis: str | None = None):
        """Weighted multi-task loss (reference ``loss_hpweighted``,
        ``Base.py:879-906``). Returns (total, [per-task losses]).

        ``loss_axis``: mesh axis name when the batch's NODE rows are
        partitioned across devices (halo route) — each masked mean then
        psums numerator and denominator over the axis so every device holds
        the exact union-batch loss (graph rows are replicated there, which
        the psum'd ratio absorbs unchanged)."""
        spec = self.spec
        var = None
        if spec.var_output:
            pred, var = pred
        loss_fn = get_loss(spec.loss_type)
        tot = 0.0
        tasks = []
        for ihead, (kind, col, dim) in enumerate(head_columns(spec)):
            if kind == "graph":
                target = batch.graph_y[:, col : col + dim]
                mask = batch.graph_mask
            else:
                target = batch.node_y[:, col : col + dim]
                mask = batch.node_mask
            if var is not None:
                task_loss = loss_fn(pred[ihead], target, mask, var[ihead],
                                    axis_name=loss_axis)
            else:
                task_loss = loss_fn(pred[ihead], target, mask,
                                    axis_name=loss_axis)
            tot = tot + task_loss * spec.task_weights[ihead]
            tasks.append(task_loss)
        return tot, tasks

    def head_sse(self, pred, batch: GraphBatch):
        """Per-head (sum of squared errors, element count) over real rows.

        Callers accumulate these across batches and take ONE sqrt at the end —
        the statistically correct split RMSE (the CI accuracy gate metric,
        reference ``test_graphs.py:144-170``); a mean of per-batch RMSEs is not.
        """
        spec = self.spec
        if spec.var_output:
            pred = pred[0]
        sses, counts = [], []
        for ihead, (kind, col, dim) in enumerate(head_columns(spec)):
            if kind == "graph":
                target = batch.graph_y[:, col : col + dim]
                mask = batch.graph_mask
            else:
                target = batch.node_y[:, col : col + dim]
                mask = batch.node_mask
            m = mask[:, None]
            sses.append((((pred[ihead] - target) ** 2) * m).sum())
            counts.append(mask.sum() * dim)
        return sses, counts


def _apply(self, variables, *args, method=None, **kwargs):
    """``nn.Module.apply``; the whole forward of a model that asks for it
    (``Training.scan_conv_layers``) runs its homogeneous conv blocks as one
    ``lax.scan`` (``models/layer_scan.py``). ``init`` and a call of one
    ``method`` are what they were."""
    if self.spec.scan_conv_layers and method is None:
        from .layer_scan import scanned_apply

        return scanned_apply(self, variables, *args, **kwargs)
    return nn.Module.apply(self, variables, *args, method=method, **kwargs)


# set after the class statement: flax wraps every method of a module's class
# body in a named scope, and a model that scans nothing keeps the operation
# names (the profiler's scopes) it always had
HydraModel.apply = _apply
