"""Edge-sharded execution of FULL models — long-context for graphs.

``edge_sharding.py`` holds the manual shard_map primitive (one GIN-style
layer). This module is the production path: ANY ``HydraModel`` forward /
training step runs over a batch whose EDGE-dimension arrays are sharded
across the mesh's data axis while node/graph arrays stay replicated. The
XLA SPMD partitioner then emits, for every conv stack automatically, the
same schedule the primitive hand-writes: local gather from replicated nodes,
edge transforms partitioned E/D per device, partial segment-sums, one
all-reduce of the node accumulator over ICI (the "halo exchange").

This is the graph analog of sequence/context parallelism: graph size is the
sequence length, and the per-device edge shard is the context slice. The
reference has no counterpart (its answer to big structures is radius cutoffs
+ many small graphs); SURVEY §5 marks this as the TPU build's first-class
long-context mechanism.

Config: ``NeuralNetwork.Architecture.edge_sharding: true`` routes
``run_training`` through these steps when more than one device is present.

Resilience pass-through: the train step built here keeps the generic
``(state, batch) -> (state, metrics)`` contract, so the non-finite step
guard (``resilience/guard.py``) wraps it unchanged in the epoch loop —
a NaN on ANY edge shard propagates into the all-reduced loss and the
whole-mesh update is select-skipped in the same dispatch. Divergence
rollback and preemption checkpointing operate at the loop/checkpoint layer
and need nothing mode-specific; only supersteps stay pinned at K=1 (the
per-batch ``put_large_batch`` placement has no stacked [K, ...] form yet).

The Pallas fused-scatter kernel is trace-time disabled on this path (a
pallas_call is opaque to the SPMD partitioner and would force an edge
all-gather); the XLA segment_sum partitions cleanly.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..graphs.batching import flat_triplets
from ..graphs.graph import GraphBatch
from ..models.base import HydraModel
from ..ops import routing
from ..train.step import TrainState, make_eval_step, make_train_step
from .mesh import DATA_AXIS

# GraphBatch fields whose leading axis is the edge (or triplet) dimension.
_EDGE_FIELDS = frozenset(
    {"senders", "receivers", "edge_attr", "edge_shifts", "edge_mask",
     "idx_kj", "idx_ji", "triplet_mask", "rel_pe"}
)


# GSPMD splits these programs' edge arrays and cannot partition a Mosaic
# call: they are traced with the fused kernels on their XLA paths.
_EDGE_ROUTE = "edge-sharded GSPMD program (Mosaic calls cannot be auto-partitioned)"


# GraphBatch fields whose leading axis is the node dimension.
_NODE_FIELDS = frozenset(
    {"x", "pos", "batch", "node_y", "forces_y", "node_mask", "pe", "z"}
)


def edge_batch_shardings(mesh: Mesh, shard_nodes: bool = False) -> GraphBatch:
    """Edge-dimension fields split over the data axis; node fields split too
    when ``shard_nodes`` (at-rest node memory 1/D — XLA all-gathers node
    features right before each layer's gather, ZeRO-style); everything else
    replicated."""
    split = NamedSharding(mesh, P(DATA_AXIS))
    rep = NamedSharding(mesh, P())

    def pick(f):
        if f in _EDGE_FIELDS:
            return split
        if shard_nodes and f in _NODE_FIELDS:
            return split
        return rep

    # meta=None matches put_large_batch, which invalidates the collate-time
    # layout certificate (padding here changes the edge layout anyway, and
    # the edge-sharded path always runs the XLA segment_sum)
    return GraphBatch(*[pick(f) for f in GraphBatch._fields[:-1]], meta=None)


def put_large_batch(
    batch: GraphBatch, mesh: Mesh, shard_nodes: bool = False
) -> GraphBatch:
    """Place one (possibly giant) collated batch with edge (and optionally
    node) arrays sharded. Pads the sharded dimensions to multiples of the
    data-axis size with masked fill (shape-preserving semantics)."""
    # the meta goes below, and a dense triplet block is read through it: the
    # sharded fields are lists along the edge / triplet axis, so flatten first
    batch = flat_triplets(batch)
    n_dev = mesh.shape[DATA_AXIS]
    n_node = np.asarray(batch.x).shape[0]
    e_padded = np.asarray(batch.senders).shape[0]
    e_padded += -e_padded % n_dev
    n_graph = np.asarray(batch.graph_y).shape[0]
    sharded_fields = _EDGE_FIELDS | (_NODE_FIELDS if shard_nodes else frozenset())

    def pad_field(name, arr):
        arr = np.asarray(arr)
        if name not in sharded_fields:
            return arr
        pad = -arr.shape[0] % n_dev
        if not pad:
            return arr
        if name in ("senders", "receivers"):
            fill = n_node - 1  # masked pad edges wired to the padding node
        elif name in ("idx_kj", "idx_ji"):
            fill = e_padded - 1  # pad triplets point at a padded edge
        elif name == "batch":
            fill = n_graph - 1  # pad nodes belong to the dummy graph
        else:
            fill = 0
        width = ((0, pad),) + ((0, 0),) * (arr.ndim - 1)
        return np.pad(arr, width, constant_values=fill)

    # node padding changes num_nodes: pad-edge endpoints must still point at
    # a PADDING node; node n_node-1 is one by the collate contract, and pads
    # added here extend the padding tail, so fills above stay valid.
    batch = GraphBatch(
        *[pad_field(f, getattr(batch, f)) for f in GraphBatch._fields[:-1]],
        meta=None,
    )
    sh = edge_batch_shardings(mesh, shard_nodes)
    return jax.tree.map(lambda x, s: jax.device_put(jnp.asarray(x), s), batch, sh)


def make_edge_sharded_apply(model: HydraModel, mesh: Mesh):
    """Jitted inference over an edge-sharded batch; returns model outputs
    (replicated)."""

    @jax.jit
    def forward(variables, batch: GraphBatch):
        return model.apply(variables, batch, train=False)

    def apply(variables, batch: GraphBatch):
        with routing.xla_only(_EDGE_ROUTE):
            return forward(variables, batch)

    return apply


def make_edge_sharded_train_step(
    model: HydraModel, optimizer, mesh: Mesh, compute_dtype=jnp.float32,
    loss_scale=None,
):
    """Training step over edge-sharded batches: ``make_train_step``'s own
    program, traced with the kernels on their XLA paths — XLA inserts the
    node-accumulator all-reduces and the gradient psum from the shardings
    alone."""
    if model.spec.sync_batch_norm:
        raise ValueError(
            "SyncBatchNorm is not supported with edge_sharding: the graph is "
            "ONE giant sample split across devices (there is no per-device "
            "batch whose statistics could be synced); feature norms already "
            "see the full node set"
        )
    step = make_train_step(model, optimizer, compute_dtype, loss_scale)

    def train_step(state: TrainState, batch: GraphBatch):
        with routing.xla_only(_EDGE_ROUTE):
            return step(state, batch)

    return train_step


def make_edge_sharded_eval_step(model: HydraModel, mesh: Mesh, compute_dtype=jnp.float32):
    inner = make_eval_step(model, compute_dtype)

    def eval_step(state: TrainState, batch: GraphBatch):
        with routing.xla_only(_EDGE_ROUTE):
            return inner(state, batch)

    return eval_step
