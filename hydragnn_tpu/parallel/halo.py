"""Halo-exchange message passing — node-RESIDENT giant graphs.

The third large-graph route, next to plain data parallelism and the
replicated-node edge sharding in ``large_graph.py``. Edge sharding keeps
every node feature on every device and all-reduces the whole ``[N, F]``
accumulator once per conv layer, so per-device memory AND per-layer comm
scale with TOTAL graph size. Here the graph is partitioned *spatially*
(``graphs/partition.py``: cell-list grid, Morton-ordered, count-balanced
contiguous ranges) and each device keeps only

* its OWNED nodes (features, labels, masks — 1/D of the graph at rest),
* its OWNED edges (every edge whose RECEIVER it owns — so each device can
  aggregate its own nodes' messages completely), and
* HALO slots: read-only copies of the remote senders its owned edges touch.

Before every conv layer after the first, ONLY the halo rows are refreshed:
a static ring schedule of ``lax.ppermute`` steps (shift 1 .. D-1 over the
data axis) moves each boundary row from its owner into the neighbors' halo
slots. Morton partitions keep boundaries thin, so the bytes on the wire are
proportional to the partition SURFACE — not to N like the replicated
all-reduce (the bench row ``halo_exchange_ab`` reports the analytic ratio).

The whole exchange is one *static plan* built host-side at collate time
(``HaloPlan``): per-shift send/recv index lists, bucket-padded so the jit
program stays shape-stable across batches. Index VALUES are data — a new
frame with the same buckets reuses the compiled step. Autodiff handles the
reverse exchange for free: the transpose of ``ppermute`` is the inverted
permutation, so halo cotangents flow back to the owner's rows inside the
same backward pass.

Resilience: the steps keep the generic ``(state, batch) -> (state,
metrics)`` contract, so the non-finite guard wraps them unchanged; like the
other K=1-pinned layouts (edge-sharded, pipeline) a device loss routes to
``plan_remesh``'s restart fallback — the partition count is baked into the
program.

Config: ``NeuralNetwork.Architecture.halo`` (single-sourced from
``HaloConfig``) routes ``run_training`` here; env ``HYDRAGNN_HALO``
overrides the ``enabled`` key.
"""

from __future__ import annotations

import dataclasses
from functools import partial as _partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..graphs.graph import GraphBatch
from ..graphs.partition import boundary_sets, partition_nodes
from ..graphs.segment import segment_count
from ..models.base import HydraModel
from ..train.step import (
    TrainState,
    _cast_floats,
    apply_gradients,
    donate_state_argnums,
    dropout_rng,
    scaled_value_and_grad,
)
from .mesh import DATA_AXIS


# -- config -------------------------------------------------------------------

@dataclasses.dataclass
class HaloConfig:
    """``Architecture.halo`` block — the single source of its defaults.

    ``partitions``      0 = one partition per data-axis device (the only
                        supported value today; a nonzero value must match).
    ``slot_multiple``   halo send/recv slot lists are padded up to this
                        multiple per ring shift — the shape-stability bucket
                        (bigger = fewer recompiles across frames, more pad).
    ``node_multiple`` / ``edge_multiple``
                        per-device node/edge array buckets, same role.
    ``fallback``        what to do when the model or batch is outside halo
                        support: "error" fails fast, "data" falls back to
                        plain data-parallel steps with a log line.
    """

    enabled: bool = False
    partitions: int = 0
    slot_multiple: int = 8
    node_multiple: int = 8
    edge_multiple: int = 128
    fallback: str = "error"

    def validate(self) -> "HaloConfig":
        if self.partitions < 0:
            raise ValueError(f"halo.partitions must be >= 0, got {self.partitions}")
        for key in ("slot_multiple", "node_multiple", "edge_multiple"):
            if int(getattr(self, key)) < 1:
                raise ValueError(f"halo.{key} must be >= 1, got {getattr(self, key)}")
        if self.fallback not in ("error", "data"):
            raise ValueError(
                f"halo.fallback must be 'error' or 'data', got {self.fallback!r}"
            )
        return self


def halo_config_defaults() -> dict:
    return dataclasses.asdict(HaloConfig())


def halo_config(arch_cfg: dict | None) -> HaloConfig:
    """Typed view of ``Architecture.halo`` with defaults back-filled."""
    raw = dict((arch_cfg or {}).get("halo") or {})
    cfg = {**halo_config_defaults(), **raw}
    return HaloConfig(**cfg).validate()


def halo_enabled(arch_cfg: dict | None) -> bool:
    """``HYDRAGNN_HALO`` env flag wins over ``Architecture.halo.enabled``."""
    from ..utils import flags

    cfg = ((arch_cfg or {}).get("halo") or {})
    return bool(flags.get(flags.HALO, default=bool(cfg.get("enabled", False))))


# -- support surface ----------------------------------------------------------

# Conv stacks whose aggregation is receiver-directed (messages land on the
# edge's receiver): owning every in-edge of an owned node makes the local
# aggregate exact, and halo rows only ever serve as gather sources.
HALO_SUPPORTED_CONVS = frozenset(
    {"GIN", "GAT", "PNA", "PNAPlus", "SAGE", "MFC", "CGCNN", "SchNet"}
)


def validate_halo_support(spec) -> None:
    """Fail fast on model features the partitioned step cannot reproduce
    bit-for-bit. Mirrors the edge-sharded path's explicit rejections."""
    if spec.mpnn_type not in HALO_SUPPORTED_CONVS:
        raise ValueError(
            f"halo partitioning does not support mpnn_type={spec.mpnn_type!r} "
            f"(receiver-directed stacks only: {sorted(HALO_SUPPORTED_CONVS)}; "
            "DimeNet triplets and MACE per-layer readouts cross partitions)"
        )
    if spec.equivariance:
        raise ValueError(
            "halo partitioning does not support equivariance: coordinate "
            "updates aggregate by SENDER, and a sender owned elsewhere would "
            "drop its contribution (needs a reverse halo reduction)"
        )
    if spec.global_attn_engine:
        raise ValueError(
            "halo partitioning does not support global attention "
            f"({spec.global_attn_engine}): it is all-to-all over nodes by "
            "construction — use replicated edge_sharding instead"
        )
    if spec.sync_batch_norm:
        raise ValueError(
            "SyncBatchNorm is not supported with halo partitioning: the graph "
            "is ONE giant sample; feature-norm statistics are already psum'd "
            "over the data axis by the halo step itself"
        )
    if spec.enable_interatomic_potential:
        raise ValueError(
            "halo partitioning does not support the interatomic-potential "
            "loss yet: force autograd differentiates through positions that "
            "live on other devices"
        )
    for b in spec.node_heads:
        if (b.node_type or "mlp") != "mlp":
            raise ValueError(
                f"halo partitioning supports only 'mlp' node heads, got "
                f"{b.node_type!r}: per-position banks index GLOBAL node "
                "positions and conv heads need their own halo refreshes"
            )


# -- static plan --------------------------------------------------------------

class HaloPlan(NamedTuple):
    """Static ring-exchange schedule. For each shift ``s`` (1-indexed by
    position: entry ``i`` is shift ``i + 1``):

    ``send_idx[i]``  [D, S_i] — per device, LOCAL indices (into the owned
                     region) of the rows it must send to device ``d + s``;
                     padded with 0 (a real owned row whose copy lands in a
                     trash slot on the receiver).
    ``recv_slot[i]`` [D, S_i] — per device, LOCAL indices (into the halo
                     region) where the rows arriving from device ``d - s``
                     land; padded with the trash slot ``N_loc - 1``.

    Both sides order a pair's rows by ascending GLOBAL node id, so position
    k of a send buffer is position k of the matching recv list. All leaves
    are data — only the bucket-padded widths are baked into the program.
    """

    send_idx: tuple
    recv_slot: tuple


class HaloBatch(NamedTuple):
    """One partitioned frame: every ``batch`` leaf is stacked ``[D, ...]``
    (device d's local view at index d) and placed with its leading axis on
    the mesh's data axis. ``node_global`` ([D, N_loc], -1 = pad) and
    ``n_owned`` ([D]) ride along for host-side reassembly of node-level
    predictions; the step programs never read them."""

    batch: GraphBatch
    plan: HaloPlan
    node_global: jax.Array
    n_owned: jax.Array


def _round_up(v: int, m: int) -> int:
    return int(-(-int(v) // int(m)) * int(m))


# GraphBatch fields gathered per-node / per-edge / per-graph when building
# the local views (everything else is re-derived or replicated).
_NODE_GATHER = ("x", "pos", "node_y", "forces_y", "pe", "z")
_GRAPH_REPLICATE = (
    "graph_attr", "graph_y", "energy_y", "graph_mask", "dataset_id"
)


def partition_graph_batch(
    batch: GraphBatch,
    n_parts: int,
    cfg: HaloConfig | None = None,
    cutoff: float | None = None,
) -> HaloBatch:
    """Split ONE collated single-graph batch into ``n_parts`` device-local
    views + the static exchange plan. Host-side numpy; deterministic.

    Requires exactly one real graph (the giant-graph regime this route
    exists for — the loader runs ``batch_size=1``); the dummy padding graph
    is preserved, so every local view keeps the collate padding convention:
    padded nodes/edges point at slot ``N_loc - 1`` of graph ``G - 1``.
    """
    cfg = cfg or HaloConfig()
    arr = {f: np.asarray(getattr(batch, f)) for f in GraphBatch._fields[:-1]}
    n_real_graphs = int(arr["graph_mask"].sum())
    if n_real_graphs != 1:
        raise ValueError(
            f"halo partitioning expects exactly 1 real graph per batch, got "
            f"{n_real_graphs} (set Training.batch_size=1 for the giant-graph "
            "regime)"
        )
    if n_parts < 2:
        raise ValueError(f"halo partitioning needs >= 2 partitions, got {n_parts}")
    G = arr["graph_y"].shape[0]
    n_real = int(np.round(arr["node_mask"].sum()))
    e_real = int(np.round(arr["edge_mask"].sum()))
    # collate packs real rows first; padding is the tail
    pos = arr["pos"][:n_real]
    senders = arr["senders"][:e_real].astype(np.int64)
    receivers = arr["receivers"][:e_real].astype(np.int64)

    plan = partition_nodes(pos, n_parts, cutoff=cutoff)
    owner = plan.owner
    halos = boundary_sets(senders, receivers, owner, n_parts)

    owned = [plan.part(p) for p in range(n_parts)]
    # halo layout per device: grouped by source partition ascending, each
    # group ascending by global id (the same order the plan's send side uses)
    halo_ids = [
        np.concatenate(
            [halos.get((src, d), np.zeros(0, np.int32)) for src in range(n_parts)]
        ).astype(np.int64)
        for d in range(n_parts)
    ]
    n_owned = np.array([len(o) for o in owned], np.int64)
    recv_owner = owner[receivers]
    edge_of = [np.nonzero(recv_owner == d)[0] for d in range(n_parts)]

    n_loc = _round_up(
        int(max(n_owned[d] + len(halo_ids[d]) for d in range(n_parts))) + 1,
        cfg.node_multiple,
    )
    e_loc = _round_up(
        max(int(max(len(e) for e in edge_of)), 1), cfg.edge_multiple
    )

    # global id -> local slot, per device (owned region then halo region)
    loc_of = []
    for d in range(n_parts):
        m = np.full(n_real, -1, np.int64)
        m[owned[d]] = np.arange(len(owned[d]))
        m[halo_ids[d]] = n_owned[d] + np.arange(len(halo_ids[d]))
        loc_of.append(m)

    fields = {name: [] for name in GraphBatch._fields[:-1]}
    node_global = np.full((n_parts, n_loc), -1, np.int32)
    for d in range(n_parts):
        gids = np.concatenate([owned[d], halo_ids[d]])
        n_here = len(gids)
        node_global[d, :n_here] = gids
        for name in _NODE_GATHER:
            src = arr[name]
            out = np.zeros((n_loc,) + src.shape[1:], src.dtype)
            out[:n_here] = src[gids]
            fields[name].append(out)
        batch_ids = np.full(n_loc, G - 1, arr["batch"].dtype)
        batch_ids[: n_owned[d]] = 0  # halo + pad rows sit in the dummy graph
        fields["batch"].append(batch_ids)
        node_mask = np.zeros(n_loc, arr["node_mask"].dtype)
        node_mask[: n_owned[d]] = 1.0
        fields["node_mask"].append(node_mask)

        eids = edge_of[d]
        snd = np.full(e_loc, n_loc - 1, arr["senders"].dtype)
        rcv = np.full(e_loc, n_loc - 1, arr["receivers"].dtype)
        snd[: len(eids)] = loc_of[d][senders[eids]]
        rcv[: len(eids)] = loc_of[d][receivers[eids]]
        fields["senders"].append(snd)
        fields["receivers"].append(rcv)
        emask = np.zeros(e_loc, arr["edge_mask"].dtype)
        emask[: len(eids)] = 1.0
        fields["edge_mask"].append(emask)
        for name in ("edge_attr", "edge_shifts", "rel_pe"):
            src = arr[name]
            out = np.zeros((e_loc,) + src.shape[1:], src.dtype)
            out[: len(eids)] = src[eids]
            fields[name].append(out)
        nn = np.zeros(G, arr["n_node"].dtype)
        nn[0] = n_owned[d]
        fields["n_node"].append(nn)
        for name in _GRAPH_REPLICATE:
            fields[name].append(arr[name])
        # triplets cross partitions — DimeNet is rejected by
        # validate_halo_support, so local views carry empty triplet arrays
        for name in ("idx_kj", "idx_ji"):
            fields[name].append(np.zeros(0, arr[name].dtype))
        fields["triplet_mask"].append(np.zeros(0, arr["triplet_mask"].dtype))

    stacked = GraphBatch(
        *[np.stack(fields[name]) for name in GraphBatch._fields[:-1]],
        meta=None,
    )

    send_steps, recv_steps = [], []
    for shift in range(1, n_parts):
        widths = [
            len(halos.get((d, (d + shift) % n_parts), ())) for d in range(n_parts)
        ]
        s_w = _round_up(max(widths), cfg.slot_multiple) if max(widths) else 0
        send = np.zeros((n_parts, s_w), np.int32)
        recv = np.full((n_parts, s_w), n_loc - 1, np.int32)
        for d in range(n_parts):
            dst = (d + shift) % n_parts
            ids = halos.get((d, dst))
            if ids is not None:
                send[d, : len(ids)] = loc_of[d][ids]  # owned rows on d
                recv[dst, : len(ids)] = loc_of[dst][ids]  # halo slots on dst
        send_steps.append(send)
        recv_steps.append(recv)

    return HaloBatch(
        batch=stacked,
        plan=HaloPlan(send_idx=tuple(send_steps), recv_slot=tuple(recv_steps)),
        node_global=node_global,
        n_owned=n_owned.astype(np.int32),
    )


def put_halo_batch(
    batch: GraphBatch,
    mesh: Mesh,
    cfg: HaloConfig | None = None,
    cutoff: float | None = None,
) -> HaloBatch:
    """Partition + place one frame: every leaf's leading (device) axis lands
    on the mesh's data axis, so each device holds exactly its local view."""
    cfg = cfg or HaloConfig()
    n_dev = mesh.shape[DATA_AXIS]
    if cfg.partitions and cfg.partitions != n_dev:
        raise ValueError(
            f"halo.partitions={cfg.partitions} != data-axis size {n_dev}; "
            "set 0 to follow the mesh"
        )
    hbatch = partition_graph_batch(batch, n_dev, cfg=cfg, cutoff=cutoff)
    sh = NamedSharding(mesh, P(DATA_AXIS))
    return jax.tree.map(lambda a: jax.device_put(jnp.asarray(a), sh), hbatch)


# -- analytic comm model ------------------------------------------------------

def halo_boundary_bytes(plan: HaloPlan, feat_dim: int, bytes_per_el: int = 4) -> int:
    """Fabric bytes ONE conv layer's halo refresh moves, summed over devices:
    every ring step ships its bucket-padded [S, F] buffer from each device."""
    rows = sum(int(s.shape[0]) * int(s.shape[1]) for s in plan.send_idx)
    return rows * int(feat_dim) * int(bytes_per_el)


def replicated_allreduce_bytes(
    n_nodes: int, feat_dim: int, n_dev: int, bytes_per_el: int = 4
) -> int:
    """Fabric bytes one ring all-reduce of the replicated [N, F] accumulator
    moves, summed over devices: 2 (N F / D) (D - 1) per device (reduce-scatter
    + all-gather), x D devices — the per-layer cost of the edge-sharded
    route this module replaces."""
    return 2 * (int(n_dev) - 1) * int(n_nodes) * int(feat_dim) * int(bytes_per_el)


# -- shard_map'd steps --------------------------------------------------------

def _halo_model(model: HydraModel) -> HydraModel:
    """The same architecture with feature-norm statistics psum'd over the
    data axis — under a partitioned node set, per-device BatchNorm moments
    are not the union-graph moments (parameter tree is unchanged, so the
    caller's TrainState is used as-is)."""
    return HydraModel(
        spec=dataclasses.replace(model.spec, bn_sync_axis=DATA_AXIS)
    )


def _squeeze_local(tree):
    return jax.tree.map(lambda a: a[0], tree)


def _refresh_fn(plan_local, n_dev):
    """Per-device halo refresh: for each ring shift, gather the boundary
    rows, rotate them ``shift`` devices down the data axis, scatter into the
    matching halo slots. Gathers touch only the owned region and scatters
    only the halo region, so steps compose in any order."""
    def refresh(inv, equiv):
        h = inv
        for i, (snd, rcv) in enumerate(plan_local):
            if snd.shape[0] == 0:
                continue  # statically empty shift (bucket width 0)
            shift = i + 1
            perm = [(d, (d + shift) % n_dev) for d in range(n_dev)]
            h = h.at[rcv].set(jax.lax.ppermute(h[snd], DATA_AXIS, perm))
        return h, equiv

    return refresh


def _pool_reduce_fn(kind: str, batch: GraphBatch):
    """Merge per-device partial graph readouts into the union-graph pooled
    value, matching the single-device reduction per pooling kind."""
    if kind in ("add", "sum"):
        return lambda pooled: jax.lax.psum(pooled, DATA_AXIS)
    if kind == "mean":
        def merge(pooled):
            cnt = segment_count(
                batch.batch, batch.num_graphs, weights=batch.node_mask
            )
            num = jax.lax.psum(pooled * cnt[:, None], DATA_AXIS)
            den = jax.lax.psum(cnt, DATA_AXIS)
            return num / jnp.maximum(den, 1e-12)[:, None]

        return merge
    if kind == "max":
        return lambda pooled: jax.lax.pmax(pooled, DATA_AXIS)
    if kind == "min":
        return lambda pooled: jax.lax.pmin(pooled, DATA_AXIS)
    raise ValueError(f"halo partitioning: unsupported graph_pooling {kind!r}")


def make_halo_train_step(
    model: HydraModel, optimizer, mesh: Mesh, compute_dtype=jnp.float32,
    loss_scale=None,
):
    """Training step over halo-partitioned batches: identical contract to
    ``make_train_step`` (scalar loss / tasks_loss / num_graphs metrics), so
    the non-finite guard and the epoch loop compose unchanged."""
    validate_halo_support(model.spec)
    hmodel = _halo_model(model)
    n_dev = mesh.shape[DATA_AXIS]

    def device_fn(state: TrainState, hbatch: HaloBatch):
        batch = _squeeze_local(hbatch.batch)
        plan_local = [
            (s[0], r[0])
            for s, r in zip(hbatch.plan.send_idx, hbatch.plan.recv_slot)
        ]
        refresh = _refresh_fn(plan_local, n_dev)
        pool_reduce = _pool_reduce_fn(hmodel.spec.graph_pooling, batch)
        rng = dropout_rng(state)

        def loss_fn(p):
            c_params = _cast_floats(p, compute_dtype)
            c_batch = _cast_floats(batch, compute_dtype)
            outputs, updates = hmodel.apply(
                {"params": c_params, "batch_stats": state.batch_stats},
                c_batch,
                train=True,
                mutable=["batch_stats"],
                rngs={"dropout": rng},
                layer_hook=refresh,
                pool_reduce=pool_reduce,
            )
            pred = _cast_floats(outputs, jnp.float32)
            # psum'd masked means: every device holds the exact union loss
            tot, tasks = hmodel.loss(pred, batch, loss_axis=DATA_AXIS)
            return tot, (tasks, updates["batch_stats"])

        (tot, (tasks, new_stats)), grads = scaled_value_and_grad(
            loss_fn, loss_scale
        )(state.params)
        # pmean, NOT psum: every device seeds ITS copy of the (replicated,
        # psum'd) loss with cotangent 1, so the jointly-differentiated
        # objective is sum_d L_d = D * L — the cross-device mean of the
        # local grads is exactly dL/dp (D a power of two on real meshes,
        # so the /D is even bit-exact)
        grads = jax.lax.pmean(grads, DATA_AXIS)
        new_state = apply_gradients(
            state, grads, new_stats, optimizer, hmodel.spec, loss_scale
        )
        metrics = {
            "loss": tot,
            "tasks_loss": jnp.stack(tasks),
            "num_graphs": batch.graph_mask.sum(),
        }
        return new_state, metrics

    sharded = shard_map(
        device_fn,
        mesh=mesh,
        in_specs=(P(), P(DATA_AXIS)),
        out_specs=(P(), P()),
        # outputs are replicated by construction (psum'd loss/grads feed
        # every update) but flow through gathers/scatters the static
        # replication checker cannot track
        check_vma=False,
    )

    @_partial(jax.jit, donate_argnums=donate_state_argnums())
    def train_step(state: TrainState, hbatch: HaloBatch):
        return sharded(state, hbatch)

    return train_step


def make_halo_eval_step(model: HydraModel, mesh: Mesh, compute_dtype=jnp.float32):
    """(state, halo batch) -> metrics matching ``make_eval_step``'s keys;
    per-head SSE/count sums are psum'd so the epoch RMSE accumulators see
    union-graph totals."""
    validate_halo_support(model.spec)
    hmodel = _halo_model(model)
    n_dev = mesh.shape[DATA_AXIS]

    def device_fn(params, batch_stats, hbatch: HaloBatch):
        batch = _squeeze_local(hbatch.batch)
        plan_local = [
            (s[0], r[0])
            for s, r in zip(hbatch.plan.send_idx, hbatch.plan.recv_slot)
        ]
        c_params = _cast_floats(params, compute_dtype)
        c_batch = _cast_floats(batch, compute_dtype)
        outputs = hmodel.apply(
            {"params": c_params, "batch_stats": batch_stats},
            c_batch,
            train=False,
            layer_hook=_refresh_fn(plan_local, n_dev),
            pool_reduce=_pool_reduce_fn(hmodel.spec.graph_pooling, batch),
        )
        pred = _cast_floats(outputs, jnp.float32)
        tot, tasks = hmodel.loss(pred, batch, loss_axis=DATA_AXIS)
        sses, counts = hmodel.head_sse(pred, batch)
        # node-head rows are PARTITIONED (psum = union total); graph-head
        # rows are REPLICATED on every device (psum over-counts by D)
        scale = jnp.array(
            [1.0 / n_dev if k == "graph" else 1.0 for k in hmodel.spec.output_type]
        )
        return {
            "loss": tot,
            "tasks_loss": jnp.stack(tasks),
            "head_sse": jax.lax.psum(jnp.stack(sses), DATA_AXIS) * scale,
            "head_count": jax.lax.psum(jnp.stack(counts), DATA_AXIS) * scale,
            "num_graphs": batch.graph_mask.sum(),
        }

    sharded = shard_map(
        device_fn,
        mesh=mesh,
        in_specs=(P(), P(), P(DATA_AXIS)),
        out_specs=P(),
        check_vma=False,
    )

    @jax.jit
    def eval_step(state: TrainState, hbatch: HaloBatch):
        return sharded(state.params, state.batch_stats, hbatch)

    return eval_step


def make_halo_apply(model: HydraModel, mesh: Mesh, compute_dtype=jnp.float32):
    """Jitted halo forward. Returns per-head outputs: graph heads replicated
    ``[G, d]``, node heads stacked ``[D, N_loc, d]`` (reassemble with
    ``gather_node_predictions``)."""
    validate_halo_support(model.spec)
    hmodel = _halo_model(model)
    n_dev = mesh.shape[DATA_AXIS]
    kinds = tuple(hmodel.spec.output_type)

    def device_fn(variables, hbatch: HaloBatch):
        batch = _squeeze_local(hbatch.batch)
        plan_local = [
            (s[0], r[0])
            for s, r in zip(hbatch.plan.send_idx, hbatch.plan.recv_slot)
        ]
        c_vars = {
            "params": _cast_floats(variables["params"], compute_dtype),
            "batch_stats": variables.get("batch_stats", {}),
        }
        outputs = hmodel.apply(
            c_vars,
            _cast_floats(batch, compute_dtype),
            train=False,
            layer_hook=_refresh_fn(plan_local, n_dev),
            pool_reduce=_pool_reduce_fn(hmodel.spec.graph_pooling, batch),
        )
        if hmodel.spec.var_output:
            outputs, _ = outputs
        outputs = [_cast_floats(o, jnp.float32) for o in outputs]
        # node heads keep their leading device axis; graph heads are
        # replicated (pool_reduce psums feed them)
        return [
            o if kind == "graph" else o[None] for o, kind in zip(outputs, kinds)
        ]

    out_specs = [P() if kind == "graph" else P(DATA_AXIS) for kind in kinds]
    sharded = shard_map(
        device_fn,
        mesh=mesh,
        in_specs=(P(), P(DATA_AXIS)),
        out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(sharded)


def gather_node_predictions(
    stacked: np.ndarray, hbatch: HaloBatch
) -> np.ndarray:
    """Host-side reassembly of a node head's ``[D, N_loc, d]`` output into
    global node order ``[N_real, d]`` using the owned-slot global ids."""
    stacked = np.asarray(stacked)
    node_global = np.asarray(hbatch.node_global)
    n_owned = np.asarray(hbatch.n_owned)
    n_real = int(max(node_global.max(), -1)) + 1
    out = np.zeros((n_real,) + stacked.shape[2:], stacked.dtype)
    for d in range(stacked.shape[0]):
        k = int(n_owned[d])
        out[node_global[d, :k]] = stacked[d, :k]
    return out
