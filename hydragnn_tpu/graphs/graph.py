"""Padded, statically-shaped graph batches — the TPU-native replacement for
PyG ``Data``/``Batch``.

Design (differs deliberately from the reference):

* The reference (ORNL/HydraGNN) batches variable-size graphs with PyG's ragged
  ``Batch`` and indexes multi-head targets through a concatenated ``data.y`` plus
  per-sample ``y_loc`` offset tensors (``hydragnn/preprocess/
  graph_samples_checks_and_updates.py:604-645``, consumed by ``get_head_indices``
  in ``hydragnn/train/train_validate_test.py:494-557``). Ragged shapes and
  gather-by-offset are hostile to XLA: every batch would recompile.

* Here every batch is padded to a static ``(n_node, n_edge, n_graph)`` bucket so
  each bucket jit-compiles exactly once. Padded nodes/edges belong to a dummy
  *padding graph* (the last graph slot), mirroring jraph's convention. Targets
  are stored **columnar**: ``graph_y[, G, sum(graph head dims)]`` and
  ``node_y[N, sum(node head dims)]`` — each head owns a fixed column slice, so
  head indexing is a static slice instead of dynamic gather.

All fields are numpy/jax arrays; the structure is a pytree (NamedTuple) and can
cross ``jit``/``pjit`` boundaries and be sharded along the leading axis.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

Array = Any  # np.ndarray on host, jax.Array on device


class BatchMeta(NamedTuple):
    """Host-verified STATIC layout guarantees for a batch, decided at collate
    time and carried as pytree *aux data* (not a leaf): two batches with
    different guarantees have different treedefs, so ``jit`` automatically
    traces each combination once and every in-program fast-path/fallback
    choice below becomes trace-time static — no ``lax.cond`` that would
    degrade to executing BOTH branches under ``vmap`` (the SPMD path).

    ``None`` for any field means "unknown" (e.g. a hand-built batch): the
    consuming op keeps its dynamic in-program fallback.

    - ``gs_fits``: every 256-edge block of (senders, receivers) spans a node
      window ≤ 256 — the fused gather-scatter kernel's layout contract
      (``ops.fused_scatter.fused_gather_scatter``), valid for both the fwd
      and the transposed bwd kernel since the check covers both arrays.
    - ``recv_fits`` / ``send_fits`` / ``pool_fits``: the scatter-only kernel's
      contract (window 128) for edge→node reductions keyed by receivers /
      senders and node→graph pooling keyed by ``batch``.
    - ``attn_fits``: the fused segment-softmax kernel's contract
      (``ops.fused_softmax``, window 256) for the self-loop-extended receiver
      array GAT attention builds (real edges + ``self_loop_pad`` alignment
      slots + one arange(N) self-loop section).
    - ``max_n_node``: static upper bound on per-graph node count (rounded up
      to a power of two so retrace count stays O(log N)); lets GPS pick
      dense-block vs flat attention at trace time.
    - ``triplet_rows``: ``"kj"`` / ``"ji"`` where the triplet dimension is a
      dense ``[E, K]`` block with that side of a triplet as its row (the
      bucket's ``PadSpec.triplet_rows``; see ``GraphBatch``), ``None`` for
      the flat list.
    """

    gs_fits: bool | None = None
    recv_fits: bool | None = None
    send_fits: bool | None = None
    pool_fits: bool | None = None
    max_n_node: int | None = None
    attn_fits: bool | None = None
    triplet_rows: str | None = None

    @staticmethod
    def merge(metas: "list[BatchMeta | None]") -> "BatchMeta | None":
        """Conservative merge for stacked per-device batches: a guarantee
        holds for the stack only if it holds for every member."""
        if any(m is None for m in metas) or not metas:
            return None

        def all_or_none(vals):
            if any(v is None for v in vals):
                return None
            return all(vals)

        return BatchMeta(
            gs_fits=all_or_none([m.gs_fits for m in metas]),
            recv_fits=all_or_none([m.recv_fits for m in metas]),
            send_fits=all_or_none([m.send_fits for m in metas]),
            pool_fits=all_or_none([m.pool_fits for m in metas]),
            max_n_node=(
                None
                if any(m.max_n_node is None for m in metas)
                else max(m.max_n_node for m in metas)
            ),
            attn_fits=all_or_none([m.attn_fits for m in metas]),
            triplet_rows=metas[0].triplet_rows,  # the bucket's: the members share it
        )


class GraphBatch(NamedTuple):
    """A batch of graphs padded to static shapes.

    Shapes (N = padded node count, E = padded edge count, G = padded graph
    count, incl. one trailing dummy graph absorbing padding):

    - ``x``:        [N, F_in]   invariant node features
    - ``pos``:      [N, 3]      atomic positions (zeros when absent)
    - ``senders``:  [E]         edge source node ids (messages flow s -> r)
    - ``receivers``:[E]         edge target node ids
    - ``edge_attr``:[E, F_e]    edge features (zeros / zero-width when absent)
    - ``edge_shifts``:[E, 3]    PBC cell shift vectors (r_vec = pos[r] - pos[s] + shift)
    - ``batch``:    [N]         node -> graph segment ids
    - ``graph_attr``:[G, F_g]   per-graph conditioning features
    - ``graph_y``:  [G, Yg]     columnar graph-level targets
    - ``node_y``:   [N, Yn]     columnar node-level targets
    - ``energy_y``: [G, 1]      MLIP total energy target
    - ``forces_y``: [N, 3]      MLIP force targets
    - ``node_mask``:[N]         1.0 for real nodes
    - ``edge_mask``:[E]         1.0 for real edges
    - ``graph_mask``:[G]        1.0 for real graphs
    - ``n_node``:   [G]         real node count per graph (0 for padding)
    - ``dataset_id``:[G]        multidataset branch id per graph (int32)
    - ``idx_kj``/``idx_ji``:[T] triplet edge-index pairs (DimeNet angles;
      zero-length unless the pipeline attaches triplets)
    - ``triplet_mask``:[T]      1.0 for real triplets
      Where ``meta.triplet_rows`` names a side, T = E x K is a dense block:
      slot ``r * K + s`` pairs row edge ``r`` with its s-th partner, the row
      side's index field is zero-length (it is ``slot // K``) and the partner
      side's holds the ``[N, K]`` table of the edge ids each atom sends
      (rows kj: ``idx_ji``) or receives (rows ji: ``idx_kj``), ``E - 1`` where
      empty; the partner of slot ``(r, s)`` is ``table[j(r), s]`` with ``j``
      the row's receiver (rows kj) or sender (rows ji). The layout lives in
      the meta, so a placement that drops the meta turns the block into
      lists first (``graphs.batching.flat_triplets``)
    - ``pe``:       [N, K]      Laplacian positional encodings (GPS; width 0
      unless the pipeline attaches them)
    - ``rel_pe``:   [E, K]      relative edge encodings |pe_i - pe_j|
    - ``z``:        [N]         raw atomic numbers (int32) — preserved BEFORE
      feature normalization so element-aware models (MACE one-hot Z) are not
      corrupted by min-max scaling of x
    """

    x: Array
    pos: Array
    senders: Array
    receivers: Array
    edge_attr: Array
    edge_shifts: Array
    batch: Array
    graph_attr: Array
    graph_y: Array
    node_y: Array
    energy_y: Array
    forces_y: Array
    node_mask: Array
    edge_mask: Array
    graph_mask: Array
    n_node: Array
    dataset_id: Array
    idx_kj: Array
    idx_ji: Array
    triplet_mask: Array
    pe: Array
    rel_pe: Array
    z: Array
    # STATIC aux metadata (BatchMeta | None) — part of the treedef, not a
    # leaf; see the explicit pytree registration below the class.
    meta: Any = None

    # -- static helpers -------------------------------------------------------
    @property
    def num_nodes(self) -> int:
        return self.x.shape[0]

    @property
    def num_edges(self) -> int:
        return self.senders.shape[0]

    @property
    def num_graphs(self) -> int:
        return self.graph_mask.shape[0]

    def edge_vectors(self) -> Array:
        """Relative position vectors along edges, honoring PBC shifts.

        The single geometry primitive shared by the equivariant stacks —
        reference ``hydragnn/utils/model/operations.py:21-36``
        (``get_edge_vectors_and_lengths``).
        """
        return self.pos[self.receivers] - self.pos[self.senders] + self.edge_shifts

    def edge_lengths(self, eps: float = 1e-12) -> Array:
        vec = self.edge_vectors()
        return jnp.sqrt(jnp.sum(vec * vec, axis=-1, keepdims=True) + eps)

    def replace(self, **kwargs) -> "GraphBatch":
        return self._replace(**kwargs)

    def seg_hint(self, segment_ids) -> bool | None:
        """Static window-fit hint for a segment reduction keyed by WHICH id
        array it uses — matched by object identity, which is stable for
        attribute reads off this NamedTuple (including tracers inside jit).
        Returns None (→ dynamic fallback) for unknown id arrays.

        Identity matching silently loses certification for transformed
        copies (``jnp.asarray``, re-indexed edges); ``SegHintStats`` counts
        trace-time certified-vs-dynamic resolutions so a regression that
        re-enters the dynamic path is visible (round-3 advisor note)."""
        m = self.meta
        if m is None:
            SegHintStats.dynamic += 1
            return None
        if segment_ids is self.receivers:
            hint = m.recv_fits
        elif segment_ids is self.senders:
            hint = m.send_fits
        elif segment_ids is self.batch:
            hint = m.pool_fits
        else:
            hint = None
        if hint is None:
            SegHintStats.dynamic += 1
        else:
            SegHintStats.certified += 1
        return hint


class SegHintStats:
    """Trace-time audit of layout-certificate hits: how many segment
    reductions resolved a static certificate vs fell back to the dynamic
    in-program check. Counters tick at TRACE time (cached executions don't
    re-count), so after a warmup epoch ``dynamic`` staying at its baseline
    proves no caller silently lost certification."""

    certified = 0
    dynamic = 0

    @classmethod
    def reset(cls) -> None:
        cls.certified = 0
        cls.dynamic = 0

    @classmethod
    def snapshot(cls) -> dict:
        return {"certified": cls.certified, "dynamic": cls.dynamic}


# Data fields (leaves) vs static metadata (aux): explicit registration takes
# precedence over JAX's built-in NamedTuple flattening, so ``meta`` rides the
# treedef — ``jax.tree.map`` never touches it and ``jit`` keys traces on it.
_DATA_FIELDS = GraphBatch._fields[:-1]
assert GraphBatch._fields[-1] == "meta"

jax.tree_util.register_pytree_with_keys(
    GraphBatch,
    lambda b: (
        tuple((jax.tree_util.GetAttrKey(f), getattr(b, f)) for f in _DATA_FIELDS),
        b.meta,
    ),
    lambda meta, children: GraphBatch(*children, meta=meta),
)


# ``jax.export`` serialization (serialized-AOT replica boot) must carry the
# treedef across processes, and the custom registration above makes
# GraphBatch NOT a plain namedtuple node: 23 data children + ``meta`` as
# static auxdata. Register the matching auxdata codec here, next to the
# flattening it mirrors — BatchMeta is JSON-plain (bools/ints/None) by
# construction, so a round trip reconstructs the exact treedef and ``jit``
# keys traces identically on both sides of the boot.
def _export_serialization() -> None:
    import json as _json

    from jax import export as _export

    def _ser_meta(meta):
        return _json.dumps(None if meta is None else list(meta)).encode()

    def _deser_meta(blob):
        payload = _json.loads(blob.decode())
        return None if payload is None else BatchMeta(*payload)

    _export.register_pytree_node_serialization(
        GraphBatch,
        serialized_name=f"{GraphBatch.__module__}.GraphBatch",
        serialize_auxdata=_ser_meta,
        deserialize_auxdata=_deser_meta,
    )


_export_serialization()


class GraphSample:
    """One host-side (numpy, unpadded) graph sample — the analog of PyG ``Data``.

    Produced by dataset loaders and the radius-graph preprocessors; consumed by
    ``hydragnn_tpu.graphs.batching.collate``. Plain attribute bag on purpose:
    cheap to construct in data-loading hot loops, pickleable.
    """

    __slots__ = (
        "x", "pos", "senders", "receivers", "edge_attr", "edge_shifts",
        "graph_attr", "graph_y", "node_y", "energy_y", "forces_y",
        "dataset_id", "cell", "pbc", "extras",
    )

    def __init__(
        self,
        x: np.ndarray,
        pos: np.ndarray | None = None,
        senders: np.ndarray | None = None,
        receivers: np.ndarray | None = None,
        edge_attr: np.ndarray | None = None,
        edge_shifts: np.ndarray | None = None,
        graph_attr: np.ndarray | None = None,
        graph_y: np.ndarray | None = None,
        node_y: np.ndarray | None = None,
        energy_y: np.ndarray | None = None,
        forces_y: np.ndarray | None = None,
        dataset_id: int = 0,
        cell: np.ndarray | None = None,
        pbc: np.ndarray | None = None,
        extras: dict | None = None,
    ):
        self.x = np.asarray(x, dtype=np.float32)
        n = self.x.shape[0]
        self.pos = (
            np.asarray(pos, dtype=np.float32)
            if pos is not None
            else np.zeros((n, 3), np.float32)
        )
        self.senders = (
            np.asarray(senders, dtype=np.int32) if senders is not None else np.zeros((0,), np.int32)
        )
        self.receivers = (
            np.asarray(receivers, dtype=np.int32)
            if receivers is not None
            else np.zeros((0,), np.int32)
        )
        e = self.senders.shape[0]
        self.edge_attr = (
            np.asarray(edge_attr, dtype=np.float32)
            if edge_attr is not None
            else np.zeros((e, 0), np.float32)
        )
        self.edge_shifts = (
            np.asarray(edge_shifts, dtype=np.float32)
            if edge_shifts is not None
            else np.zeros((e, 3), np.float32)
        )
        self.graph_attr = (
            np.asarray(graph_attr, dtype=np.float32).reshape(-1)
            if graph_attr is not None
            else np.zeros((0,), np.float32)
        )
        self.graph_y = (
            np.asarray(graph_y, dtype=np.float32).reshape(-1)
            if graph_y is not None
            else np.zeros((0,), np.float32)
        )
        self.node_y = (
            np.asarray(node_y, dtype=np.float32).reshape(n, -1)
            if node_y is not None
            else np.zeros((n, 0), np.float32)
        )
        self.energy_y = (
            np.asarray(energy_y, dtype=np.float32).reshape(1)
            if energy_y is not None
            else np.zeros((1,), np.float32)
        )
        self.forces_y = (
            np.asarray(forces_y, dtype=np.float32).reshape(n, 3)
            if forces_y is not None
            else np.zeros((n, 3), np.float32)
        )
        self.dataset_id = int(dataset_id)
        self.cell = None if cell is None else np.asarray(cell, dtype=np.float64).reshape(3, 3)
        self.pbc = None if pbc is None else np.asarray(pbc, dtype=bool).reshape(3)
        self.extras = extras or {}

    @property
    def num_nodes(self) -> int:
        return self.x.shape[0]

    @property
    def num_edges(self) -> int:
        return self.senders.shape[0]

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"GraphSample(nodes={self.num_nodes}, edges={self.num_edges}, "
            f"x={self.x.shape}, graph_y={self.graph_y.shape}, node_y={self.node_y.shape})"
        )
