"""Data pipeline: feature/target selection, splits, loader construction.

Reference counterparts:
* ``update_predicted_values`` + ``update_atom_features``
  (``hydragnn/preprocess/graph_samples_checks_and_updates.py:604-659``) —
  column-select inputs and build target layout. The reference concatenates
  targets into ragged ``data.y`` with ``y_loc`` offsets; here targets become
  columnar ``graph_y``/``node_y`` (static shapes — see graphs/graph.py).
* ``split_dataset`` (``hydragnn/preprocess/load_data.py:337-357``) — random
  split into train/val/test by ``perc_train``.
* ``create_dataloaders`` (``load_data.py:226-334``) — per-process
  DistributedSampler semantics via ``GraphLoader(rank, world)``.
"""

from __future__ import annotations

import numpy as np

from ..graphs.batching import GraphLoader, PadSpec, compute_pad_spec
from ..graphs.graph import GraphSample


def apply_variables_of_interest(samples, config: dict) -> list[GraphSample]:
    """Select model inputs (``input_node_features``) and build columnar targets
    from per-sample feature tables per ``Variables_of_interest``.

    Each sample must carry ``extras['node_table']`` ([N, F_node]) and
    ``extras['graph_table']`` ([F_graph]) — or already have x/graph_y/node_y
    set, in which case it passes through untouched.
    """
    voi = config["NeuralNetwork"]["Variables_of_interest"]
    ds = config.get("Dataset", {})
    input_cols = list(voi.get("input_node_features", []))
    output_type = list(voi.get("type", []))
    output_index = list(voi.get("output_index", []))

    node_dims = ds.get("node_features", {}).get("dim", [])
    node_cols = ds.get("node_features", {}).get("column_index", [])
    graph_dims = ds.get("graph_features", {}).get("dim", [])
    graph_cols = ds.get("graph_features", {}).get("column_index", [])

    out = []
    for s in samples:
        node_table = s.extras.get("node_table")
        graph_table = s.extras.get("graph_table")
        if node_table is None:
            out.append(s)
            continue
        node_table = np.asarray(node_table, np.float64)
        graph_table = np.asarray(graph_table, np.float64).reshape(-1)

        s.x = node_table[:, input_cols].astype(np.float32)
        # raw atomic numbers survive normalization (element-aware models)
        if input_cols:
            s.extras.setdefault("atomic_numbers", node_table[:, input_cols[0]].copy())

        graph_targets = []
        node_targets = []
        for otype, oidx in zip(output_type, output_index):
            if otype == "graph":
                col = graph_cols[oidx] if graph_cols else oidx
                dim = graph_dims[oidx] if graph_dims else 1
                graph_targets.append(graph_table[col : col + dim])
            elif otype == "node":
                col = node_cols[oidx] if node_cols else oidx
                dim = node_dims[oidx] if node_dims else 1
                node_targets.append(node_table[:, col : col + dim])
            else:
                raise ValueError(f"Unknown output type '{otype}'")
        s.graph_y = (
            np.concatenate(graph_targets).astype(np.float32)
            if graph_targets
            else np.zeros((0,), np.float32)
        )
        s.node_y = (
            np.concatenate(node_targets, axis=1).astype(np.float32)
            if node_targets
            else np.zeros((s.num_nodes, 0), np.float32)
        )
        out.append(s)
    return out


def normalize_features(samples) -> tuple[np.ndarray, np.ndarray]:
    """Min-max normalize x / graph_y / node_y in place over the dataset
    (the reference's raw-loader normalization, ``raw_dataset_loader.py``).
    Returns (node_minmax, graph_minmax) for later denormalization."""
    def _minmax(arrs):
        lo = np.min([a.min(axis=0) for a in arrs if a.size], axis=0)
        hi = np.max([a.max(axis=0) for a in arrs if a.size], axis=0)
        rng = np.where(hi - lo < 1e-12, 1.0, hi - lo)
        return lo, rng

    xs = [s.x for s in samples]
    lo_x, rng_x = _minmax(xs)
    for s in samples:
        s.x = ((s.x - lo_x) / rng_x).astype(np.float32)

    if samples and samples[0].node_y.shape[1]:
        lo_ny, rng_ny = _minmax([s.node_y for s in samples])
        for s in samples:
            s.node_y = ((s.node_y - lo_ny) / rng_ny).astype(np.float32)
    else:
        lo_ny = rng_ny = np.zeros((0,))
    if samples and samples[0].graph_y.shape[0]:
        gys = np.stack([s.graph_y for s in samples])
        lo_gy = gys.min(axis=0)
        rng_gy = np.where(gys.max(axis=0) - lo_gy < 1e-12, 1.0, gys.max(axis=0) - lo_gy)
        for s in samples:
            s.graph_y = ((s.graph_y - lo_gy) / rng_gy).astype(np.float32)
    else:
        lo_gy = rng_gy = np.zeros((0,))
    node_minmax = np.stack([np.concatenate([lo_x, lo_ny]), np.concatenate([lo_x + rng_x, lo_ny + rng_ny])]) if lo_ny.size or lo_x.size else np.zeros((2, 0))
    graph_minmax = np.stack([lo_gy, lo_gy + rng_gy]) if lo_gy.size else np.zeros((2, 0))
    return node_minmax, graph_minmax


def _composition_key(sample: GraphSample) -> tuple:
    """Composition signature: sorted (type, count) pairs of the first input
    feature column (the atom type in every reference dataset)."""
    if sample.x.size == 0:
        return ()
    types, counts = np.unique(sample.x[:, 0].round(6), return_counts=True)
    return tuple(zip(types.tolist(), counts.tolist()))


def split_dataset(samples, perc_train: float, stratify_splitting: bool = False, seed: int = 0):
    """Train/val/test split: val and test each get (1-perc_train)/2
    (reference ``load_data.py:337-357``). With ``stratify_splitting``, samples
    are grouped by atomic composition and each group is split proportionally
    (reference ``compositional_data_splitting.py``), so every split sees every
    composition."""
    rng = np.random.default_rng(seed)
    if stratify_splitting:
        groups: dict[tuple, list[int]] = {}
        for i, s in enumerate(samples):
            groups.setdefault(_composition_key(s), []).append(i)
        train_idx, val_idx, test_idx = [], [], []
        for key in sorted(groups):
            idx = np.asarray(groups[key])
            idx = idx[rng.permutation(len(idx))]
            n = len(idx)
            n_train = int(n * perc_train)
            n_val = int(n * (1.0 - perc_train) / 2.0)
            train_idx.extend(idx[:n_train].tolist())
            val_idx.extend(idx[n_train : n_train + n_val].tolist())
            test_idx.extend(idx[n_train + n_val :].tolist())
        perm_of = lambda lst: [samples[i] for i in lst]
        return perm_of(train_idx), perm_of(val_idx), perm_of(test_idx)
    n = len(samples)
    perm = rng.permutation(n)
    n_train = int(n * perc_train)
    n_val = int(n * (1.0 - perc_train) / 2.0)
    train = [samples[i] for i in perm[:n_train]]
    val = [samples[i] for i in perm[n_train : n_train + n_val]]
    test = [samples[i] for i in perm[n_train + n_val :]]
    return train, val, test


def create_dataloaders(
    trainset,
    valset,
    testset,
    batch_size: int,
    rank: int = 0,
    world: int = 1,
    pad: PadSpec | None = None,
    seed: int = 0,
    buckets: int | None = None,
    attn_cap: int = 0,
    triplet_cap: int = 0,
):
    """Three loaders over a shared pad-bucket table (so the XLA program count
    is bounded by the table size across all splits) and DistributedSampler
    semantics on the train split. ``buckets > 1`` pads each batch to the
    smallest of at most that many buckets instead of the dataset worst case
    (``Training.pad_buckets``): the worst case and, below it, the buckets
    that pad simulated batches of this corpus least
    (``graphs.batching.compute_pad_buckets``). ``triplet_cap``: the cap on an
    atom's edges that sizes the triplet pad dimension; where it holds on one
    side for the whole corpus that dimension is a dense block
    (``graphs.batching.compute_pad_spec`` works that out from the samples)."""
    from ..graphs.batching import compute_pad_buckets

    all_samples = list(trainset) + list(valset) + list(testset)
    # never let drop_last starve training: a dataset smaller than the batch
    # still yields one (smaller) batch per epoch
    batch_size = max(1, min(batch_size, len(trainset) // max(world, 1) or 1))
    bucket_list = (
        compute_pad_buckets(all_samples, batch_size, max_buckets=buckets,
                            attn_cap=attn_cap, triplet_cap=triplet_cap)
        if buckets and buckets > 1
        else None
    )
    pad = pad or compute_pad_spec(all_samples, batch_size, attn_cap=attn_cap,
                                  triplet_cap=triplet_cap)
    train_loader = GraphLoader(
        trainset, batch_size, pad=pad, shuffle=True, seed=seed, rank=rank, world=world,
        buckets=bucket_list,
    )
    # val/test may legitimately be empty (tiny datasets, perc_train=1.0);
    # the train loop skips evaluation then
    val_loader = GraphLoader(
        valset, batch_size, pad=pad, drop_last=False, rank=rank, world=world,
        buckets=bucket_list,
    )
    test_loader = GraphLoader(
        testset, batch_size, pad=pad, drop_last=False, rank=rank, world=world,
        buckets=bucket_list,
    )
    return train_loader, val_loader, test_loader


def dataset_loading_and_splitting(config: dict, samples=None, rank: int = 0, world: int = 1):
    """Reference ``dataset_loading_and_splitting`` (``load_data.py:207-223``):
    raw -> selected/normalized -> split -> loaders. ``samples`` may be supplied
    directly (unit-test path); otherwise the ``Dataset.format`` dispatches to a
    raw loader (LSMS/CFG/XYZ/pickle — built out in the datasets package)."""
    if samples is None:
        from ..datasets import load_raw_dataset

        samples = load_raw_dataset(config)
    training = config.setdefault("NeuralNetwork", {}).setdefault("Training", {})
    # rotation normalization BEFORE edge construction (reference
    # serialized_dataset_loader.py:130-132, Dataset.rotational_invariance)
    if config["Dataset"].get("rotational_invariance"):
        from .transforms import normalize_rotation

        samples = [normalize_rotation(s) for s in samples]
    # raw-format samples arrive without neighbor lists: build radius graphs
    # from the architecture's cutoff (reference SerializedDataLoader
    # ``load_serialized_data`` radius-graph pass, serialized_dataset_loader.py:134-150)
    arch_pre = config["NeuralNetwork"].get("Architecture", {})
    radius = arch_pre.get("radius")
    if radius and any(s.num_edges == 0 and s.num_nodes > 1 for s in samples):
        from ..graphs.radius import build_radius_graph

        for s in samples:
            if s.num_edges == 0 and s.num_nodes > 1:
                build_radius_graph(
                    s, float(radius), max_neighbours=arch_pre.get("max_neighbours"),
                    ensure_connected=bool(arch_pre.get("ensure_connected", True)),
                )
    # edge-length + geometric descriptor columns (reference :152-180):
    # Distance(cat=True) + dataset/processes-global max normalization, then
    # Spherical / PointPairFeatures appended to edge_attr
    desc_cfg = config["Dataset"].get("Descriptors", {}) or {}
    if config["Dataset"].get("compute_edge_lengths"):
        from .transforms import attach_edge_lengths, normalize_edge_lengths_global

        for s in samples:
            attach_edge_lengths(s)
        normalize_edge_lengths_global(samples)
    if desc_cfg.get("spherical_coordinates"):
        from .transforms import spherical_features

        for s in samples:
            spherical_features(s)
    if desc_cfg.get("point_pair_features"):
        from .transforms import point_pair_features

        for s in samples:
            point_pair_features(s)

    samples = apply_variables_of_interest(samples, config)
    # stratified composition subsampling (reference :214-259)
    sub_pct = config["NeuralNetwork"].get("Variables_of_interest", {}).get(
        "subsample_percentage"
    )
    if sub_pct:
        from .transforms import stratified_subsample

        samples = stratified_subsample(samples, float(sub_pct))
    arch_cfg = config["NeuralNetwork"].get("Architecture", {})
    triplet_cap = 0
    if arch_cfg.get("mpnn_type") == "DimeNet":
        # DimeNet mixes (kj, ji) edge pairs. With a cap on an atom's edges the
        # pad buckets follow from it and collate enumerates each batch's
        # triplets from its edges: as a dense [E, K] block where the cap holds
        # on ONE side for every sample (``compute_pad_spec`` decides which
        # side is the row, once, for every bucket), else as a flat list;
        # without a cap the samples carry their lists, and their counts size
        # the buckets (graphs/triplets.py)
        from ..graphs.triplets import attach_triplets, degree_cap

        triplet_cap = int(arch_cfg.get("max_neighbours") or 0)
        if triplet_cap:  # a loose cap gives way to what the samples bear out
            triplet_cap = min(triplet_cap, degree_cap(samples))
        else:
            for s in samples:
                if "idx_kj" not in s.extras:
                    attach_triplets(s)
    if arch_cfg.get("global_attn_engine") == "GPS":
        # GPS needs Laplacian positional encodings (reference
        # serialized_dataset_loader.py:183-189); without GPS nothing reads
        # them, so don't pay the per-sample eigendecomposition
        from .encodings import attach_lap_pe

        k = int(arch_cfg.get("pe_dim") or 1)
        for s in samples:
            attach_lap_pe(s, k)
    if config["NeuralNetwork"]["Variables_of_interest"].get("denormalize_output") or config[
        "Dataset"
    ].get("normalize", True):
        node_minmax, graph_minmax = normalize_features(samples)
        config["NeuralNetwork"]["Variables_of_interest"]["minmax_node_feature"] = (
            node_minmax.tolist()
        )
        config["NeuralNetwork"]["Variables_of_interest"]["minmax_graph_feature"] = (
            graph_minmax.tolist()
        )
    train, val, test = split_dataset(
        samples,
        perc_train=float(training.get("perc_train", 0.7)),
        stratify_splitting=config["Dataset"].get("compositional_stratified_splitting", False),
    )
    bs = int(training.get("batch_size", 32))
    return create_dataloaders(
        train, val, test, bs, rank=rank, world=world,
        buckets=int(training.get("pad_buckets", 0) or 0) or None,
        # a USER-set dense-attention cap (GPS max_graph_nodes) below the
        # dataset max: collate certifies against it so fitting batches keep
        # the dense-block path (see PadSpec.attn_cap)
        attn_cap=(
            int(arch_cfg.get("max_graph_nodes") or 0)
            if arch_cfg.get("global_attn_engine")
            else 0
        ),
        triplet_cap=triplet_cap,
    )
