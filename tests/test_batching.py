"""Collate/pad/loader tests."""

import numpy as np
import pytest

from hydragnn_tpu.graphs import GraphLoader, GraphSample, PadSpec, collate, compute_pad_spec


def make_sample(n, e, fx=3, yg=2, yn=1, seed=0):
    rng = np.random.default_rng(seed)
    return GraphSample(
        x=rng.normal(size=(n, fx)),
        pos=rng.normal(size=(n, 3)),
        senders=rng.integers(0, n, size=e),
        receivers=rng.integers(0, n, size=e),
        graph_y=rng.normal(size=(yg,)),
        node_y=rng.normal(size=(n, yn)),
    )


def test_collate_shapes_and_masks():
    samples = [make_sample(4, 7, seed=1), make_sample(6, 9, seed=2)]
    pad = PadSpec(n_node=16, n_edge=32, n_graph=4)
    b = collate(samples, pad)
    assert b.x.shape == (16, 3)
    assert b.senders.shape == (32,)
    assert b.graph_y.shape == (4, 2)
    assert b.node_mask.sum() == 10
    assert b.edge_mask.sum() == 16
    assert b.graph_mask.sum() == 2
    # second sample's nodes shifted by first sample's node count
    np.testing.assert_array_equal(b.batch[:4], 0)
    np.testing.assert_array_equal(b.batch[4:10], 1)
    # padding nodes assigned to dummy graph
    np.testing.assert_array_equal(b.batch[10:], 3)
    # padded edges point at last (padded) node
    np.testing.assert_array_equal(b.senders[16:], 15)
    assert b.n_node[0] == 4 and b.n_node[1] == 6


def test_collate_overflow_raises():
    samples = [make_sample(10, 5)]
    with pytest.raises(ValueError):
        collate(samples, PadSpec(n_node=8, n_edge=32, n_graph=2))
    with pytest.raises(ValueError):
        collate(samples, PadSpec(n_node=32, n_edge=4, n_graph=2))
    with pytest.raises(ValueError):
        collate(samples * 3, PadSpec(n_node=64, n_edge=64, n_graph=3))


def test_compute_pad_spec_fits():
    samples = [make_sample(5, 11, seed=i) for i in range(5)]
    pad = compute_pad_spec(samples, batch_size=3)
    b = collate(samples[:3], pad)
    assert b.node_mask.sum() == 15


def test_loader_epoch_determinism_and_sharding():
    samples = [make_sample(4, 6, seed=i) for i in range(12)]
    loader = GraphLoader(samples, batch_size=2, shuffle=True, seed=42)
    loader.set_epoch(0)
    first = [np.asarray(b.x).copy() for b in loader]
    loader.set_epoch(0)
    again = [np.asarray(b.x) for b in loader]
    for a, c in zip(first, again):
        np.testing.assert_array_equal(a, c)
    loader.set_epoch(1)
    shuffled = [np.asarray(b.x) for b in loader]
    assert any(not np.array_equal(a, c) for a, c in zip(first, shuffled))

    # rank sharding covers the dataset disjointly
    l0 = GraphLoader(samples, batch_size=2, rank=0, world=2)
    l1 = GraphLoader(samples, batch_size=2, rank=1, world=2)
    assert len(l0) == len(l1) == 3
    seen0 = set(l0._epoch_indices().tolist())
    seen1 = set(l1._epoch_indices().tolist())
    assert seen0 | seen1 == set(range(12))
    assert seen0 & seen1 == set()


def test_edge_vectors_with_shifts():
    import jax.numpy as jnp

    s = make_sample(3, 2)
    s.senders = np.array([0, 1], np.int32)
    s.receivers = np.array([1, 2], np.int32)
    s.edge_shifts = np.array([[1.0, 0, 0], [0, 0, 0]], np.float32)
    pad = PadSpec(8, 8, 2)
    b = collate([s], pad)
    vec = np.asarray(b.edge_vectors())
    expected0 = s.pos[1] - s.pos[0] + np.array([1.0, 0, 0])
    np.testing.assert_allclose(vec[0], expected0, rtol=1e-5)


def test_collate_requires_reserved_padding_node():
    # exactly filling the node slots must be rejected: padded edges wire to
    # node n_node-1 which would then be a real node
    s = make_sample(8, 2)
    with pytest.raises(ValueError):
        collate([s], PadSpec(n_node=8, n_edge=8, n_graph=2))
    collate([s], PadSpec(n_node=9, n_edge=8, n_graph=2))  # one spare -> fine


def test_stratified_split_covers_compositions():
    from hydragnn_tpu.preprocess import split_dataset
    # two distinct compositions, 10 samples each
    samples = []
    for i in range(20):
        s = make_sample(4, 6, seed=i)
        s.x[:, 0] = float(i % 2)  # composition marker
        samples.append(s)
    train, val, test = split_dataset(samples, perc_train=0.6, stratify_splitting=True)
    for split in (train, val, test):
        comps = {float(s.x[0, 0]) for s in split}
        assert comps == {0.0, 1.0}, "every split must see every composition"
    assert len(train) + len(val) + len(test) == 20


def test_empty_split_trains_without_valtest():
    import hydragnn_tpu
    from test_config import CI_CONFIG
    import copy
    from hydragnn_tpu.datasets import deterministic_graph_data
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 1
    cfg["NeuralNetwork"]["Training"]["perc_train"] = 1.0
    samples = deterministic_graph_data(number_configurations=20, seed=4)
    state, model, aug = hydragnn_tpu.run_training(cfg, samples=samples)
    assert state.step > 0


# ---------- bucketed padding (SURVEY §7 step 1) ----------


def mixed_size_samples(n=200, seed=0):
    """Bimodal dataset: many small molecules + a few big crystals — the GFM
    mix where a single worst-case bucket wastes most of every step."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        big = rng.uniform() < 0.1
        nn_ = int(rng.integers(40, 60)) if big else int(rng.integers(8, 16))
        ee = nn_ * 6
        out.append(make_sample(nn_, ee, seed=int(rng.integers(1 << 30))))
    return out


def test_pad_buckets_bounded_and_fitting():
    from hydragnn_tpu.graphs.batching import compute_pad_buckets

    samples = mixed_size_samples()
    buckets = compute_pad_buckets(samples, batch_size=16, max_buckets=4)
    assert 1 <= len(buckets) <= 4
    # component-wise nested so the largest per-rank pick fits all ranks
    for a, b in zip(buckets, buckets[1:]):
        assert a.n_node <= b.n_node and a.n_edge <= b.n_edge
    loader = GraphLoader(samples, 16, shuffle=True, buckets=buckets)
    seen = set()
    for batch in loader:
        seen.add(batch.x.shape[0])
        assert batch.node_mask.sum() < batch.x.shape[0]  # reserved pad node
    assert len(seen) <= 4  # compile count bounded by bucket table


def test_pad_buckets_reduce_padding_waste():
    samples = mixed_size_samples()
    single = GraphLoader(samples, 16, shuffle=True)
    bucketed = GraphLoader(samples, 16, shuffle=True, buckets=4)

    def waste(loader):
        tot_slots = tot_real = 0
        for b in loader:
            tot_slots += b.x.shape[0]
            tot_real += int(b.node_mask.sum())
        return 1.0 - tot_real / tot_slots

    w_single, w_bucketed = waste(single), waste(bucketed)
    assert w_bucketed < w_single * 0.8, (w_single, w_bucketed)


def test_bucket_choice_identical_across_ranks():
    """SPMD safety: every rank must pick the same bucket at the same step."""
    samples = mixed_size_samples()
    shapes = []
    for rank in (0, 1):
        loader = GraphLoader(
            samples, 8, shuffle=True, seed=3, rank=rank, world=2, buckets=4
        )
        loader.set_epoch(5)
        shapes.append([b.x.shape[0] for b in loader])
    assert shapes[0] == shapes[1]


def test_bucketed_loader_bounded_compile_count():
    import jax
    import jax.numpy as jnp

    samples = mixed_size_samples(120)
    loader = GraphLoader(samples, 16, shuffle=True, buckets=3)
    traces = []

    @jax.jit
    def pool(x, mask):
        traces.append(x.shape)
        return (x * mask[:, None]).sum()

    for epoch in range(2):
        loader.set_epoch(epoch)
        for b in loader:
            pool(jnp.asarray(b.x), jnp.asarray(b.node_mask))
    assert len(traces) <= 3, f"recompile churn: {traces}"


# ---------- where the bucket table stands (ISSUE 41) ----------


def heavy_tailed_samples(n=300, seed=1):
    """Lognormal sizes and one giant: the corpus whose worst-case bucket is
    many times its typical batch."""
    rng = np.random.default_rng(seed)
    atoms = np.clip(np.rint(rng.lognormal(np.log(12.0), 0.7, n)), 2, 150).astype(int)
    atoms[7] = 150
    return [make_sample(int(a), int(a) * 4, seed=i) for i, a in enumerate(atoms)]


def one_size_samples(n=40):
    return [make_sample(9, 36, seed=i) for i in range(n)]


def capped_samples(n=60, k=4, seed=2):
    """Every atom sends exactly ``k`` edges: what a ``triplet_cap`` of k reads."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        a = int(rng.integers(3, 30))
        s = make_sample(a, a * k, seed=i)
        s.senders = np.repeat(np.arange(a, dtype=np.int32), k)
        out.append(s)
    return out


CORPORA = {"mixed": mixed_size_samples, "heavy_tailed": heavy_tailed_samples}


def quantile_buckets(samples, batch_size, max_buckets, n_sim, seed=0):
    """The table rule of PR 39 and before: the lower buckets at the 0.5 / 0.8 /
    0.95 quantiles of the simulated batch totals, each dimension on its own."""
    worst = compute_pad_spec(samples, batch_size)
    totals = simulated_totals(samples, batch_size, n_sim, seed)
    table = []
    for q in (0.5, 0.8, 0.95)[: max_buckets - 1]:
        n, e = np.quantile(totals, q, axis=0)
        spec = PadSpec(min(-(-(int(n) + 1) // 8) * 8, worst.n_node),
                       min(-(-int(e) // 128) * 128, worst.n_edge), batch_size + 1)
        if spec not in table and spec != worst:
            table.append(spec)
    return table + [worst]


def simulated_totals(samples, batch_size, n_sim, seed=0):
    """(nodes, edges) of the batches ``compute_pad_buckets`` simulates."""
    sizes = np.array([(s.num_nodes, s.num_edges) for s in samples], np.int64)
    draws = np.random.default_rng(seed).integers(0, len(samples), size=(n_sim, batch_size))
    return sizes[draws].sum(axis=1)


def mean_edge_slots(table, totals):
    from hydragnn_tpu.graphs.batching import pick_bucket

    return np.mean([(pick_bucket(table, int(n), int(e)) or table[-1]).n_edge
                    for n, e in totals])


@pytest.mark.parametrize("max_buckets", [2, 3, 4])
@pytest.mark.parametrize("corpus", sorted(CORPORA))
def test_cost_placed_table_pads_no_more_than_the_quantile_table(corpus, max_buckets):
    from hydragnn_tpu.graphs.batching import compute_pad_buckets

    samples = CORPORA[corpus]()
    totals = simulated_totals(samples, 16, 512)
    new = compute_pad_buckets(samples, 16, max_buckets=max_buckets, n_sim=512)
    old = quantile_buckets(samples, 16, max_buckets, 512)
    assert len(old) <= len(new) <= max_buckets
    assert mean_edge_slots(new, totals) <= mean_edge_slots(old, totals)
    if corpus == "heavy_tailed":  # by a margin where the worst case is far off
        assert mean_edge_slots(new, totals) < 0.9 * mean_edge_slots(old, totals)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("max_buckets", [2, 3, 4])
def test_placement_agrees_with_brute_force_on_twelve_batches(max_buckets, seed):
    """Every choice of at most ``max_buckets - 1`` lower buckets among the
    twelve simulated batches' rounded edge totals: none pads fewer slots."""
    import itertools

    from hydragnn_tpu.graphs.batching import compute_pad_buckets

    samples = heavy_tailed_samples(seed=seed)
    table = compute_pad_buckets(samples, 4, max_buckets=max_buckets, edge_multiple=8,
                                n_sim=12, seed=seed)
    edges = simulated_totals(samples, 4, 12, seed)[:, 1]
    top = table[-1].n_edge
    stands = sorted({-(-int(e) // 8) * 8 for e in edges} - {top})
    assert len(stands) >= max_buckets  # a real choice

    def cost(chosen):
        sizes = np.array(sorted(chosen) + [top])
        return int(sizes[np.searchsorted(sizes, edges)].sum())

    best = min(cost(c) for r in range(max_buckets)
               for c in itertools.combinations(stands, r))
    assert cost([b.n_edge for b in table[:-1]]) == best
    assert {b.n_edge for b in table[:-1]} <= set(stands)


@pytest.mark.parametrize("corpus", sorted(CORPORA))
def test_table_is_nested_distinct_capped_and_holds_every_batch(corpus):
    from hydragnn_tpu.graphs.batching import compute_pad_buckets

    samples = CORPORA[corpus]()
    table = compute_pad_buckets(samples, 8, max_buckets=4)
    assert table == compute_pad_buckets(samples, 8, max_buckets=4)  # a pure function
    assert table[-1] == compute_pad_spec(samples, 8)
    assert 2 <= len(table) <= 4 and len(set(table)) == len(table)
    for a, b in zip(table, table[1:]):
        assert a.n_node <= b.n_node and a.n_edge < b.n_edge and a.n_graph == b.n_graph
    ranks = [GraphLoader(samples, 8, shuffle=True, seed=3, rank=r, world=2, buckets=4)
             for r in (0, 1)]
    assert ranks[0].buckets == ranks[1].buckets
    loader = GraphLoader(samples, 8, shuffle=True, seed=3, buckets=table)
    used = set()
    for epoch in range(3):
        loader.set_epoch(epoch)
        for b in loader:  # collate raises where a bucket does not hold its batch
            assert b.node_mask.sum() < b.x.shape[0]
            used.add(b.senders.shape[0])
    assert len(used) > 1


@pytest.mark.parametrize("case", ["one_size", "fewer_samples_than_a_batch", "one_bucket_asked"])
def test_nothing_to_choose_gives_the_worst_case_alone(case):
    from hydragnn_tpu.graphs.batching import compute_pad_buckets

    samples, batch_size, max_buckets = {
        "one_size": (one_size_samples(), 8, 4),
        "fewer_samples_than_a_batch": (mixed_size_samples(12), 16, 4),
        "one_bucket_asked": (mixed_size_samples(), 16, 1),
    }[case]
    assert compute_pad_buckets(samples, batch_size, max_buckets=max_buckets) == [
        compute_pad_spec(samples, batch_size)]


@pytest.mark.parametrize("max_buckets", [2, 3])
def test_triplet_slots_follow_the_edges_under_a_cap(max_buckets):
    from hydragnn_tpu.graphs.batching import compute_pad_buckets

    samples = capped_samples(k=4)
    table = compute_pad_buckets(samples, 4, max_buckets=max_buckets, triplet_cap=4)
    assert len(table) == max_buckets
    assert all(b.n_triplet == 4 * b.n_edge and b.triplet_rows == "kj" for b in table)


def test_samples_that_carry_triplets_size_their_own_dimension():
    """Without a cap a bucket's triplet slots are the largest triplet total
    among the simulated batches its edges hold, so all three dimensions nest
    and every batch of an epoch finds a bucket by all three."""
    from hydragnn_tpu.graphs.batching import compute_pad_buckets
    from hydragnn_tpu.graphs.triplets import attach_triplets

    samples = heavy_tailed_samples(80)
    for s in samples:
        attach_triplets(s)
    table = compute_pad_buckets(samples, 4, max_buckets=3)
    assert len(table) == 3 and table[-1] == compute_pad_spec(samples, 4)
    for a, b in zip(table, table[1:]):
        assert 0 < a.n_triplet <= b.n_triplet and a.n_edge < b.n_edge
    loader = GraphLoader(samples, 4, shuffle=True, buckets=table)
    assert {b.idx_kj.shape[0] for b in loader} <= {t.n_triplet for t in table}


@pytest.mark.parametrize("epoch", [0, 1, 5])
def test_the_table_moves_no_sample_between_batches(epoch):
    """Which samples share a batch, and in what order, is the epoch
    permutation's alone: the same under any table and under none."""
    samples = heavy_tailed_samples(120)
    plans = []
    for buckets in (None, 4, quantile_buckets(samples, 8, 4, 512)):
        loader = GraphLoader(samples, 8, shuffle=True, seed=11, buckets=buckets)
        loader.set_epoch(epoch)
        plans.append([chunk.tolist() for chunk, _ in loader.batch_plan()])
    perm = np.random.default_rng(11 + epoch).permutation(len(samples))
    assert plans[0] == [perm[i * 8:(i + 1) * 8].tolist() for i in range(len(samples) // 8)]
    assert plans[0] == plans[1] == plans[2]


def test_compute_pad_buckets_takes_no_level_to_set():
    import inspect

    from hydragnn_tpu.graphs.batching import compute_pad_buckets

    assert list(inspect.signature(compute_pad_buckets).parameters) == [
        "samples", "batch_size", "max_buckets", "node_multiple", "edge_multiple",
        "n_sim", "seed", "attn_cap", "triplet_cap"]


# ---------- prefetch pipeline ----------


def test_prefetch_loader_matches_direct_iteration():
    from hydragnn_tpu.graphs.batching import PrefetchLoader

    samples = [make_sample(6, 12, seed=i) for i in range(32)]
    loader = GraphLoader(samples, 4, shuffle=True, seed=7)
    direct = [b.x for b in loader]
    pre = PrefetchLoader(GraphLoader(samples, 4, shuffle=True, seed=7), depth=3,
                         device_put=False)
    got = [b.x for b in pre]
    assert len(direct) == len(got)
    for a, b in zip(direct, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prefetch_loader_early_break_does_not_leak_threads():
    import threading
    import time

    from hydragnn_tpu.graphs.batching import PrefetchLoader

    samples = [make_sample(6, 12, seed=i) for i in range(64)]
    pre = PrefetchLoader(GraphLoader(samples, 4), depth=2, device_put=False)
    for _ in range(5):
        for b in pre:
            break  # consumer abandons mid-epoch
    time.sleep(1.0)  # workers observe stop and exit
    leaked = [
        t for t in threading.enumerate() if t.daemon and "Thread-" in t.name and t.is_alive()
    ]
    assert len(leaked) <= 1, f"leaked prefetch workers: {leaked}"
    # and the loader still works for a full pass afterwards
    assert len([b for b in pre]) == len(GraphLoader(samples, 4))


def test_prefetch_loader_propagates_worker_exception():
    from hydragnn_tpu.graphs.batching import PrefetchLoader

    class Boom:
        samples = []
        pad = None

        def __iter__(self):
            yield make_sample(4, 8)
            raise RuntimeError("collate exploded")

        def __len__(self):
            return 2

        def set_epoch(self, e):
            pass

    pre = PrefetchLoader(Boom(), depth=2, device_put=False)
    with pytest.raises(RuntimeError, match="collate exploded"):
        list(pre)


def test_prefetch_multiworker_preserves_order():
    from hydragnn_tpu.graphs.batching import PrefetchLoader

    samples = [make_sample(6, 12, seed=i) for i in range(48)]
    base = GraphLoader(samples, 4, shuffle=True, seed=11)
    direct = [b.x for b in base]
    pooled = PrefetchLoader(
        GraphLoader(samples, 4, shuffle=True, seed=11), depth=3, workers=4,
        device_put=False,
    )
    got = [b.x for b in pooled]
    assert len(got) == len(direct)
    for a, b in zip(direct, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # epochs advance through the wrapper
    pooled.set_epoch(1)
    got2 = [b.x for b in pooled]
    assert not all(
        np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(got, got2)
    )


def test_batch_plan_matches_iteration():
    samples = mixed_size_samples(60)
    loader = GraphLoader(samples, 8, shuffle=True, buckets=3, seed=5)
    plan = loader.batch_plan()
    batches = list(loader)
    assert len(plan) == len(batches)
    for (chunk, pad), b in zip(plan, batches):
        assert b.x.shape[0] == pad.n_node
        assert int(b.graph_mask.sum()) == len(chunk)


def test_run_training_with_buckets_and_workers(monkeypatch, tmp_path):
    """Training.pad_buckets + prefetch + num_workers end-to-end on a single
    device (the bucketed path is disabled under in-process meshes)."""
    import copy

    import hydragnn_tpu
    from hydragnn_tpu.datasets import deterministic_graph_data
    from test_config import CI_CONFIG

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("HYDRAGNN_AUTO_PARALLEL", "0")
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Training"].update(
        {"num_epoch": 2, "pad_buckets": 3, "prefetch": 2, "num_workers": 2}
    )
    samples = deterministic_graph_data(number_configurations=40, seed=23)
    state, model, aug = hydragnn_tpu.run_training(cfg, samples=samples)
    assert int(np.asarray(state.step)) > 0


def test_group_coarsened_buckets_share_shape_within_group():
    """Device-group streaming (round-3 verdict next-round #4): with
    set_group(n), every n consecutive batches collate to ONE bucket (the max
    of the members), so the epoch loop can stack them into a single device
    batch — and more than one bucket still appears across the epoch (the
    bucketing win survives the mesh)."""
    samples = mixed_size_samples(240)
    loader = GraphLoader(samples, 8, shuffle=True, seed=1, buckets=4)
    loader.set_group(4)
    shapes = [b.x.shape[0] for b in loader]
    groups = [shapes[i : i + 4] for i in range(0, len(shapes) - 3, 4)]
    for g in groups:
        assert len(set(g)) == 1, f"mixed shapes inside a device group: {g}"
    assert len({g[0] for g in groups}) > 1, "coarsening collapsed to one bucket"
    # plan-level agreement: batch_plan carries the same coarsened choice
    plan = loader.batch_plan()
    for i in range(0, len(plan) - loader.group + 1, loader.group):
        pads = {p.as_tuple() for _, p in plan[i : i + loader.group]}
        assert len(pads) == 1


def test_group_coarsening_keeps_rank_alignment():
    """group + world together: coarsened choices still derive from the shared
    permutation, so every rank stacks identical shapes at every step."""
    samples = mixed_size_samples(240)
    shapes = []
    for rank in (0, 1):
        loader = GraphLoader(
            samples, 8, shuffle=True, seed=3, rank=rank, world=2, buckets=4,
            group=4,
        )
        loader.set_epoch(2)
        shapes.append([b.x.shape[0] for b in loader])
    assert shapes[0] == shapes[1]


def test_run_training_pad_buckets_compose_with_mesh(monkeypatch):
    """pad_buckets is no longer force-disabled under a mesh: run_training on
    the 8-device mesh with bucketed padding trains end-to-end, stacks only
    same-bucket groups, and compiles at most one program per bucket."""
    import copy

    import jax
    import hydragnn_tpu
    from test_config import CI_CONFIG

    monkeypatch.setenv("HYDRAGNN_AUTO_PARALLEL", "1")
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Training"].update(
        {"num_epoch": 2, "pad_buckets": 3, "batch_size": 4, "prefetch": 0}
    )
    # mixed-size synthetic data so >1 bucket genuinely exists
    from hydragnn_tpu.datasets import deterministic_graph_data

    small = deterministic_graph_data(number_configurations=150, seed=5)
    big = deterministic_graph_data(
        number_configurations=50, seed=6, linear_only=True
    )
    state, model, aug = hydragnn_tpu.run_training(cfg, samples=small + big)
    leaf = jax.tree.leaves(state.params)[0]
    assert len(leaf.sharding.device_set) == 8
