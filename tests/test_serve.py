"""Serving-tier tests (ISSUE 9): warm-up → zero-recompile steady state,
bucket coalescing, served-vs-batch-evaluator bit parity, typed load-shed,
multi-model routing isolation, Serving config/flags.

Everything runs fp32 on CPU (JAX_PLATFORMS=cpu in tier-1), so "bit-match"
assertions are exact ``np.array_equal`` — the acceptance criterion is that
the server and ``run_prediction`` execute the same predict core on the same
padded inputs and therefore agree to the bit.
"""

import copy
import time

import numpy as np
import pytest

from hydragnn_tpu.config import update_config
from hydragnn_tpu.datasets import deterministic_graph_data
from hydragnn_tpu.graphs.batching import GraphLoader, compute_pad_buckets
from hydragnn_tpu.models.create import create_model_config
from hydragnn_tpu.preprocess.load_data import dataset_loading_and_splitting
from hydragnn_tpu.run_prediction import run_prediction
from hydragnn_tpu.serve import (
    DeadlineExceededError,
    MicroBatcher,
    OversizeError,
    PredictionServer,
    Predictor,
    QueueFullError,
    Request,
    RequestQueue,
    ServerClosedError,
    ServingConfig,
    UnknownModelError,
    canonical_meta,
    run_traffic,
    serving_collate,
)
from hydragnn_tpu.train.optimizer import select_optimizer
from hydragnn_tpu.train.step import create_train_state, make_predict_step

from test_config import CI_CONFIG


@pytest.fixture(scope="module", autouse=True)
def _threadsan(threadsan_module):
    """Every lock the serving tier creates in this module (queues, endpoint
    counters, batcher conditions, dispatcher plumbing) runs under the
    lock-order sanitizer; module teardown asserts the observed acquisition
    graph is cycle-free — the serve suite doubles as a deadlock drill."""
    yield threadsan_module


def _multihead_config():
    """CI config with a graph head + a node head (covers both gather paths)."""
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Variables_of_interest"] = {
        "input_node_features": [0],
        "output_names": ["sum", "x"],
        "output_index": [0, 1],
        "type": ["graph", "node"],
        "denormalize_output": False,
    }
    cfg["NeuralNetwork"]["Architecture"]["task_weights"] = [1.0, 1.0]
    cfg["NeuralNetwork"]["Architecture"]["output_heads"]["node"] = {
        "num_headlayers": 2,
        "dim_headlayers": [8, 8],
        "type": "mlp",
    }
    return cfg


@pytest.fixture(scope="module")
def served_model():
    """One tiny trained-shape GIN endpoint's ingredients, shared across the
    module: (raw config, augmented config, model, state, train samples)."""
    import jax
    import jax.numpy as jnp

    cfg = _multihead_config()
    samples = deterministic_graph_data(number_configurations=60, seed=7)
    tl, vl, sl = dataset_loading_and_splitting(copy.deepcopy(cfg), samples=samples)
    aug = update_config(copy.deepcopy(cfg), tl.samples, vl.samples, sl.samples)
    model = create_model_config(aug)
    opt = select_optimizer(aug["NeuralNetwork"]["Training"]["Optimizer"])
    state = create_train_state(
        model, opt, jax.tree.map(jnp.asarray, next(iter(tl)))
    )
    return cfg, aug, model, state, samples


def _boot_server(served_model, **kwargs):
    cfg, aug, model, state, samples = served_model
    server = PredictionServer(ServingConfig(flush_ms=25.0, **kwargs))
    server.add_model("gin", model, state, aug, samples=samples, batch_size=8)
    server.warmup(verify=True)
    return server.start()


# -- warm-up / steady state --------------------------------------------------


def test_warmup_zero_recompile_steady_state(served_model, compile_sentinel):
    """The acceptance gate: after boot warm-up, serving mixed-size traffic
    across every bucket performs ZERO jit lowerings (strict sentinel)."""
    cfg, aug, model, state, samples = served_model
    server = _boot_server(served_model)
    try:
        ep = server.stats()["gin"]
        assert ep["warm_executables"] == len(ep["buckets"]) > 1
        # span the size distribution so several buckets are exercised
        order = np.argsort([s.num_nodes for s in samples])
        probe = [samples[i] for i in order[:: max(1, len(order) // 24)]]
        with compile_sentinel(max_compiles=0, what="steady-state serving"):
            heads = server.predict("gin", probe)
        assert len(heads) == len(probe)
        stats = server.stats()["gin"]
        assert stats["served"] == len(probe) and stats["failed"] == 0
    finally:
        server.stop()


def test_warmup_report_shape(served_model):
    cfg, aug, model, state, samples = served_model
    server = PredictionServer(ServingConfig())
    server.add_model("gin", model, state, aug, samples=samples, batch_size=8)
    report = server.warmup()
    assert report["total_s"] > 0
    ep = server._models["gin"]
    assert set(report["gin"]) == {repr(b) for b in ep.buckets}
    assert all(v >= 0 for v in report["gin"].values())


# -- served outputs == batch evaluator ---------------------------------------


def test_served_bitmatch_run_prediction(served_model):
    """Serve the test split grouped exactly as ``run_prediction``'s test
    loader batches it; per-head predictions must bit-match (fp32/CPU)."""
    cfg, aug, model, state, samples = served_model
    err, tasks_loss, trues, preds = run_prediction(
        copy.deepcopy(cfg), state, model, samples=samples
    )
    # replicate the deterministic split to learn the loader's batch plan
    _, _, test_loader = dataset_loading_and_splitting(
        copy.deepcopy(cfg), samples=samples
    )
    server = PredictionServer(ServingConfig(flush_ms=250.0))
    server.add_model(
        "gin", model, state, aug,
        samples=test_loader.samples, buckets=[test_loader.pad],
    )
    server.warmup(verify=True)
    server.start()
    try:
        served = [[] for _ in preds]
        for chunk, pad in test_loader.batch_plan():
            futs = [
                server.submit("gin", test_loader.samples[i]) for i in chunk
            ]
            results = [f.result(timeout=60.0) for f in futs]
            # the whole chunk must have coalesced into ONE micro-batch, or
            # the comparison would not be composition-identical
            assert {r["batch_graphs"] for r in results} == {len(chunk)}
            for ihead in range(len(preds)):
                for r in results:
                    served[ihead].append(np.atleast_1d(r["heads"][ihead]))
        for ihead in range(len(preds)):
            got = np.concatenate(
                [np.asarray(a).reshape(-1, preds[ihead].shape[1])
                 for a in served[ihead]]
            )
            assert got.shape == preds[ihead].shape
            assert np.array_equal(got, preds[ihead]), (
                f"head {ihead}: served != run_prediction "
                f"(max |d| {np.abs(got - preds[ihead]).max()})"
            )
    finally:
        server.stop()


def test_run_prediction_refactor_ab(served_model):
    """Refactor pin: ``run_prediction`` through the shared Predictor returns
    byte-identical outputs to the historical inline predict loop."""
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.models.base import head_columns

    cfg, aug, model, state, samples = served_model
    err, tasks_loss, trues, preds = run_prediction(
        copy.deepcopy(cfg), state, model, samples=samples
    )
    # the pre-refactor loop, verbatim (run_prediction.py @ PR 8)
    _, _, test_loader = dataset_loading_and_splitting(
        copy.deepcopy(cfg), samples=samples
    )
    predict_step = make_predict_step(model)
    cols = head_columns(model.spec)
    ref_t = [[] for _ in cols]
    ref_p = [[] for _ in cols]
    for batch in test_loader:
        batch = jax.tree.map(jnp.asarray, batch)
        out = predict_step(state, batch)
        if model.spec.var_output:
            out = out[0]
        for ihead, (kind, col, dim) in enumerate(cols):
            mask = np.asarray(
                batch.graph_mask if kind == "graph" else batch.node_mask
            ) > 0
            y = batch.graph_y if kind == "graph" else batch.node_y
            ref_t[ihead].append(np.asarray(y[:, col : col + dim])[mask])
            ref_p[ihead].append(np.asarray(out[ihead])[mask])
    for ihead in range(len(cols)):
        assert np.array_equal(np.concatenate(ref_t[ihead]), trues[ihead])
        assert np.array_equal(np.concatenate(ref_p[ihead]), preds[ihead])
    ref_losses = [
        float(np.mean((np.concatenate(t) - np.concatenate(p)) ** 2))
        for t, p in zip(ref_t, ref_p)
    ]
    assert tasks_loss == ref_losses


def test_predictor_denormalize_matches_postprocess(served_model):
    """Predictor.denormalize is exactly postprocess.output_denormalize when
    the config asks for it, and the identity when it does not."""
    from hydragnn_tpu.postprocess.postprocess import output_denormalize

    cfg, aug, model, state, samples = served_model
    predictor = Predictor(model, state, aug)
    trues = [np.linspace(0, 1, 6).reshape(6, 1) for _ in predictor.cols]
    preds = [t * 0.5 for t in trues]
    t0, p0 = predictor.denormalize(trues, preds)
    assert all(np.array_equal(a, b) for a, b in zip(t0, trues))
    den_aug = copy.deepcopy(aug)
    voi = den_aug["NeuralNetwork"]["Variables_of_interest"]
    voi["denormalize_output"] = True
    voi["minmax_graph_feature"] = [[2.0], [6.0]]
    voi["minmax_node_feature"] = [[0.0, -1.0], [1.0, 3.0]]
    den = Predictor(model, state, den_aug)
    t1, p1 = den.denormalize(trues, preds)
    rt, rp = output_denormalize(voi, trues, preds, model.spec)
    assert all(np.array_equal(a, b) for a, b in zip(t1, rt))
    assert all(np.array_equal(a, b) for a, b in zip(p1, rp))
    # the serving hot path's preds-only variant agrees with the paired API
    assert all(
        np.array_equal(a, b) for a, b in zip(den.denormalize_preds(preds), rp)
    )
    assert all(
        np.array_equal(a, b)
        for a, b in zip(predictor.denormalize_preds(preds), preds)
    )


# -- micro-batching / admission ----------------------------------------------


def test_bucket_coalescing_and_occupancy(served_model):
    """Concurrent submissions coalesce into shared micro-batches collated to
    a table bucket, and every answer matches a per-sample reference."""
    cfg, aug, model, state, samples = served_model
    server = _boot_server(served_model)
    predictor = Predictor(model, state, aug)
    try:
        probe = samples[:16]
        futs = [server.submit("gin", s) for s in probe]
        results = [f.result(timeout=60.0) for f in futs]
        stats = server.stats()["gin"]
        assert stats["batches"] < len(probe), "no coalescing happened"
        table = {b for b in stats["buckets"]}
        assert {r["bucket"] for r in results} <= table
        assert stats["occupancy"] is not None and stats["occupancy"] > 0.5
        for s, r in zip(probe, results):
            pad = next(
                b for b in server._models["gin"].buckets
                if b.as_tuple() == r["bucket"]
            )
            # reference: the same sample alone in the same bucket program
            ref = predictor.split_graphs(
                predictor.outputs(serving_collate([s], pad)), [s.num_nodes]
            )[0]
            for h_served, h_ref in zip(r["heads"], ref):
                np.testing.assert_allclose(
                    np.asarray(h_served), np.asarray(h_ref),
                    rtol=1e-5, atol=1e-6,
                )
    finally:
        server.stop()


def test_queue_admission_and_load_shed():
    q = RequestQueue(depth=2)
    import hydragnn_tpu.graphs.graph as gg

    s = gg.GraphSample(x=np.zeros((2, 1), np.float32))
    q.put(Request(sample=s))
    q.put(Request(sample=s))
    with pytest.raises(QueueFullError):
        q.put(Request(sample=s))
    assert len(q) == 2
    q.close()
    with pytest.raises(ServerClosedError):
        q.put(Request(sample=s))


def test_deadline_and_oversize_shed(served_model):
    """Expired requests and never-fit requests fail with their own typed
    exceptions while live requests around them still get served."""
    cfg, aug, model, state, samples = served_model
    buckets = compute_pad_buckets(samples, 4, max_buckets=2)
    q = RequestQueue(depth=16)
    batcher = MicroBatcher(q, buckets, flush_s=0.01)
    dead = Request(sample=samples[0], deadline=time.monotonic() - 1.0)
    import hydragnn_tpu.graphs.graph as gg

    huge = gg.GraphSample(
        x=np.zeros((buckets[-1].n_node + 8, 1), np.float32),
        node_y=np.zeros((buckets[-1].n_node + 8, 1), np.float32),
        graph_y=np.zeros((1,), np.float32),
    )
    oversize = Request(sample=huge)
    live = Request(sample=samples[1])
    q.put(dead)
    q.put(oversize)
    q.put(live)
    members, pad = batcher.next_batch(block=True)
    assert [r is live for r in members] == [True]
    assert pad in buckets
    with pytest.raises(DeadlineExceededError):
        dead.future.result(timeout=0)
    with pytest.raises(OversizeError):
        oversize.future.result(timeout=0)


def test_batcher_overflow_pushback(served_model):
    """A request that would overflow the TOP bucket flushes the batch being
    formed and re-heads the queue for the next one — nothing is lost."""
    cfg, aug, model, state, samples = served_model
    order = sorted(samples, key=lambda s: -s.num_nodes)
    big = order[:8]
    # top bucket sized for ~3 of the biggest samples
    buckets = compute_pad_buckets(big, 3, max_buckets=1)
    q = RequestQueue(depth=32)
    batcher = MicroBatcher(q, buckets, flush_s=0.01)
    reqs = [Request(sample=s) for s in big]
    for r in reqs:
        q.put(r)
    seen = []
    while len(seen) < len(reqs):
        got = batcher.next_batch(block=False)
        assert got is not None, "batcher lost requests"
        members, pad = got
        assert 1 <= len(members) <= 3
        seen.extend(members)
    assert [r.sample for r in seen] == [r.sample for r in reqs]  # FIFO kept


def test_server_restart_keeps_serving(served_model):
    """stop() then start() re-arms the request plane; the warm executable
    table survives (the expensive part of boot)."""
    cfg, aug, model, state, samples = served_model
    server = _boot_server(served_model)
    try:
        assert len(server.predict("gin", samples[:3])) == 3
        server.stop()
        with pytest.raises(ServerClosedError):
            server.submit("gin", samples[0])
        exes_before = dict(server._models["gin"].executables)
        server.start()
        assert server._models["gin"].executables == exes_before
        assert len(server.predict("gin", samples[3:6])) == 3
    finally:
        server.stop()


def test_nonuniform_bucket_table_graph_capacity(served_model):
    """Caller-supplied tables may have non-uniform graph capacity: a batch
    of more graphs than a small bucket's slots must pick a bucket that
    holds it (pick_bucket's n_graphs check), not fail collate."""
    cfg, aug, model, state, samples = served_model
    from hydragnn_tpu.graphs.batching import PadSpec, pick_bucket

    small = PadSpec(n_node=64, n_edge=256, n_graph=5)
    big = PadSpec(n_node=512, n_edge=2048, n_graph=33)
    assert pick_bucket([small, big], 30, 100, 0, n_graphs=8) is big
    q = RequestQueue(depth=32)
    batcher = MicroBatcher(q, [small, big], flush_s=0.01)
    reqs = [Request(sample=samples[i]) for i in range(8)]
    for r in reqs:
        q.put(r)
    members, pad = batcher.next_batch(block=True)
    assert len(members) <= pad.n_graph - 1
    # every member must actually collate into the chosen bucket
    serving_collate([r.sample for r in members], pad)


def test_serving_config_validation_direct_construction():
    """PredictionServer validates ALL ServingConfig fields even when the
    schema's update_config is bypassed (direct dataclass/dict use)."""
    with pytest.raises(ValueError, match="max_batch_graphs"):
        PredictionServer(ServingConfig(max_batch_graphs=-1))
    with pytest.raises(ValueError, match="deadline_ms"):
        PredictionServer(ServingConfig(deadline_ms=-5.0))
    with pytest.raises(ValueError, match="queue_depth"):
        PredictionServer(ServingConfig(queue_depth=0))
    with pytest.raises(ValueError, match="flush_ms"):
        PredictionServer(ServingConfig(flush_ms=-1.0))


def test_batcher_sheds_update_stats(served_model):
    """Batcher-side sheds (deadline, oversize) land in the endpoint
    counters so submitted == served + sheds + failed holds for stats()."""
    import hydragnn_tpu.graphs.graph as gg

    cfg, aug, model, state, samples = served_model
    server = _boot_server(served_model)
    try:
        top = server._models["gin"].buckets[-1]
        huge = gg.GraphSample(
            x=np.zeros((top.n_node + 8, 1), np.float32),
            node_y=np.zeros((top.n_node + 8, 1), np.float32),
            graph_y=np.zeros((1,), np.float32),
        )
        fut = server.submit("gin", huge)
        with pytest.raises(OversizeError):
            fut.result(timeout=10.0)
        fut = server.submit("gin", samples[0], deadline_ms=0.0001)
        try:
            fut.result(timeout=10.0)
            deadline_hit = False  # dispatcher won the (sub-µs) race
        except DeadlineExceededError:
            deadline_hit = True
        stats = server.stats()["gin"]
        assert stats["shed_oversize"] == 1
        served_or_dead = stats["served"] + stats["shed_deadline"]
        assert stats["shed_deadline"] == (1 if deadline_hit else 0)
        assert (
            stats["submitted"]
            == stats["served"] + stats["shed"] + stats["shed_deadline"]
            + stats["shed_oversize"] + stats["failed"] + stats["cancelled"]
        )
        assert served_or_dead >= 1
    finally:
        server.stop()


def test_client_cancel_does_not_kill_dispatcher(served_model):
    """A client cancelling its future must never InvalidStateError the
    dispatcher thread — later requests still get served."""
    cfg, aug, model, state, samples = served_model
    server = _boot_server(served_model)
    try:
        futs = [server.submit("gin", s) for s in samples[:6]]
        cancelled = sum(1 for f in futs if f.cancel())
        # whatever the race outcome, the endpoint must still serve
        after = server.predict("gin", samples[6:10])
        assert len(after) == 4
        stats = server.stats()["gin"]
        resolved = (
            stats["served"] + stats["shed"] + stats["shed_deadline"]
            + stats["shed_oversize"] + stats["failed"] + stats["cancelled"]
        )
        assert stats["cancelled"] == cancelled
        assert stats["submitted"] == resolved
    finally:
        server.stop()


def test_serving_config_env_applies_to_dataclass(monkeypatch):
    """HYDRAGNN_SERVE_* flags override even a directly-constructed
    ServingConfig — the documented 'override at server construction'."""
    monkeypatch.setenv("HYDRAGNN_SERVE_QUEUE_DEPTH", "1024")
    server = PredictionServer(ServingConfig(queue_depth=64))
    assert server.cfg.queue_depth == 1024


def test_stop_counts_drained_backlog_as_cancelled(served_model):
    """stop() with queued requests resolves them ServerClosedError AND
    counts them, keeping submitted == sum of resolved counters."""
    cfg, aug, model, state, samples = served_model
    server = PredictionServer(ServingConfig())
    server.add_model("gin", model, state, aug, samples=samples, batch_size=8)
    server._running = True  # request plane open, no dispatcher thread
    futs = [server.submit("gin", s) for s in samples[:3]]
    server.stop()
    for f in futs:
        with pytest.raises(ServerClosedError):
            f.result(timeout=0)
    stats = server.stats()["gin"]
    assert stats["cancelled"] == 3
    assert (
        stats["submitted"]
        == stats["served"] + stats["shed"] + stats["shed_deadline"]
        + stats["shed_oversize"] + stats["failed"] + stats["cancelled"]
    )


def test_incompatible_sample_shed_and_certified_node_bound(served_model):
    """A request whose feature widths don't match the endpoint signature is
    shed typed at admission (collate's first-sample pe rule must never see a
    mixed batch); a graph above the certified per-graph node bound sheds as
    oversize instead of being served under a false attention bound."""
    import hydragnn_tpu.graphs.graph as gg
    from hydragnn_tpu.serve import IncompatibleSampleError

    cfg, aug, model, state, samples = served_model
    server = _boot_server(served_model)
    try:
        wrong_width = gg.GraphSample(
            x=np.zeros((4, 3), np.float32),  # endpoint signature is width 1
            node_y=np.zeros((4, 1), np.float32),
            graph_y=np.zeros((1,), np.float32),
        )
        with pytest.raises(IncompatibleSampleError, match="x_width"):
            server.submit("gin", wrong_width)
        wrong_graph_attr = gg.GraphSample(
            x=np.zeros((4, 1), np.float32),
            node_y=np.zeros((4, 1), np.float32),
            graph_y=np.zeros((1,), np.float32),
            graph_attr=np.zeros((3,), np.float32),  # endpoint has width 0
        )
        with pytest.raises(IncompatibleSampleError, match="graph_attr"):
            server.submit("gin", wrong_graph_attr)
        ep = server._models["gin"]
        bound = ep.batcher.node_bound
        assert bound >= max(s.num_nodes for s in samples)
        too_many_nodes = gg.GraphSample(
            x=np.zeros((bound + 1, 1), np.float32),
            node_y=np.zeros((bound + 1, 1), np.float32),
            graph_y=np.zeros((1,), np.float32),
        )
        fut = server.submit("gin", too_many_nodes)
        with pytest.raises(OversizeError, match="certified|bucket"):
            fut.result(timeout=10.0)
    finally:
        server.stop()
    # the JOINER path sheds over-bound graphs too (not only the batch
    # opener): a live first request must not drag a truncatable one in
    buckets = server._models["gin"].buckets
    q = RequestQueue(depth=8)
    batcher = MicroBatcher(q, buckets, flush_s=0.05)
    first = Request(sample=samples[0])
    joiner = Request(sample=gg.GraphSample(
        x=np.zeros((batcher.node_bound + 1, 1), np.float32),
        node_y=np.zeros((batcher.node_bound + 1, 1), np.float32),
        graph_y=np.zeros((1,), np.float32),
    ))
    q.put(first)
    q.put(joiner)
    members, _pad = batcher.next_batch(block=True)
    assert members == [first]
    with pytest.raises(OversizeError, match="certified"):
        joiner.future.result(timeout=0)


def test_add_model_buckets_only_with_example(served_model):
    """The explicit-buckets registration path works without shipping the
    training set — one example sample fixes the signature."""
    cfg, aug, model, state, samples = served_model
    buckets = compute_pad_buckets(samples, 8, max_buckets=2)
    server = PredictionServer(ServingConfig(flush_ms=25.0))
    server.add_model("gin", model, state, aug, buckets=buckets,
                     example=samples[0])
    server.warmup(verify=True)
    server.start()
    try:
        assert len(server.predict("gin", samples[:4])) == 4
    finally:
        server.stop()
    with pytest.raises(ValueError, match="example"):
        PredictionServer(ServingConfig()).add_model(
            "m", model, state, aug, buckets=buckets
        )


def test_server_typed_routing_errors(served_model):
    cfg, aug, model, state, samples = served_model
    server = PredictionServer(ServingConfig())
    server.add_model("gin", model, state, aug, samples=samples, batch_size=8)
    with pytest.raises(ServerClosedError):
        server.submit("gin", samples[0])  # not started yet
    with pytest.raises(ValueError):
        server.add_model("gin", model, state, aug, samples=samples)  # dup name
    server.warmup()
    server.start()
    try:
        with pytest.raises(UnknownModelError):
            server.submit("nope", samples[0])
    finally:
        server.stop()
    with pytest.raises(ServerClosedError):
        server.submit("gin", samples[0])


# -- multi-model routing ------------------------------------------------------


def test_multi_model_routing_isolation(served_model):
    """Two checkpoints of one architecture served from one process: each
    request's answer bit-matches its OWN endpoint's direct predict — routing
    never crosses states."""
    import jax
    import jax.numpy as jnp

    cfg, aug, model, state, samples = served_model
    opt = select_optimizer(aug["NeuralNetwork"]["Training"]["Optimizer"])
    tl = GraphLoader(samples, 8)
    state_b = create_train_state(
        model, opt, jax.tree.map(jnp.asarray, next(iter(tl))),
        rng=jax.random.PRNGKey(123),
    )
    server = PredictionServer(ServingConfig(flush_ms=25.0))
    server.add_model("ckpt_a", model, state, aug, samples=samples, batch_size=8)
    server.add_model("ckpt_b", model, state_b, aug, samples=samples, batch_size=8)
    server.warmup(verify=True)
    server.start()
    try:
        probe = samples[:6]
        futs = [
            (name, server.submit(name, s))
            for s in probe
            for name in ("ckpt_a", "ckpt_b")
        ]
        results = {"ckpt_a": [], "ckpt_b": []}
        for name, f in futs:
            results[name].append(f.result(timeout=60.0))
        refs = {
            "ckpt_a": Predictor(model, state, aug),
            "ckpt_b": Predictor(model, state_b, aug),
        }
        for name in ("ckpt_a", "ckpt_b"):
            ep = server._models[name]
            for s, r in zip(probe, results[name]):
                pad = next(
                    b for b in ep.buckets if b.as_tuple() == r["bucket"]
                )
                # isolation proof: compare against the OWN state's program;
                # composition may differ, so allclose not bitwise
                ref = refs[name].split_graphs(
                    refs[name].outputs(serving_collate([s], pad)),
                    [s.num_nodes],
                )[0]
                for h_served, h_ref in zip(r["heads"], ref):
                    np.testing.assert_allclose(
                        np.asarray(h_served), np.asarray(h_ref),
                        rtol=1e-5, atol=1e-6,
                    )
        # and the two endpoints disagree with each other (different params)
        a0 = results["ckpt_a"][0]["heads"][0]
        b0 = results["ckpt_b"][0]["heads"][0]
        assert not np.allclose(np.asarray(a0), np.asarray(b0))
    finally:
        server.stop()


# -- traffic generator / config / flags --------------------------------------


def test_traffic_generator_burst(served_model):
    cfg, aug, model, state, samples = served_model
    server = _boot_server(served_model, queue_depth=512)
    try:
        report = run_traffic(server, "gin", samples, n_requests=40, seed=3)
        s = report.summary()
        assert s["n_served"] == 40 and s["n_shed"] == 0
        assert s["p50_ms"] is not None and s["p99_ms"] >= s["p50_ms"]
        assert s["graphs_per_sec"] > 0
    finally:
        server.stop()


def test_serving_canonical_meta_stability(served_model):
    """Every batch of a bucket shares ONE treedef regardless of request mix
    — the property the zero-recompile guarantee rests on."""
    import jax

    cfg, aug, model, state, samples = served_model
    buckets = compute_pad_buckets(samples, 8, max_buckets=3)
    pad = buckets[-1]
    b1 = serving_collate(samples[:3], pad)
    b2 = serving_collate(samples[10:14], pad)
    assert b1.meta == b2.meta == canonical_meta(pad)
    assert jax.tree.structure(b1) == jax.tree.structure(b2)


def test_serving_config_block_schema():
    cfg = _multihead_config()
    samples = deterministic_graph_data(number_configurations=12, seed=1)
    aug = update_config(copy.deepcopy(cfg), samples)
    from hydragnn_tpu.serve import serving_config_defaults

    assert aug["Serving"] == serving_config_defaults()
    bad = copy.deepcopy(cfg)
    bad["Serving"] = {"queue_depth": 0}
    with pytest.raises(ValueError, match="queue_depth"):
        update_config(bad, samples)
    bad = copy.deepcopy(cfg)
    bad["Serving"] = {"flush_ms": -1.0}
    with pytest.raises(ValueError, match="flush_ms"):
        update_config(bad, samples)
    bad = copy.deepcopy(cfg)
    bad["Serving"] = {"flash_ms": 5.0}  # typo'd key must not silently vanish
    with pytest.raises(ValueError, match="flash_ms"):
        update_config(bad, samples)
    bad = copy.deepcopy(cfg)
    bad["Serving"] = []
    with pytest.raises(ValueError, match="Serving"):
        update_config(bad, samples)
    partial = copy.deepcopy(cfg)
    partial["Serving"] = {"flush_ms": 2.5}
    aug = update_config(partial, samples)
    assert aug["Serving"]["flush_ms"] == 2.5
    assert aug["Serving"]["queue_depth"] == serving_config_defaults()["queue_depth"]
    # the serving block passed DIRECTLY (not nested under "Serving") is
    # recognized by its field names, not silently dropped to defaults
    assert ServingConfig.from_config({"queue_depth": 8}).queue_depth == 8
    with pytest.raises(TypeError):
        ServingConfig.from_config({"queue_depth": 8, "typo_field": 1})


def test_flush_window_clamped_to_deadline(served_model):
    """A lone request whose deadline is shorter than the flush window must
    dispatch before the deadline, not wait out the window and get shed."""
    cfg, aug, model, state, samples = served_model
    server = PredictionServer(ServingConfig(flush_ms=2000.0))
    server.add_model("gin", model, state, aug, samples=samples, batch_size=8)
    server.warmup(verify=True)
    server.start()
    from hydragnn_tpu.serve.admission import DeadlineExceededError

    try:
        for _ in range(3):
            t0 = time.monotonic()
            fut = server.submit("gin", samples[0], deadline_ms=150.0)
            try:
                heads = fut.result(timeout=10.0)["heads"]
            except DeadlineExceededError:
                # the window did close at the deadline (far under 2 s) and the
                # dispatch thread came over its 2 ms margin late: a loaded
                # host's scheduling, not the clamp. Ask again.
                assert time.monotonic() - t0 < 1.0
                continue
            assert time.monotonic() - t0 < 1.0  # far under the 2 s window
            assert len(heads) == len(server._models["gin"].predictor.cols)
            break
        else:
            pytest.fail("three lone requests in a row were shed at their deadline")
    finally:
        server.stop()


def test_from_config_rejects_typo_only_dict():
    """A dict that is neither a full config nor a recognizable Serving
    block raises instead of silently booting with defaults."""
    with pytest.raises(ValueError, match="flushms"):
        PredictionServer({"flushms": 1000})
    # a full config without a Serving block is still fine (defaults)
    from hydragnn_tpu.serve import serving_config_defaults

    cfg = ServingConfig.from_config({"NeuralNetwork": {}})
    assert cfg.queue_depth == serving_config_defaults()["queue_depth"]


def test_incompatible_shed_is_counted(served_model):
    """Admission-layer schema rejections land in the shed counter so
    stats() exposes misrouted client traffic."""
    import hydragnn_tpu.graphs.graph as gg
    from hydragnn_tpu.serve import IncompatibleSampleError

    cfg, aug, model, state, samples = served_model
    server = _boot_server(served_model)
    try:
        before = server.stats()["gin"]
        with pytest.raises(IncompatibleSampleError):
            server.submit("gin", gg.GraphSample(
                x=np.zeros((4, 5), np.float32),
                graph_y=np.zeros((1,), np.float32),
            ))
        after = server.stats()["gin"]
        assert after["submitted"] == before["submitted"] + 1
        assert after["shed"] == before["shed"] + 1
    finally:
        server.stop()


def test_serve_flags_override(monkeypatch):
    monkeypatch.setenv("HYDRAGNN_SERVE_QUEUE_DEPTH", "7")
    monkeypatch.setenv("HYDRAGNN_SERVE_FLUSH_MS", "1.5")
    monkeypatch.setenv("HYDRAGNN_SERVE_WARMUP", "0")
    cfg = ServingConfig.from_config({"Serving": {"queue_depth": 99}})
    assert cfg.queue_depth == 7  # env beats the config block
    assert cfg.flush_ms == 1.5
    assert cfg.warmup is False
    monkeypatch.delenv("HYDRAGNN_SERVE_QUEUE_DEPTH")
    cfg = ServingConfig.from_config({"Serving": {"queue_depth": 99}})
    assert cfg.queue_depth == 99
