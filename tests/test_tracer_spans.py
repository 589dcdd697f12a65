"""The span system (``utils/tracer.py``) and what rides on it: per-thread
timing, the profiler-trace sink, the loop's and the loader's spans with their
arguments, model scopes on the step's operations, and the sentinel's compile
seconds by function."""

import contextlib
import gc
import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.analysis import sentinel
from hydragnn_tpu.graphs.batching import GraphLoader, PrefetchLoader
from hydragnn_tpu.models.mlip import make_mlip_train_step
from hydragnn_tpu.train import create_train_state, select_optimizer
from hydragnn_tpu.train.loop import train_epoch
from hydragnn_tpu.utils import tracer as tr

from test_forces import build_mlip


class Recorder:
    """Stand-in for ``jax.profiler.TraceAnnotation``: every instance logs
    its name, arguments, thread and its enter/exit into ``Recorder.log``."""

    log: list = []

    def __init__(self, name, **kwargs):
        self.entry = {"name": name, "args": dict(kwargs), "enter": 0, "exit": 0,
                      "thread": threading.get_ident()}
        Recorder.log.append(self.entry)

    def __enter__(self):
        self.entry["enter"] += 1
        return self

    def __exit__(self, *exc):
        self.entry["exit"] += 1

    def set_metadata(self, **kwargs):  # ``tr.note``: what a span learns while it runs
        self.entry["args"].update(kwargs)


class Stamped(Recorder):
    """``Recorder`` with a clock: how long a span was, whether two overlap."""

    def __enter__(self):
        self.entry["t0"] = time.perf_counter()
        return super().__enter__()

    def __exit__(self, *exc):
        self.entry["t1"] = time.perf_counter()
        super().__exit__(*exc)


@pytest.fixture
def recorded(monkeypatch):
    """The annotations a test's spans make, in opening order. The collector's
    hook is the test's to install (``tr.watch_gc``, or the loop and the
    prefetcher that call it); it is taken out again afterwards."""
    Recorder.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    unwatch_gc()
    with tr.isolated_timers():
        yield Recorder.log
    unwatch_gc()


def unwatch_gc():
    if tr.gc_watched():
        gc.callbacks.remove(tr._on_gc)


def spans(log, name):
    return [e for e in log if e["name"] == f"hydragnn/{name}"]


@pytest.fixture(scope="module")
def mlip():
    model, _, cfg, samples = build_mlip(n_samples=12)
    opt = select_optimizer(cfg["NeuralNetwork"]["Training"]["Optimizer"])
    return model, opt, samples


def fresh_state(mlip, loader):
    model, opt, _ = mlip
    return create_train_state(model, opt, next(iter(loader)))


def test_one_name_on_two_threads_sums_both():
    with tr.isolated_timers():
        barrier = threading.Barrier(2)

        def work():
            barrier.wait()
            with tr.span("work"):
                time.sleep(0.05)

        threads = [threading.Thread(target=work) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        timer = tr.get("work")
        assert timer.count == 2
        assert timer.total >= 0.095  # both threads' 50 ms, though they overlap


def test_first_close_of_a_name_on_many_threads_loses_no_count():
    with tr.isolated_timers():
        for round_no in range(20):  # a fresh name each round: every close is a first miss
            barrier = threading.Barrier(8)

            def work():
                tr.start(f"fresh_{round_no}")
                barrier.wait()
                tr.stop(f"fresh_{round_no}")

            threads = [threading.Thread(target=work) for _ in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert tr.get(f"fresh_{round_no}").count == 8


def test_nesting_and_out_of_order_stop():
    with tr.isolated_timers():
        tr.start("a")
        tr.start("b")
        tr.start("b")  # the same name nested
        tr.stop("a")  # out of order: closes a, leaves both b open
        tr.stop("b")
        tr.stop("b")
        tr.stop("b")  # nothing open: does nothing
        tr.stop("never_started")
        s = tr.summary()
        assert s["a"]["count"] == 1 and s["b"]["count"] == 2
        assert "never_started" not in s or s["never_started"]["count"] == 0
        with tr.span("outer"):
            with tr.span("inner"):
                time.sleep(0.01)
        assert tr.get("outer").total >= tr.get("inner").total >= 0.01


def test_arguments_reach_the_chrome_buffer(telemetry_isolate):
    tel = telemetry_isolate
    tel.trace.set_trace_enabled(True)
    tr.start("collate", batch=7, real_edges=123)
    tr.stop("collate")
    with tr.span("dispatch", batch=1):
        pass
    events = {e["name"]: e for e in tel.trace.trace_events()}
    assert events["collate"]["args"] == {"batch": 7, "real_edges": 123}
    assert events["dispatch"]["args"]["batch"] == 1


def test_annotation_sees_balanced_enter_exit_and_arguments(recorded):
    with tr.span("train"):
        tr.start("collate", batch=2, real_edges=5, edge_slots=8)
        tr.stop("collate")
        with pytest.raises(RuntimeError):
            with tr.span("dispatch", batch=2):
                raise RuntimeError("the span closes on the way out")
    assert [e["name"] for e in recorded] == [
        "hydragnn/train", "hydragnn/collate", "hydragnn/dispatch"]
    assert all(e["enter"] == 1 and e["exit"] == 1 for e in recorded)
    assert spans(recorded, "collate")[0]["args"] == {
        "batch": 2, "real_edges": 5, "edge_slots": 8}
    assert spans(recorded, "dispatch")[0]["args"] == {"batch": 2}


def test_train_epoch_emits_the_loop_and_loader_spans(recorded, mlip):
    model, opt, samples = mlip
    loader = GraphLoader(samples, 4, buckets=2)  # 12 samples: three batches
    state = fresh_state(mlip, loader)
    step = make_mlip_train_step(model, opt)
    recorded.clear()
    state, loss, _ = train_epoch(step, state, loader)
    assert np.isfinite(loss)
    for name in ("stage", "dispatch", "release", "backpressure"):
        found = spans(recorded, name)
        assert [e["args"] for e in found] == [{"batch": i} for i in range(3)], name
    # one release a batch, between the step call and the wait for the device
    loop = [e["name"][len("hydragnn/"):] for e in recorded
            if e["name"][len("hydragnn/"):] in ("dispatch", "release", "backpressure")]
    assert loop == ["dispatch", "release", "backpressure"] * 3
    # the loop asks once more after the last batch, and finds the loader empty
    assert [e["args"]["batch"] for e in spans(recorded, "dataload")] == [0, 1, 2, 3]
    assert len(spans(recorded, "train")) == 1
    assert [e["args"] for e in spans(recorded, "drain")] == [{}]
    assert [e["args"] for e in spans(recorded, "reduce")] == [{}]
    assert all(e["enter"] == 1 and e["exit"] == 1 for e in recorded)
    collates = spans(recorded, "collate")
    plan = loader.batch_plan()
    assert [c["args"]["batch"] for c in collates] == [0, 1, 2]
    assert [set(c["args"]) for c in collates] == [
        {"batch", "real_edges", "edge_slots", "fetch_us", "fill_us", "certify_us"}] * 3
    assert sum(c["args"]["real_edges"] for c in collates) == sum(s.num_edges for s in samples)
    assert [c["args"]["edge_slots"] for c in collates] == [pad.n_edge for _, pad in plan]
    # the timers the benchmark reads are the same spans
    assert tr.get("dataload").count == 4 and tr.get("dispatch").count == 3


@pytest.mark.parametrize("workers", [1, 2])
def test_prefetch_records_collate_on_the_worker_threads(recorded, mlip, workers):
    _, _, samples = mlip
    loader = PrefetchLoader(GraphLoader(samples, 2), depth=2, device_put=True, workers=workers)
    batches = list(loader)
    assert len(batches) == 6
    collates = spans(recorded, "collate")
    assert sorted(c["args"]["batch"] for c in collates) == list(range(6))
    assert all(c["thread"] != threading.get_ident() for c in collates)
    assert 1 <= len({c["thread"] for c in collates}) <= workers
    assert tr.get("collate").count == 6  # every thread's spans, one timer
    # a transfer says which batch it moved and what that held
    transfers = spans(recorded, "transfer")
    assert [t["args"]["batch"] for t in transfers] == list(range(6))
    for t, batch in zip(transfers, batches):
        leaves = jax.tree.leaves(batch)
        assert t["args"]["leaves"] == len(leaves)
        assert t["args"]["bytes"] == sum(leaf.nbytes for leaf in leaves) > 0


# -- the host threads' account: collate's phases, handoff, release, gc ---------------


@pytest.mark.parametrize("certify", [True, False])
def test_collate_notes_its_phases_within_its_duration(recorded, mlip, certify, monkeypatch):
    from hydragnn_tpu.graphs import batching

    _, _, samples = mlip
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Stamped)
    loader = GraphLoader(samples, 4)
    if not certify:
        collate = batching.collate
        monkeypatch.setattr(batching, "collate", lambda s, pad: collate(s, pad, certify=False))
    batches = list(loader)
    assert all((b.meta is not None) == certify for b in batches)
    collates = spans(recorded, "collate")
    assert len(collates) == 3
    for c in collates:
        phases = [c["args"][k] for k in ("fetch_us", "fill_us", "certify_us")]
        assert all(isinstance(us, int) and us >= 0 for us in phases)
        assert (c["args"]["certify_us"] > 0) == certify
        assert sum(phases) <= (c["t1"] - c["t0"]) * 1e6  # parts of the span, each rounded down
    # outside a collate span the phases have nowhere to go, and nothing breaks
    recorded.clear()
    assert batching.collate(samples[:2], loader.pad).x.shape[0] == loader.pad.n_node
    assert not recorded


def test_a_full_queue_is_a_handoff_span_beside_collate_and_transfer(recorded, mlip, monkeypatch):
    _, _, samples = mlip
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Stamped)
    loader = PrefetchLoader(GraphLoader(samples, 2), depth=1, device_put=True)
    with tr.span("train"):
        it = iter(loader)
        for ib in range(7):
            with tr.span("dataload", batch=ib):
                batch = next(it, None)
            time.sleep(0.03)  # a slow consumer: the producer finds the queue full
    assert batch is None
    handoffs = spans(recorded, "handoff")
    # six batches and the end-of-stream marker, which carries no batch
    assert [h["args"] for h in handoffs] == [{"batch": i} for i in range(6)] + [{}]
    producer = {h["thread"] for h in handoffs}
    assert len(producer) == 1 and threading.get_ident() not in producer
    busy = [e for name in ("collate", "transfer") for e in spans(recorded, name)]
    assert {e["thread"] for e in busy} == producer
    for h in handoffs:  # a sibling of collate and transfer, never inside one
        assert all(h["t0"] >= e["t1"] or h["t1"] <= e["t0"] for e in busy)
    assert max(h["t1"] - h["t0"] for h in handoffs) >= 0.02  # it did wait for the slot
    # the consumer says how many finished batches it found
    ready = [d["args"]["ready"] for d in spans(recorded, "dataload")]
    assert len(ready) == 7 and set(ready) <= {0, 1} and 1 in ready
    assert all(e["enter"] == 1 and e["exit"] == 1 for e in recorded)


def test_the_pooled_prefetcher_notes_ready_and_has_no_handoff(recorded, mlip):
    _, _, samples = mlip
    loader = PrefetchLoader(GraphLoader(samples, 2), depth=2, device_put=True, workers=2)
    it = iter(loader)
    for ib in range(7):
        with tr.span("dataload", batch=ib):
            next(it, None)
    assert not spans(recorded, "handoff")
    ready = [d["args"].get("ready") for d in spans(recorded, "dataload")]
    assert all(isinstance(r, int) and 0 <= r <= 3 for r in ready[:6])
    # transfer runs on the consumer's thread there, inside its dataload
    assert {t["thread"] for t in spans(recorded, "transfer")} == {threading.get_ident()}


def test_an_old_generation_collection_is_a_span_on_its_thread(recorded):
    tr.watch_gc()
    tr.watch_gc()  # asked twice, registered once
    assert gc.callbacks.count(tr._on_gc) == 1 and tr.gc_watched()
    with tr.span("collate", batch=0):
        gc.collect(0)  # the young generation: no span
        assert not spans(recorded, "gc")
        gc.collect(2)
    found = spans(recorded, "gc")
    assert len(found) == 1 and found[0]["thread"] == threading.get_ident()
    assert found[0]["args"]["generation"] == 2 and found[0]["args"]["collected"] >= 0
    assert all(e["enter"] == 1 and e["exit"] == 1 for e in recorded)
    assert tr._span_stack() == []
    assert tr.get("gc").count == 1
    # opened after collate and closed before it: nested, so it leaves collate's self time
    assert [e["name"] for e in recorded] == ["hydragnn/collate", "hydragnn/gc"]


def test_a_collection_on_a_thread_with_no_span_open_is_counted_and_not_annotated(recorded):
    tr.watch_gc()
    with tr.span("collate", batch=0):  # another thread's open span does not count
        worker = threading.Thread(target=gc.collect, args=(2,))
        worker.start()
        worker.join(10)
        assert not worker.is_alive()
    assert not spans(recorded, "gc")
    assert tr.get("gc").count == 1 and tr.get("gc").total > 0.0
    gc.collect(1)  # nor does this thread's, once its span is closed
    assert not spans(recorded, "gc") and tr.get("gc").count == 2


def test_a_collection_started_inside_stop_returns(recorded, monkeypatch):
    """The first close of a name makes its ``Timer`` under the tracer's lock;
    an allocation may start a collection there, whose hook closes a ``gc`` span
    on the same thread. A hook that waits for a plain lock never returns."""

    class Colliding(tr.Timer):
        def __init__(self):
            super().__init__()
            gc.collect(2)

    monkeypatch.setattr(tr, "Timer", Colliding)
    tr.watch_gc()
    done = threading.Event()

    def work():
        with tr.isolated_timers():  # made here: a registry of the colliding timers
            with tr.span("train"):
                with tr.span("never_closed_before"):
                    pass
            assert tr.get("never_closed_before").count == 1
            assert tr.get("gc").count >= 1
        done.set()

    worker = threading.Thread(target=work, daemon=True)
    worker.start()
    worker.join(10)
    assert done.is_set(), "the collector's hook waits for a lock its own thread holds"
    opened = spans(recorded, "gc")
    assert opened and all(e["enter"] == 1 and e["exit"] == 1 for e in opened)


def test_sentinel_keeps_seconds_by_function():
    before_counts = sentinel.compile_counts()
    before = sentinel.compile_seconds()

    @jax.jit
    def a_function_of_this_test(x):
        return (x * 2.0).sum()

    a_function_of_this_test(jnp.ones(7))
    after_counts = sentinel.compile_counts()
    assert set(after_counts) == set(before_counts) == {
        "traces", "lowerings", "backend_compiles",
        "persistent_cache_hits", "persistent_cache_misses"}
    assert after_counts["lowerings"] > before_counts["lowerings"]
    record = {}
    for fun, counters in sentinel.compile_seconds().items():
        if "a_function_of_this_test" in fun:
            assert fun not in before
            record.update(counters)
    assert record["traces"][0] == 1 and record["lowerings"][0] == 1
    assert all(secs > 0.0 for _, secs in record.values())
    # the per-function counts are the same events compile_counts() counts
    for name in ("traces", "lowerings", "backend_compiles"):
        by_function = sum(c.get(name, (0, 0.0))[0]
                          for c in sentinel.compile_seconds().values())
        assert by_function <= sentinel.compile_counts()[name]


def test_step_scopes_are_in_the_lowered_text_and_change_no_number(mlip, monkeypatch):
    model, opt, samples = mlip
    loader = GraphLoader(samples, 4)
    batch = jax.tree.map(jnp.asarray, next(iter(loader)))
    text = make_mlip_train_step(model, opt).lower(
        fresh_state(mlip, loader), batch).as_text(debug_info=True)
    assert "mlip_loss" in text and "jit(train_step)/optimizer/" in text
    assert "HydraModel.conv_block" in text  # flax's own module path

    scoped_state, scoped = make_mlip_train_step(model, opt)(fresh_state(mlip, loader), batch)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain_step = make_mlip_train_step(model, opt)
    plain_text = plain_step.lower(fresh_state(mlip, loader), batch).as_text(debug_info=True)
    assert "mlip_loss" not in plain_text
    plain_state, plain = plain_step(fresh_state(mlip, loader), batch)
    assert np.array_equal(np.asarray(scoped["loss"]), np.asarray(plain["loss"]))
    for a, b in zip(jax.tree.leaves(scoped_state.params), jax.tree.leaves(plain_state.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_step_program_keeps_the_names_the_benchmark_reads(mlip):
    """The program's side of a contract ``benchmark/`` cannot pin itself:
    ``benchmark/lib/spans.py`` finds the step program among the sentinel's
    records by the jitted functions' names ``train_step`` and (with the
    non-finite guard) ``guarded_step``, and tells device time apart by the
    scopes ``mlip_loss`` and ``optimizer``, flax's ``HydraModel.conv_block``
    and the AD pass tags. A refactor of the step that loses one of them
    fails here and not in a chip run."""
    import re

    from hydragnn_tpu.resilience import wrap_step_with_guard

    model, opt, samples = mlip
    loader = GraphLoader(samples, 4)
    batch = jax.tree.map(jnp.asarray, next(iter(loader)))
    sentinel.install()
    step = make_mlip_train_step(model, opt)
    assert step.__name__ == "train_step"
    text = step.lower(fresh_state(mlip, loader), batch).as_text(debug_info=True)
    assert "module @jit_train_step" in text
    scopes = set(re.findall(r'loc\("jit\(train_step\)/([^"/]+)/', text))
    assert {"jvp(mlip_loss)", "transpose(jvp(mlip_loss))", "optimizer"} <= scopes
    # the four passes of a grad-of-grad step, by their tags (PERF.md section 3)
    for tag in ("jvp(jvp(HydraModel))", "jvp(transpose(jvp(HydraModel)))",
                "transpose(jvp(jvp(HydraModel)))",
                "transpose(jvp(transpose(jvp(HydraModel))))"):
        assert f"jit(train_step)/{tag}/" in text, tag
    assert "HydraModel.conv_block" in text

    guarded = wrap_step_with_guard(step)
    assert guarded.__name__ == "guarded_step"
    guarded_text = guarded.lower(fresh_state(mlip, loader), batch).as_text(debug_info=True)
    assert "module @jit_guarded_step" in guarded_text
    assert 'mlip_loss' in guarded_text and 'optimizer/' in guarded_text
    lowered = {fun[4:-1] if fun.startswith("jit(") and fun.endswith(")") else fun
               for fun, record in sentinel.compile_seconds().items()
               if "lowerings" in record}
    assert {"train_step", "guarded_step"} <= lowered


def test_a_profile_holds_the_dispatch_span_with_its_arguments(tmp_path, mlip):
    """A real ``jax.profiler`` trace on the CPU round a two-batch epoch."""
    from jax.profiler import ProfileData

    model, opt, samples = mlip
    loader = GraphLoader(samples[:8], 4)
    state = fresh_state(mlip, loader)
    step = make_mlip_train_step(model, opt)
    state, _, _ = train_epoch(step, state, loader)  # compile outside the profile
    jax.profiler.start_trace(str(tmp_path))
    try:
        train_epoch(step, state, loader)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    assert files
    found = {}
    for plane in ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("hydragnn/"):
                    found.setdefault(event.name, []).append(
                        {k: int(v) for k, v in event.stats})
    assert found["hydragnn/dispatch"] == [{"batch": 0}, {"batch": 1}]
    assert [c["batch"] for c in found["hydragnn/collate"]] == [0, 1]
    assert all(c["real_edges"] <= c["edge_slots"] for c in found["hydragnn/collate"])
    assert {"hydragnn/train", "hydragnn/dataload", "hydragnn/stage", "hydragnn/backpressure",
            "hydragnn/drain", "hydragnn/reduce"} <= set(found)


# -- the triplet dimension (DimeNet): spans, counters, scopes -----------------------

LAYOUTS = pytest.mark.parametrize("layout", ["flat", "block"])


def _dimenet_case(layout="flat"):
    """A small periodic DimeNet MLIP model the way the benchmark builds it, its
    samples (two crystals of 6 and 2 atoms, 8 neighbours each) and a loader
    whose buckets carry a triplet dimension: the flat list, or the dense
    ``[E, 8]`` block with the side the corpus caps as its row (kj)."""
    import test_dimenet_reference as dn
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.graphs.batching import PadSpec, compute_pad_spec
    from hydragnn_tpu.graphs.triplets import degree_cap
    from hydragnn_tpu.models import create_model_config

    bench_cfg = dn.bench_config()
    samples = dn.program.to_samples(dn.crystals.generate(dn.CRYSTALS, 5), bench_cfg["input_scale"])
    cfg = update_config({k: bench_cfg[k] for k in dn.program.PROGRAM_KEYS if k in bench_cfg},
                        samples)
    pad = compute_pad_spec(samples, 2, triplet_cap=degree_cap(samples))
    assert pad.triplet_rows == "kj"  # worked out from the samples: what an atom sends
    if layout == "flat":  # the same slots as a list
        pad = PadSpec(*pad.as_tuple(), node_cap=pad.node_cap)
    return create_model_config(cfg), samples * 2, pad


@LAYOUTS
def test_collate_and_triplets_spans_carry_the_triplet_counts(recorded, layout):
    _, samples, pad = _dimenet_case(layout)
    batches = list(GraphLoader(samples, 2, pad=pad))
    collates, triplets = spans(recorded, "collate"), spans(recorded, "triplets")
    assert [set(c["args"]) for c in collates] == [
        {"batch", "real_edges", "edge_slots", "triplet_slots", "triplet_block",
         "real_triplets", "fetch_us", "fill_us", "certify_us"}] * 2
    assert [set(t["args"]) for t in triplets] == [{"edges", "triplets"}] * 4  # one a sample
    assert all(c["args"]["triplet_slots"] == pad.n_triplet == 8 * pad.n_edge for c in collates)
    # the counter that says the block layout engaged: K, or 0 on the flat list
    assert [c["args"]["triplet_block"] for c in collates] == [8 if layout == "block" else 0] * 2
    assert [c["args"]["real_triplets"] for c in collates] == [
        int(b.triplet_mask.sum()) for b in batches]
    assert sum(t["args"]["triplets"] for t in triplets) == sum(
        c["args"]["real_triplets"] for c in collates)
    assert sum(t["args"]["edges"] for t in triplets) == sum(
        c["args"]["real_edges"] for c in collates)
    assert all(t["enter"] == t["exit"] == 1 for t in triplets)
    assert tr.get("triplets").count == 4


def test_a_profile_holds_what_a_span_noted_while_it_ran(tmp_path):
    """``tr.note`` on the real ``TraceAnnotation``: the counts reach the
    profile's events as statistics beside the span's opening arguments."""
    from jax.profiler import ProfileData

    _, samples, pad = _dimenet_case()
    jax.profiler.start_trace(str(tmp_path))
    try:
        batches = list(GraphLoader(samples, 2, pad=pad))
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("hydragnn/"):
                    found.setdefault(event.name, []).append({k: int(v) for k, v in event.stats})
    assert [c["real_triplets"] for c in found["hydragnn/collate"]] == [
        int(b.triplet_mask.sum()) for b in batches]
    assert all(c["triplet_slots"] == pad.n_triplet for c in found["hydragnn/collate"])
    assert all(t["triplets"] <= 8 * t["edges"] for t in found["hydragnn/triplets"])


def test_the_build_line_names_the_layout_and_the_row_side(capsys):
    """``DimeNetConv.describe``: what the triplet dimension is laid out as
    under a cap (block, which side is the row, who decides) and without one."""
    import dataclasses

    from hydragnn_tpu.models.dimenet import DimeNetConv

    model, _, _ = _dimenet_case()
    built = capsys.readouterr().out
    assert "layout: dense [E, K] block, rows kj where no atom sends more than K" in built
    assert "rows ji where none receives more" in built and "else flat" in built
    assert "8 x n_edge slots" in DimeNetConv.describe(model.spec)
    bare = DimeNetConv.describe(dataclasses.replace(model.spec, max_neighbours=None))
    assert "block" not in bare and "layout: flat list (idx_kj, idx_ji)" in bare


@LAYOUTS
def test_dimenet_scopes_are_in_the_lowered_text(layout):
    """The six scopes ``benchmark/metrics`` reads device time by (PERF.md
    section 3), in every pass; geometry and basis under the first layer only.
    The block layout keeps them: ``device_triplet_ms`` / ``device_basis_ms``
    read the same work."""
    import re

    model, samples, pad = _dimenet_case(layout)
    opt = select_optimizer({"type": "AdamW", "learning_rate": 1e-4})
    loader = GraphLoader(samples, 2, pad=pad)
    batch = jax.tree.map(jnp.asarray, next(iter(loader)))
    state = create_train_state(model, opt, batch)
    text = make_mlip_train_step(model, opt).lower(state, batch).as_text(debug_info=True)
    under = {}
    for layer, scope in re.findall(
            r"HydraModel\.conv_block/graph_convs_(\d)/([a-z]+(?:/(?:triplets|dense))?)/", text):
        under.setdefault(scope, set()).add(int(layer))
    assert under == {"geometry": {0}, "basis": {0}, "embedding": {0, 1, 2},
                     "interaction/triplets": {0, 1, 2}, "interaction/dense": {0, 1, 2},
                     "output": {0, 1, 2}}
    for tag in ("jvp(jvp(HydraModel))", "transpose(jvp(transpose(jvp(HydraModel))))"):
        assert re.search(re.escape(tag) + r"/[^\"]*graph_convs_1/interaction/triplets/", text), tag


# -- SchNet: the five scopes of a conv layer, the build line -----------------------


def _schnet_case():
    """A small periodic SchNet MLIP model the way the benchmark builds it (its
    rehearsal widths) and one padded batch of four crystals of 2-20 atoms."""
    import test_schnet_reference as sn

    case = sn.Case()
    return case.model, case.batch


@pytest.mark.parametrize("fused", ["0", "1"])
def test_schnet_scopes_are_in_the_lowered_text(fused, monkeypatch):
    """The five scopes ``benchmark/metrics`` reads device time by (PERF.md
    section 3), in every pass; geometry and smearing under the first layer only
    (the layers share them); the Pallas call of the gather-multiply-sum, where
    it runs, under ``aggregate``."""
    import re

    monkeypatch.setenv("HYDRAGNN_FUSED_SCATTER", fused)
    model, batch = _schnet_case()
    opt = select_optimizer({"type": "AdamW", "learning_rate": 1e-4})
    state = create_train_state(model, opt, batch)
    text = make_mlip_train_step(model, opt).lower(state, batch).as_text(debug_info=True)
    under = {}
    for layer, scope in re.findall(r"HydraModel\.conv_block/graph_convs_(\d)/([a-z]+)/", text):
        under.setdefault(scope, set()).add(int(layer))
    every = set(range(5))
    assert under == {"geometry": {0}, "smearing": {0}, "filter": every, "aggregate": every,
                     "update": every}
    for tag in ("jvp(jvp(HydraModel))", "transpose(jvp(transpose(jvp(HydraModel))))"):
        for scope in ("filter/filter1", "filter/filter2", "aggregate/lin1", "update/lin2"):
            assert re.search(re.escape(tag) + rf"/[^\"]*graph_convs_3/{scope}/", text), (tag, scope)
    kernels = re.findall(r"graph_convs_\d/([a-z]+)/[^\"]*fused_gather_scatter", text)
    assert (set(kernels) == {"aggregate"}) if fused == "1" else not kernels


def test_the_schnet_build_line_names_widths_and_the_route(capsys, monkeypatch):
    """``SchNetConv.describe``: widths, Gaussians, cutoff, activation, where the
    edge basis is made, and the static route of the gather-multiply-sum."""
    import dataclasses

    from hydragnn_tpu.models.schnet import SchNetConv

    model, _ = _schnet_case()
    built = capsys.readouterr().out
    assert ("SchNet hidden 32, 16 filters, 20 Gaussians, 5 interactions, cutoff 6.0, "
            "activation shifted_softplus; geometry and smearing once a call; aggregate "
            "[E x 16 -> N]: XLA gather-multiply-segment_sum (the fused kernel is off on this "
            "backend)") in built
    monkeypatch.setenv("HYDRAGNN_FUSED_SCATTER", "1")
    # whole lanes: the tiled sum takes the [E, 256] rows, certificate or none
    wide = SchNetConv.describe(dataclasses.replace(model.spec, num_filters=256))
    assert ("aggregate [E x 256 -> N]: gather x filter -> tiled fused_segment_sum (Mosaic), "
            "every gather's transpose too whatever gs_fits says") in wide
    # half a lane row: the resident kernel for a certified batch, XLA's form for the others
    narrow = SchNetConv.describe(dataclasses.replace(model.spec, num_filters=64))
    assert ("gs_fits held: fused_gather_scatter (Mosaic); not held: XLA gather-multiply-"
            "segment_sum (64 channels not a multiple of 128)") in narrow
    # 2 x (1,024 + 128) rows x 8,192 lanes x 4 B x 2 > 48 MiB: past the tiled form's budget too
    over = SchNetConv.describe(dataclasses.replace(model.spec, num_filters=8192))
    assert "XLA gather-multiply-segment_sum (accumulator and edge blocks" in over
    assert "whatever gs_fits says" in over
    moving = SchNetConv.describe(dataclasses.replace(model.spec, equivariance=True))
    assert "each layer makes its own edge basis" in moving and "once a call" not in moving
