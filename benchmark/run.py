"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, on the machine it is started on. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result. The
last line of standard output is the result object; everything else (device
stamp, shapes, routes, sample counts, each number compared beside its limit)
is on earlier lines; the numbers compared beside their limits are also the
last lines of standard error and the result's last key, ``compared``.

A run: data from the seed -> the program built as ``run_training`` builds it
(``lib/program.py``) with weights the benchmark makes from the seed -> the
first steps through ``train_epoch`` (one batch of every padded shape the
window drives, their state kept for the comparison) -> one warm step for
every other (padded shape, layout certificate) the window will meet -> the
window: ``train_epoch`` over the prefetching loader, epoch
after epoch, until ``--seconds`` have passed, closed by ``block_until_ready``
-> the program's state freed -> the plain reference follows the same first
steps and the comparison decides ``correct``.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse
import json
import os
import shutil
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]

TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")


def say(msg: str) -> None:
    print(f"[bench {time.perf_counter() - _T0:7.2f}s] {msg}", flush=True)


def device_stamp(jax) -> dict:
    import importlib.metadata as md

    devs = jax.devices()
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = None
    say(f"device: platform={devs[0].platform} device_kind={devs[0].device_kind!r} "
        f"count={len(devs)} " + " ".join(f"{k}={v}" for k, v in versions.items())
        + f" python={sys.version.split()[0]}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def compile_log(jax) -> None:
    """Say each backend compile and each read of the compile cache that takes
    over half a second, as it happens."""
    from jax import monitoring

    def on_duration(event: str, seconds: float, **kw) -> None:
        if seconds >= 0.5 and event.rsplit("/", 1)[-1] in (
                "backend_compile_duration", "cache_retrieval_time_sec",
                "jaxpr_trace_duration", "jaxpr_to_mlir_module_duration"):
            say(f"  {event.rsplit('/', 1)[-1]} {seconds:.1f} s")

    monitoring.register_event_duration_secs_listener(on_duration)


def device_peak_bytes(device) -> int:
    """The peak footprint on one chip. The TPU runtime keeps a running
    program's temporaries in RESERVED memory, which ``peak_bytes_in_use``
    leaves out (it read 0.7 GB while a 12.6 GiB program ran); the two peaks
    are disjoint regions of the device's memory, so the footprint is their
    sum."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))


def reference_cache(jax, cache_dir: str | None) -> None:
    """Give the plain reference a compile cache of its own, ``reference/``
    inside the program's. Its programs are as large as the step programs
    (35-50 MB each on the TPU); on a machine that caps the cache
    (``JAX_COMPILATION_CACHE_MAX_SIZE``, 192 MiB on the chip machines) they
    would evict the step programs between runs. The cap holds for each
    directory, and jax's eviction does not look into subdirectories."""
    if not cache_dir:
        return
    from jax.experimental.compilation_cache import compilation_cache

    path = os.path.join(cache_dir, "reference")
    os.makedirs(path, exist_ok=True)
    compilation_cache.set_cache_dir(path)
    compilation_cache.reset_cache()


def signatures(prog, epochs: int) -> dict:
    """Every (padded shape, layout certificate) the window's epochs hold ->
    the plan entry of its first occurrence, in the order they occur.
    Collates each batch once, on the host, to read its certificate."""
    first = {}
    for epoch in range(epochs):
        for chunk, pad in prog.plan(epoch):
            first.setdefault((pad.as_tuple(), prog.collate(chunk, pad).meta), (chunk, pad))
    return first


def check_entries(prog, first: dict, at_least: int) -> list:
    """The steps ``correct`` compares: the first batch of every padded shape
    the window drives, so that each compiled step program is compared, the
    worst-case bucket's too; topped up from the head of epoch 0 to
    ``at_least`` steps."""
    by_shape = {}
    for (shape, _), entry in first.items():
        by_shape.setdefault(shape, entry)
    entries = list(by_shape.values())
    have = {tuple(int(i) for i in chunk) for chunk, _ in entries}
    for chunk, pad in prog.plan(0):
        if len(entries) >= at_least:
            break
        if tuple(int(i) for i in chunk) not in have:
            entries.append((chunk, pad))
    return entries


def first_moment(opt_state):
    import optax

    return optax.tree_utils.tree_get(opt_state, "mu")


def run(args, require_chip: bool = True, mutate=None) -> dict:
    """One run; returns the result object. ``require_chip=False`` and
    ``mutate`` are for ``benchmark/tests`` only: the first skips the look for
    a chip, the second is handed the built program to break it."""
    from lib import check, weights
    from lib.cells import Cell, load_module, peaks

    cell = Cell(args.workload, rehearse=not require_chip)
    import jax

    stamp = device_stamp(jax)
    compile_log(jax)
    if require_chip and (stamp["platform"] != "tpu" or stamp["count"] < cell.chips):
        print(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); found "
              f"{stamp['count']} x {stamp['platform']}", file=sys.stderr)
        raise SystemExit(2)
    peak = peaks(stamp["kind"]) if require_chip else None
    precision = cell.config["precision"]
    jax.config.update("jax_default_matmul_precision", precision["matmul"])
    say(f"cell {cell.name}: config {cell.entry['config']} traffic {cell.entry['traffic']} "
        f"chips {cell.chips} precision {precision} seed {args.seed}")

    # -- set-up -----------------------------------------------------------------
    graphs = cell.generator.generate(cell.traffic["params"], args.seed)
    say(f"data: {len(graphs)} graphs, {sum(len(g['z']) for g in graphs)} atoms, "
        f"{sum(len(g['senders']) for g in graphs)} edges from the seed")
    from lib.program import Program

    prog = Program(cell.config, cell.traffic, graphs,
                   lambda shapes: weights.make_weights(shapes, args.seed, cell.config["weights"]),
                   log=say)
    from hydragnn_tpu.analysis.sentinel import compile_counts
    from hydragnn_tpu.utils import tracer as tr

    loader = prog.inner_loader
    say(f"program: batch {loader.batch_size}, "
        f"{len(loader)} batches an epoch, buckets "
        f"{[b.as_tuple() for b in (loader.buckets or [loader.pad])]}, "
        f"compile cache {prog.cache_dir}")
    if mutate is not None:
        mutate(prog)
    params0 = weights.flat_dict(prog.params0)
    epochs_distinct = int(cell.traffic["distinct_epochs"])
    first = signatures(prog, epochs_distinct)
    checked = check_entries(prog, first, int(cell.traffic["check_steps"]))
    seen = {(pad.as_tuple(), prog.collate(chunk, pad).meta) for chunk, pad in checked}
    lower0 = compile_counts()
    prog.step.capture = len(checked)
    prog.steps(checked)
    warm = [entry for sig, entry in first.items() if sig not in seen]
    if warm:
        prog.steps(warm)
    jax.block_until_ready(prog.state)
    captured = jax.device_get(prog.step.captured)
    prog.step.captured = []
    lower1 = compile_counts()
    say(f"warm-up: {len(first)} program(s) for the window "
        f"({len(seen)} met in the {len(checked)} compared steps, padded shapes "
        f"{[pad.as_tuple()[:2] for _, pad in checked]}), "
        f"{lower1['lowerings'] - lower0['lowerings']} lowering(s), "
        f"{lower1['persistent_cache_hits'] - lower0['persistent_cache_hits']} from the compile cache")
    routes(cell, first)

    # -- the window -----------------------------------------------------------------
    seconds = float(args.seconds)
    if args.trace:
        seconds = min(seconds, float(cell.traffic.get("trace_seconds", seconds)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR, exist_ok=True)
    prog.step.clear()
    prog.feed.collated.clear()
    dataload0 = tr.get("dataload").total
    lower0 = compile_counts()
    if args.trace:
        jax.profiler.start_trace(TRACE_DIR)
    setup_s = time.perf_counter() - _T0
    t_start = time.perf_counter()
    epoch, epoch_losses = 0, []
    while time.perf_counter() - t_start < seconds:
        epoch_losses.append(prog.epoch(epoch % epochs_distinct))
        epoch += 1
    with jax.profiler.TraceAnnotation("bench_sync"):
        jax.block_until_ready(prog.state)
    window_s = time.perf_counter() - t_start
    prog.step.join()  # every step has its finish stamp: the state is ready, so no wait
    if args.trace:
        jax.profiler.stop_trace()
    lowerings = compile_counts()["lowerings"] - lower0["lowerings"]
    timers = {"dataload": tr.get("dataload").total - dataload0}
    peak_bytes = max(device_peak_bytes(d) for d in jax.local_devices())
    losses = jax.device_get(prog.step.losses)
    steps = len(prog.step.returns)
    collated = prog.feed.collated[:steps]
    real_graphs = sum(c[3] for c in collated)
    failed = sum(1 for loss in losses if not (abs(float(loss)) < float("inf")))
    returns, finishes = prog.step.returns, prog.step.finishes
    if len(finishes) != steps:
        raise RuntimeError(f"{steps} step calls returned but {len(finishes)} finishes were stamped")
    intervals = [b - a for a, b in zip(finishes, finishes[1:])]
    return_intervals = [b - a for a, b in zip(returns, returns[1:])]
    say(f"window: {window_s:.3f} s, {epoch} epoch(s), {steps} steps, {real_graphs} real graphs, "
        f"{len(intervals)} step intervals, {lowerings} lowering(s) inside, "
        f"epoch losses {[round(float(x), 5) for x in epoch_losses[:4]]}")
    step_tails(intervals, return_intervals, prog.step.dispatch_s, len(prog.feed))
    shapes_used = {}
    for c in collated:
        shapes_used[c[0]] = shapes_used.get(c[0], 0) + 1
    say("padded shapes (nodes, edges, graphs, triplets) -> steps: "
        + ", ".join(f"{k} -> {v}" for k, v in sorted(shapes_used.items())))

    # -- correct: the plain reference follows the first steps -------------------------
    dispatch_s = list(prog.step.dispatch_s)
    corpus_index, config = prog.corpus_index, prog.config
    stats0 = weights.flat_dict(prog.stats0)
    prog.release()
    opt = dict(cell.config["optimizer_reference"],
               learning_rate=float(config["NeuralNetwork"]["Training"]["Optimizer"]["learning_rate"]))
    got = check.program_numbers(captured, params0, weights.flat_dict, first_moment, opt["b1"])
    ref_steps = [[[graphs[j] for j in corpus_index[chunk]]] for chunk, _ in checked]
    t_ref = time.perf_counter()
    reference_cache(jax, prog.cache_dir)
    # a model with batch statistics: the objective file is handed their
    # initial values and answers their norms after the last step, ``stats_norm``
    with_stats = {"stats0": stats0} if stats0 else {}
    want = cell.follow(cell.reference.node_energy, cell.reference.hyperparameters(cell.config),
                       opt, params0, ref_steps, float(cell.config["input_scale"]), **with_stats)
    ok, rows = check.compare(got, want, cell.config["limits"])
    for r in rows:
        say(f"compare {r['name']}: {r['value']:.3e} (limit {r['limit']:.1e}) "
            f"{'ok' if r['ok'] else 'OVER'} at {r['where']}")
    say(f"compare finite: {failed} of {steps} step losses not finite (limit 0); "
        f"reference took {time.perf_counter() - t_ref:.1f} s, outside set-up and window")
    correct = bool(ok and failed == 0 and steps > 0)
    # each number compared beside its limit; a gap that is not finite reads 1e300
    compared = {r["name"]: {"value": r["value"] if r["value"] < 1e300 else 1e300,
                            "limit": r["limit"]} for r in rows}
    compared["nonfinite_losses"] = {"value": failed, "limit": 0}

    # -- metrics -------------------------------------------------------------------
    ctx = {
        "config": cell.config, "chips": cell.chips,
        "window_s": window_s, "steps": steps, "timers": timers, "collated": collated,
        "dispatch_s": dispatch_s, "lowerings": lowerings,
        "peak_bytes": peak_bytes, "peaks": peak, "ops": cell.ops, "trace": None, "events": None, "say": say,
    }
    device = dict(stamp, memory_peak_bytes=int(peak_bytes))
    result = {"correct": correct, "attempted": steps, "failed": failed, "metrics": {},
              "device": device}
    if args.trace:
        from lib import trace as trace_lib

        extracted = trace_lib.extract(trace_lib.find_xplane(TRACE_DIR))
        ctx["events"] = extracted
        ctx["trace"] = summary = trace_lib.reduce(extracted)
        say(f"trace: device lines {extracted['lines']}; busy {summary.get('busy_s')} s of "
            f"{window_s:.3f} s; host spans {len(extracted['host'])}")
        stamps_against_trace(finishes, extracted, len(prog.feed))
        device["busy_s"] = summary.get("busy_s", 0.0)
        device["window_s"] = window_s
        result["breakdown"] = {"device_ops": summary.get("device_ops", []),
                               "idle_gaps": summary.get("idle_gaps", [])}
        for m in cell.per_layer:
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {
            "setup_s": setup_s,
            "graphs_per_s": real_graphs / window_s,
            "step_finish_interval_p90_ms": p90_ms(intervals),
        }
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    result["compared"] = compared  # last in the line
    return result


def p90_ms(intervals: list) -> float | None:
    return 1e3 * statistics.quantiles(intervals, n=10)[-1] if len(intervals) >= 10 else None


def step_tails(intervals: list, return_intervals: list, calls: list, per_epoch: int) -> None:
    """Both stamps of the window's steps on earlier lines: the tail of the
    time between step ENDS on the device (the end-to-end metric) beside the
    tail of the time between RETURNS of the step call, which reads the
    host's pace wherever the runtime does not hold the call; how many calls
    it held (``lib/spans.py::split_held`` on the wrapper's own call lengths,
    ``calls``); and where the largest intervals sit."""
    from lib.spans import split_held

    if len(intervals) < 10:
        return
    held = split_held(calls)[1]
    cuts = statistics.quantiles(intervals, n=100)
    say(f"step tails, ms: finish interval p90 {p90_ms(intervals):.3f} (p50 {1e3 * cuts[49]:.3f}, "
        f"p99 {1e3 * cuts[98]:.3f}, max {1e3 * max(intervals):.3f}); return interval p90 "
        f"{p90_ms(return_intervals):.3f}; {held} of {len(calls)} calls held by the runtime "
        f"({100.0 * held / len(calls):.1f}%)")
    # where a stall sits: the step that ended the interval, the time between
    # the same two steps' returns, and how much of that passed inside the
    # step call (the rest is the loop and its wait for the loader)
    say("largest step intervals, ms (epoch:step, between the returns, of it inside the step "
        "call): " + ", ".join(
            f"{1e3 * intervals[i]:.1f} ({(i + 1) // per_epoch}:{(i + 1) % per_epoch}, "
            f"{1e3 * return_intervals[i]:.1f}, {1e3 * calls[i + 1]:.1f})"
            for i in sorted(range(len(intervals)), key=intervals.__getitem__)[-5:][::-1]))


def stamps_against_trace(finishes: list, extracted: dict, per_epoch: int) -> None:
    """A traced run's check of the finish stamp itself, on an earlier line:
    the watcher's stamps (the host's clock) against the ends of the step
    program's executions in the trace (the device's clock), step by step."""
    from lib import trace as trace_lib
    from lib.spans import STEP_FUNCTIONS

    if len(finishes) < 11:
        return
    ends = trace_lib.step_ends(extracted, STEP_FUNCTIONS)
    gaps = trace_lib.stamp_disagreement(finishes, ends)
    if gaps is None:
        names = sorted({n.split("(")[0] for ev in extracted.get("modules", {}).values()
                        for n, _, _ in ev})
        say(f"finish stamps against the trace: {len(finishes)} stamps but {len(ends)} executions "
            f"of {STEP_FUNCTIONS} among the trace's programs {names[:8]}; not compared")
        return

    def cuts(values):
        q = statistics.quantiles(values, n=100)
        return f"p50 {1e3 * q[49]:.3f}, p99 {1e3 * q[98]:.3f}, max {1e3 * max(values):.3f}"

    host = [b - a for a, b in zip(finishes, finishes[1:])]
    say(f"finish stamps against the trace's {len(ends)} step ends, ms: |finish interval - "
        f"device interval| {cuts(gaps['interval'])}; stamp later than the run's promptest "
        f"{cuts(gaps['late'])}; p90 of the device's own intervals "
        f"{p90_ms(gaps['device_interval']):.3f} against the stamps' {p90_ms(host):.3f}")
    say("largest finish intervals, ms (epoch:step, the device's interval between the same two "
        "steps): " + ", ".join(
            f"{1e3 * host[i]:.1f} ({(i + 1) // per_epoch}:{(i + 1) % per_epoch}, "
            f"{1e3 * gaps['device_interval'][i]:.1f})"
            for i in sorted(range(len(host)), key=host.__getitem__)[-5:][::-1]))


def routes(cell, first) -> None:
    """The static routes of the edge-to-node row sum, per padded shape, on an
    earlier line (``ops/routing.py`` reasons): ``fused_segment_sum`` keeps
    the ``[N, C]`` accumulator resident where that rule and the certificate
    admit it; where they do not, or where the call states ``fits=False`` (the
    gather-multiply-sum of a stack with ``num_filters``), it takes the tiled
    form where THAT rule admits the rows, else XLA's sum. At the width the
    cell's sum has: SchNet sums ``[E, num_filters]`` message rows."""
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.ops import fused_scatter, routing

    arch = cell.config["NeuralNetwork"]["Architecture"]
    width = int(arch.get("num_filters") or arch["hidden_dim"])
    for shape, fits in sorted({(sig[0], getattr(sig[1], "send_fits", None)) for sig in first},
                              key=lambda kv: (kv[0], str(kv[1]))):
        nodes, edges = shape[0], shape[1]
        rows = jax.ShapeDtypeStruct((edges, width), jnp.float32)
        resident, tiled = (fused_scatter.scatter_route(
            rows, edges, nodes, fused_scatter._TILE_WINDOW, tiled=t) for t in (False, True))
        if fits is False:
            resident = "collate certificate send_fits=False"
        say(f"route fused_segment_sum[{edges} x {width} -> {nodes}]: resident form "
            f"{routing.describe(resident)}; tiled form, taken where the resident one is "
            f"refused or the call states no certificate: {routing.describe(tiled)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; prints no metric")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.seconds is None:
            args.seconds = 1.0
    if args.seconds is None:
        ap.error("--seconds is required")
    result = run(args, require_chip=not args.rehearse)
    if args.rehearse:
        say(f"rehearsal on the CPU: correct={result['correct']} attempted={result['attempted']} "
            f"failed={result['failed']} metrics {sorted(result['metrics'])} "
            f"(a CPU run prints no result line)")
        return 0 if result["correct"] else 1
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']:.6g} (limit {c['limit']:g})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # the prefetcher's daemon threads must not outlive the result
