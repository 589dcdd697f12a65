"""Span timers: one span system, three sinks.

Reference: ``hydragnn/utils/profiling_and_tracing/tracer.py`` — a plugin
registry of tracers (GPTL region timers, Score-P, NVML/ROCm/XPU energy
counters) with ``tr.start/stop(name)`` spans hard-wired around the train loop.

TPU equivalent: ``tr.start/stop/span(name, **args)`` open and close one host
span, which goes to

* an aggregate ``Timer`` per name (count/total; ``get``, ``summary``,
  ``save``) — always;
* the profiler's own trace, as a ``jax.profiler.TraceAnnotation`` named
  ``hydragnn/<name>`` carrying ``args`` (small ints/strings) — always made:
  with no profile running it is one atomic check. A profile started by
  ``HYDRAGNN_TRACE_LEVEL>=1`` (``train/loop.py``) or by a benchmark
  therefore shows the program's spans on the host threads beside the
  device's operation lines, on one clock;
* the Chrome trace-event buffer (``hydragnn_tpu.telemetry.trace``) with the
  same ``args``, when ``HYDRAGNN_TRACE_EVENTS``/``Telemetry.trace_events``
  arms it.

Spans of the training path, and where each opens:

* ``train`` — one ``train_epoch``: the loop and its drain;
* ``dataload`` (batch) — the loop's wait for its next batch
  (``train/loop.py::_timed_iter``);
* ``stage`` (batch) — ``put_fn`` / the ``jnp.asarray`` tree-map;
* ``dispatch`` (batch) — the ``train_step(state, batch)`` call only;
* ``backpressure`` (batch) — ``_backpressure``: the wait for the step
  ``_MAX_IN_FLIGHT`` back;
* ``drain`` — the epoch-end ``block_until_ready``;
* ``reduce`` — ``_accumulate``: one ``device_get`` and the means;
* ``collate`` (batch, real_edges, edge_slots; with a triplet pad dimension
  also real_triplets, triplet_slots) — ``collate_chunk``, on the thread
  that runs it (``graphs/batching.py``);
* ``triplets`` (edges, triplets) — one sample's triplet enumeration inside
  ``collate`` (``graphs/triplets.py``), where the sample carries none;
* ``transfer`` — ``PrefetchLoader._transfer`` (``device_put`` of a batch);
* ``validate`` / ``test`` — one ``evaluate`` pass; ``stage_block`` — a
  superstep block's staging (``train/superstep.py``).

The loop does not sync per batch: up to ``_MAX_IN_FLIGHT`` steps are
queued, so a host span says what the HOST did; what the device did
meanwhile is on the device lines of the same profile.

Spans are NESTED per thread: each thread keeps an open-span stack and a
span's elapsed time comes from its own stack entry, so one name open on
several threads (``PrefetchLoader(workers>1)``) sums every thread's time.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import defaultdict

import jax

from ..telemetry import trace as _trace


class Timer:
    """Aggregate of one span name over every thread."""

    __slots__ = ("count", "total")

    def __init__(self):
        self.count = 0
        self.total = 0.0


_timers: dict[str, Timer] = defaultdict(Timer)
_lock = threading.Lock()  # guards Timer.count/.total across threads
# per-thread open-span stack [(name, t0_perf, t0_wall, annotation, args), ...]
_spans = threading.local()


def _span_stack() -> list:
    stack = getattr(_spans, "stack", None)
    if stack is None:
        stack = _spans.stack = []
    return stack


def start(name: str, **args):
    annotation = jax.profiler.TraceAnnotation(f"hydragnn/{name}", **args)
    annotation.__enter__()
    _span_stack().append((name, time.perf_counter(), time.time(), annotation, args))


def stop(name: str):
    """Close the INNERMOST open span of this name on this thread (spans
    close LIFO in the loop's usage; the search keeps a stray out-of-order
    stop from corrupting unrelated open spans, and a stop with no open span
    does nothing)."""
    t1 = time.perf_counter()
    stack = _span_stack()
    for i in range(len(stack) - 1, -1, -1):
        if stack[i][0] == name:
            _, t0_perf, t0_wall, annotation, args = stack.pop(i)
            annotation.__exit__(None, None, None)
            with _lock:  # the lookup too: a first miss creates the Timer
                timer = _timers[name]
                timer.total += t1 - t0_perf
                timer.count += 1
            if _trace.trace_enabled():
                _trace.add_span(name, t0_wall, t1 - t0_perf, args=args)
            return


def note(name: str, **args):
    """Add arguments to the innermost open span of this name on this thread:
    what a span learns only while it runs (a count it makes). They reach the
    same sinks as the arguments it was opened with."""
    stack = _span_stack()
    for i in range(len(stack) - 1, -1, -1):
        if stack[i][0] == name:
            stack[i][3].set_metadata(**args)
            stack[i][4].update(args)
            return


@contextlib.contextmanager
def span(name: str, **args):
    start(name, **args)
    try:
        yield
    finally:
        stop(name)


def reset():
    _timers.clear()


@contextlib.contextmanager
def isolated_timers():
    """Swap the process-global aggregate ``Timer`` registry for a fresh
    one for the duration of the scope (single rebind, atomic under the
    GIL) — the tracer half of ``telemetry.isolate()``. Spans started
    inside the scope land in the fresh registry because every accessor
    reads the module global at call time; the previous registry — and any
    half-open spans it held — comes back intact on exit."""
    global _timers
    fresh: dict[str, Timer] = defaultdict(Timer)
    prev, _timers = _timers, fresh
    try:
        yield fresh
    finally:
        _timers = prev


def get(name: str) -> Timer:
    return _timers[name]


def summary() -> dict[str, dict]:
    return {
        k: {"count": t.count, "total_s": t.total, "avg_s": t.total / max(t.count, 1)}
        for k, t in sorted(_timers.items())
    }


def save(path: str = "./logs/", prefix: str = "timing"):
    """Dump per-process timing json (the reference writes ``gp_timing.p{rank}``,
    ``tracer.py:432-458``)."""
    try:
        pid = jax.process_index()
    except Exception:
        pid = 0
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, f"{prefix}.p{pid}.json"), "w") as f:
        json.dump(summary(), f, indent=2)


def print_timers(verbosity_level: int = 0):
    from .print_utils import print_master

    for name, stats in summary().items():
        print_master(
            f"[timer] {name}: total {stats['total_s']:.3f}s over {stats['count']} calls "
            f"(avg {stats['avg_s'] * 1e3:.2f} ms)"
        )
