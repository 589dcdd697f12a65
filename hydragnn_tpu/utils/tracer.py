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
  arms it (every span but ``gc``: ``_on_gc``).

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
* ``release`` (batch) — the rebinding ``state, metrics = stepped`` alone:
  the loop's drop of the state the step donated;
* ``collate`` (batch, real_edges, edge_slots; with a triplet pad dimension
  also real_triplets, triplet_slots) — ``collate_chunk``, on the thread
  that runs it (``graphs/batching.py``). Its phases are ARGUMENTS it notes
  as it goes, not child spans (a child would leave its self time):
  ``fetch_us`` (the samples read from the store), ``fill_us`` (the
  allocations and the per-sample copy loop), ``certify_us``
  (``_batch_meta``; 0 with ``certify=False``);
* ``triplets`` (edges, triplets) — one sample's triplet enumeration inside
  ``collate`` (``graphs/triplets.py``), where the sample carries none;
* ``transfer`` (batch, leaves, bytes) — ``PrefetchLoader._transfer``: one
  ``device_put`` an array leaf of the batch, and what they held;
* ``handoff`` (batch) — ``background_iter``'s worker waiting for a free
  queue slot, a sibling of ``collate`` / ``transfer`` on its thread; the
  consumer notes ``ready`` on the loop's open ``dataload``: how many
  finished items it found;
* ``gc`` (generation, collected) — a generation-1 or -2 collection of the
  cyclic collector, on the thread it ran on, where that thread has a span
  open (``watch_gc``);
* ``validate`` / ``test`` — one ``evaluate`` pass; ``stage_block`` — a
  superstep block's staging (``train/superstep.py``).

``batch`` is one number from the loader to the step: the batch's index in
the epoch's plan, on ``collate``, ``transfer``, ``handoff``, ``dataload``,
``stage``, ``dispatch``, ``release`` and ``backpressure`` alike.

The loop does not sync per batch: up to ``_MAX_IN_FLIGHT`` steps are
queued, so a host span says what the HOST did; what the device did
meanwhile is on the device lines of the same profile.

Spans are NESTED per thread: each thread keeps an open-span stack and a
span's elapsed time comes from its own stack entry, so one name open on
several threads (``PrefetchLoader(workers>1)``) sums every thread's time.
"""

from __future__ import annotations

import contextlib
import gc
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
# guards Timer.count/.total across threads. Re-entrant: a first close of a
# name allocates its Timer under the lock, an allocation may start a
# collection, and the collector's hook (``_on_gc``) closes its own span on
# the same thread
_lock = threading.RLock()
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
            _count(name, t1 - t0_perf)
            if _trace.trace_enabled():
                _trace.add_span(name, t0_wall, t1 - t0_perf, args=args)
            return


def _count(name: str, seconds: float):
    with _lock:  # the lookup too: a first miss creates the Timer
        timer = _timers[name]
        timer.total += seconds
        timer.count += 1


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


def _on_gc(phase: str, info: dict) -> None:
    """The ``gc.callbacks`` hook. A generation-0 collection returns at once.
    An older one becomes a ``gc`` span on the thread it runs on (the
    collector runs on whichever thread allocated last), nested in whatever
    that thread has open, IF it has a span open: the loop inside ``train``,
    a producer inside ``collate`` / ``transfer`` / ``handoff``. On a thread
    with no span open (a watcher, a checkpoint writer) it is counted on the
    aggregate ``gc`` timer alone and writes no annotation: a reader of the
    profile counts the threads that hold spans side by side as producers.

    Safe against the collection that starts INSIDE ``stop`` (the Timer a
    first close allocates under ``_lock``): ``_lock`` is re-entrant, the
    collector itself does not nest (no collection starts inside a hook), and
    the close below takes no other lock: a ``gc`` span reaches the timer and
    the profile, not the Chrome buffer, whose lock is not re-entrant."""
    generation = info["generation"]
    if generation == 0:
        return
    stack = _span_stack()
    if phase == "start":
        if stack:
            start("gc", generation=generation)
        else:
            _spans.gc_t0 = time.perf_counter()
    elif stack and stack[-1][0] == "gc":
        _, t0, _, annotation, _ = stack.pop()
        annotation.set_metadata(collected=info["collected"])
        annotation.__exit__(None, None, None)
        _count("gc", time.perf_counter() - t0)
    else:
        t0 = getattr(_spans, "gc_t0", None)
        if t0 is not None:  # None: the hook was registered inside this collection
            _spans.gc_t0 = None
            _count("gc", time.perf_counter() - t0)


def watch_gc() -> None:
    """Register the collector hook, once a process however often it is
    asked (``PrefetchLoader.__iter__`` and ``train_epoch`` both ask)."""
    if not gc_watched():
        gc.callbacks.append(_on_gc)


def gc_watched() -> bool:
    """Whether the collector hook is registered (a reader of a profile asks:
    no ``gc`` span then means no pause, not no hook)."""
    return _on_gc in gc.callbacks


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
