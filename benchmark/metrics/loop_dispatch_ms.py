"""Staging and dispatch: median length of the program's own ``dispatch`` span
(``train/loop.py``: the ``train_step(state, batch)`` call only) over the
calls that returned at once, from the profiler's trace; the calls the
runtime held for the device (``lib/spans.py::fast_dispatches``) are counted
on an earlier line and left out. The inside twin of ``dispatch_ms``, which
is a median over both kinds."""

import statistics

from lib import spans


def read(ctx):
    s = spans.load(ctx)
    fast, held = spans.fast_dispatches(s["host"]) if s else ([], 0)
    if not fast:
        return None
    ctx["say"](f"dispatch: {len(fast)} calls returned at once, {held} held by the runtime "
               f"({100.0 * held / (len(fast) + held):.1f}%)")
    return 1e-6 * statistics.median(fast)
