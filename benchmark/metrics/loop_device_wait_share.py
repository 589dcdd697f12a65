"""Staging and dispatch: the share of the window (first ``train`` span's
start to the last one's end) the loop spent waiting for the device: the
``backpressure`` and ``drain`` spans, and of the ``dispatch`` spans what is
over the median call that returned at once (the runtime holds a step call
while its queue of executions is full: ``lib/spans.py::fast_dispatches``).
The host's slack: near 0 the host sets the pace. Says the three spans'
shares on an earlier line."""

import statistics

from lib import spans


def read(ctx):
    s = spans.load(ctx)
    window = spans.window_ns(s["host"]) if s else 0.0
    if not window:
        return None
    total = {name: sum(e[1] - e[0] for e in spans.named(s["host"], name))
             for name in ("backpressure", "drain", "dispatch")}
    fast, held = spans.fast_dispatches(s["host"])
    own = (len(fast) + held) * statistics.median(fast) if fast else 0.0
    ctx["say"]("loop, % of the window: " + ", ".join(
        f"{k} {100.0 * v / window:.2f}" for k, v in total.items())
        + f", of which the step calls' own work {100.0 * own / window:.2f}")
    return 100.0 * (sum(total.values()) - own) / window
