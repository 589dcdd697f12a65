"""Staging and dispatch: median, over the window's epoch boundaries, of the
time from the end of an epoch's ``drain`` span (the device has finished the
epoch's last step) to the start of the next epoch's first ``dispatch`` span:
what the device waits for at a boundary. None with fewer than two epochs in
the trace. Says the medians of what a boundary holds (``reduce``, the new
epoch's first ``dataload`` and ``stage``) on an earlier line."""

import statistics

from lib import spans


def read(ctx):
    s = spans.load(ctx)
    main = spans.loop_thread(s["host"]) if s else None
    if main is None:
        return None
    first = {name: [e for e in spans.named(s["host"], name, main) if e[3].get("batch") == 0]
             for name in ("dataload", "stage", "dispatch")}
    turns = []
    for drain in spans.named(s["host"], "drain", main):
        later = [e[0] for e in first["dispatch"] if e[0] >= drain[1]]
        if later:
            turns.append(min(later) - drain[1])
    if not turns:
        return None
    parts = {"reduce": spans.named(s["host"], "reduce", main),
             "first dataload": first["dataload"], "first stage": first["stage"]}
    ctx["say"]("epoch boundary, median ms: " + ", ".join(
        f"{k} {1e-6 * statistics.median(e[1] - e[0] for e in ev):.2f}"
        for k, ev in parts.items() if ev))
    return 1e-6 * statistics.median(turns)
