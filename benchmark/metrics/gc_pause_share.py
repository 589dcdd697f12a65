"""Host input pipeline: the share of the window in which some thread sat in a
``gc`` span (a generation-1 or -2 collection of the cyclic collector, written
by ``utils/tracer.py``'s hook on a thread that has a span open): union over
the threads / window. 0.0 where the hook was registered and the run held
none; None where the program has no such hook. Says the count by generation,
the longest pause and the span and thread it sat in on an earlier line."""

from lib import host_spans, spans


def read(ctx):
    s = spans.load(ctx)
    window = spans.window_ns(s["host"]) if s else 0.0
    if not window or not spans._kept(ctx, "_gc_watched", host_spans.gc_watched):
        return None
    pauses = host_spans.gc_pauses(s["host"])
    by_generation = {}
    for p in pauses:
        by_generation[p[2]] = by_generation.get(p[2], 0) + 1
    line = "collector pauses: " + (", ".join(
        f"{n} of generation {g}" for g, n in sorted(by_generation.items(), key=str)) or "none")
    if pauses:
        start, end, generation, collected, thread, inside = pauses[0]
        line += (f"; median {1e-6 * host_spans.median_or_nan(p[1] - p[0] for p in pauses):.3f} ms, "
                 f"longest {1e-6 * (end - start):.3f} ms (generation {generation}, {collected} "
                 f"collected) inside {inside} on {thread}")
    ctx["say"](line)
    return 100.0 * host_spans.union_ns((p[0], p[1]) for p in pauses) / window
