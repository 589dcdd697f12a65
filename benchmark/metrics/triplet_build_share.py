"""Host input pipeline: self time of the ``triplets`` spans (one sample's
enumeration, ``graphs/triplets.py``, on the thread that collates) over
window x producer threads, as ``loader_busy_share`` counts threads. 0 where
the batches carry triplets but none was enumerated inside the window; None
where the ``collate`` spans carry no triplet counts. Says the median
enumeration on an earlier line."""

import statistics

from lib import spans, triplet_spans


def read(ctx):
    s = spans.load(ctx)
    window = spans.window_ns(s["host"]) if s else 0.0
    if not window or not triplet_spans.counts(ctx)[1]:
        return None
    built = spans.named(s["host"], "triplets")
    if built:
        ctx["say"](f"triplets: median enumeration {1e-6 * statistics.median(e[1] - e[0] for e in built):.3f}"
                   f" ms over {len(built)} samples, {sum(e[3].get('triplets', 0) for e in built)} "
                   f"triplets from {sum(e[3].get('edges', 0) for e in built)} edges")
    threads = max(1, spans.producers(s["host"]))
    return 100.0 * spans.self_time_of(s["host"], ("triplets",)) / (window * threads)
