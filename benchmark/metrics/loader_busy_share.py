"""Host input pipeline: self time of the ``collate`` and ``transfer`` spans
(``graphs/batching.py``) over window x producer threads (threads other than
the loop's with spans open side by side, at least 1). Says the median
``collate`` and ``transfer`` on an earlier line."""

import statistics

from lib import spans


def read(ctx):
    s = spans.load(ctx)
    window = spans.window_ns(s["host"]) if s else 0.0
    collates = spans.named(s["host"], "collate") if window else []
    if not collates:
        return None
    transfers = spans.named(s["host"], "transfer")
    threads = max(1, spans.producers(s["host"]))
    med = lambda ev: 1e-6 * statistics.median(e[1] - e[0] for e in ev) if ev else float("nan")
    ctx["say"](f"loader: median collate {med(collates):.3f} ms over {len(collates)}, median "
               f"transfer {med(transfers):.3f} ms over {len(transfers)}, {threads} producer thread(s)")
    return 100.0 * spans.self_time_of(s["host"], ("collate", "transfer")) / (window * threads)
