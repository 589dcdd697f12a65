"""Host input pipeline: self time of the ``handoff`` spans (the producer's
wait for a free queue slot, ``graphs/batching.py::background_iter``) over
window x producer threads, the denominator of ``loader_busy_share``: near 0
the loader sets the pace, high the loop does. None where the run holds no
``handoff`` span. Says the producer's account on an earlier line: busy +
triplets + handoff + gc + other + unnamed (no span, inside a thread's life) +
absent (no producer thread alive) = 100."""

from lib import host_spans, spans


def read(ctx):
    s = spans.load(ctx)
    if not s or not spans.named(s["host"], "handoff"):
        return None
    account = host_spans.producer_account(s["host"])
    if not account:
        return None
    ctx["say"](f"producer account, % of the window x {account['threads']} thread(s): "
               + ", ".join(f"{k} {100.0 * account[k] / account['slot']:.2f}"
                           for k in host_spans.ACCOUNT))
    return 100.0 * spans.self_time_of(s["host"], ("handoff",)) / account["slot"]
