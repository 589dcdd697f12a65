"""Staging and dispatch: what the host threads' account still misses: time
of the loop thread under ``train`` and no other span, plus time of the
producer threads inside the window under no span while they live, over
window x (1 + producer threads). Says both terms on an earlier line, and
beside them the share of the producer's slot in which no producer thread
exists (``lib/host_spans.py::lives``): named, so not counted."""

from lib import host_spans, spans


def read(ctx):
    s = spans.load(ctx)
    account = host_spans.producer_account(s["host"]) if s else None
    if not account:
        return None
    window = spans.window_ns(s["host"])
    producers = spans.producers(s["host"])
    loop = host_spans.loop_train_self(s["host"])
    unnamed = account["unnamed"] if producers else 0.0
    ctx["say"](f"unnamed host time, % of the window: loop thread under train alone "
               f"{100.0 * loop / window:.2f}; {producers} producer thread(s) under no span "
               + (f"{100.0 * unnamed / (window * producers):.2f}, not alive "
                  f"{100.0 * account['absent'] / (window * producers):.2f}" if producers else "-"))
    return 100.0 * (loop + unnamed) / (window * (1 + producers))
