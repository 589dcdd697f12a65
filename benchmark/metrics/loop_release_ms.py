"""Staging and dispatch: median length of the ``release`` span
(``train/loop.py``: the rebinding ``state, metrics = stepped`` alone, where
the loop drops the state the step donated). None where the run holds none.
Says its share of the window beside the terms of ``loop_device_wait_share``
(``backpressure``, ``drain``, ``dispatch``), which leaves it out, on an
earlier line."""

from lib import host_spans, spans


def read(ctx):
    s = spans.load(ctx)
    released = spans.named(s["host"], "release") if s else []
    window = spans.window_ns(s["host"]) if released else 0.0
    if not window:
        return None
    share = {name: 100.0 * sum(e[1] - e[0] for e in spans.named(s["host"], name)) / window
             for name in ("release", "backpressure", "drain", "dispatch")}
    ctx["say"](f"release over {len(released)} steps: max "
               f"{1e-6 * max(e[1] - e[0] for e in released):.3f} ms; % of the window: "
               + ", ".join(f"{k} {v:.2f}" for k, v in share.items()))
    return 1e-6 * host_spans.median_or_nan(e[1] - e[0] for e in released)
