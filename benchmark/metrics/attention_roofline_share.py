"""Kernels: the least time the chip could take for the window's attention
(``benchmark/ops/<arch>.py::attention`` over the REAL atoms and the REAL
query x key pairs, peaks from ``benchmark/peaks.json``) over the device time
under the scope ``attention``. Dense blocks of the largest structure's width
run several times the real pairs: that shows here, as it should."""

from lib import attention_spans, scope_time


def read(ctx):
    ms = scope_time.scope_ms(ctx, "attention")
    pairs = attention_spans.window_pairs(ctx)
    if ms is None or pairs is None or not hasattr(ctx["ops"], "attention"):
        return None
    nodes, _ = scope_time.real_sizes(ctx)
    return scope_time.roofline_share(ctx, ms, ctx["ops"].attention(ctx["config"], nodes, pairs))
