"""Kernels: the least time the chip could take to read the window's attention
logits and write its weights once a pass (``benchmark/ops/<arch>.py::softmax``
over the REAL query x key pairs: a bytes bound) over the device time under the
scope ``softmax`` (``ops/fused_softmax.py::fused_masked_softmax``, a Mosaic
call and its custom VJP, where the dense-block route runs; XLA's softmax on
the flat route)."""

from lib import attention_spans, scope_time


def read(ctx):
    ms = scope_time.scope_ms(ctx, "softmax")
    pairs = attention_spans.window_pairs(ctx)
    if ms is None or pairs is None or not hasattr(ctx["ops"], "softmax"):
        return None
    return scope_time.roofline_share(ctx, ms, ctx["ops"].softmax(ctx["config"], pairs))
