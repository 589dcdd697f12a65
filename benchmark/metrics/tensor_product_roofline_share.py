"""Kernels: the least time the chip could take for the window's tensor
products (``benchmark/ops/<arch>.py::tensor_product`` over the REAL atoms and
edges, peaks from ``benchmark/peaks.json``) over the device time under the
scope ``interaction/tensor_product``."""

from lib import scope_time


def read(ctx):
    ms = scope_time.scope_ms(ctx, "interaction", "tensor_product")
    if ms is None or not hasattr(ctx["ops"], "tensor_product"):
        return None
    return scope_time.roofline_share(
        ctx, ms, ctx["ops"].tensor_product(ctx["config"], *scope_time.real_sizes(ctx)))
