"""Kernels: the least time the chip could take for the window's
gather-multiply-sums (``benchmark/ops/<arch>.py::aggregate`` over the REAL
atoms and edges: filter rows and gathered sender rows read once, node rows
written, whatever implements it; peaks from ``benchmark/peaks.json``) over the
device time under the scope ``aggregate``."""

from lib import scope_time


def read(ctx):
    ms = scope_time.scope_ms(ctx, "aggregate")
    if ms is None or not hasattr(ctx["ops"], "aggregate"):
        return None
    return scope_time.roofline_share(
        ctx, ms, ctx["ops"].aggregate(ctx["config"], *scope_time.real_sizes(ctx)))
