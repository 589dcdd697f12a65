"""Kernels: the least time the chip could take for the window's filter
networks (``benchmark/ops/<arch>.py::filter`` over the REAL edges, peaks from
``benchmark/peaks.json``) over the device time under the scope ``filter``."""

from lib import scope_time


def read(ctx):
    ms = scope_time.scope_ms(ctx, "filter")
    if ms is None or not hasattr(ctx["ops"], "filter"):
        return None
    return scope_time.roofline_share(
        ctx, ms, ctx["ops"].filter(ctx["config"], *scope_time.real_sizes(ctx)))
