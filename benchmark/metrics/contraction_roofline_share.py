"""Kernels: the same for the symmetric contraction
(``benchmark/ops/<arch>.py::contraction``) over the device time under the
scope ``product_basis/contraction``."""

from lib import scope_time


def read(ctx):
    ms = scope_time.scope_ms(ctx, "product_basis", "contraction")
    if ms is None or not hasattr(ctx["ops"], "contraction"):
        return None
    return scope_time.roofline_share(
        ctx, ms, ctx["ops"].contraction(ctx["config"], *scope_time.real_sizes(ctx)))
