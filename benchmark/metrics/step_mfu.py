"""Kernels: the whole step's share of the chip's compute peak. Needed FLOP of
the window's steps (``benchmark/ops/<arch>.py`` over the REAL atoms and edges
of its batches) / the published bf16 peak (``benchmark/peaks.json``) / the
time the devices were busy. It stands beside the rooflines of single scopes:
a change that takes a kernel off the path leaves that kernel's roofline
silent, and this share still bounds what the step can claim. The cells so
far are bytes-bound at fp32 ``highest`` (``step_roofline_share`` says which
bound applies), so it reads a few per cent."""


def read(ctx):
    t, peaks = ctx["trace"], ctx["peaks"]
    if not t or not t.get("busy_s") or not peaks:
        return None
    nodes = sum(c[1] for c in ctx["collated"])
    edges = sum(c[2] for c in ctx["collated"])
    graphs = sum(c[3] for c in ctx["collated"])
    flop, _ = ctx["ops"].needed(ctx["config"], nodes, edges, graphs)
    return 100.0 * flop / peaks["bf16_flops_per_s"] / ctx["chips"] / t["busy_s"]
