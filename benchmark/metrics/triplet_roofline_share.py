"""Kernels: the least time the chip could take for the window's triplet
exchanges (``benchmark/ops/<arch>.py::triplets`` over the REAL edges and
triplets, peaks from ``benchmark/peaks.json``) over the device time under
the scope ``interaction/triplets``. Real triplets of the window's steps: its
real edges (counted where the loader collates) x the triplets an edge that
the trace's ``collate`` spans carry."""

from lib import scope_time, triplet_spans


def read(ctx):
    ms = scope_time.scope_ms(ctx, "interaction", "triplets")
    real, _, span_edges = triplet_spans.counts(ctx)
    if ms is None or not span_edges or not hasattr(ctx["ops"], "triplets"):
        return None
    nodes, edges = scope_time.real_sizes(ctx)
    return scope_time.roofline_share(
        ctx, ms, ctx["ops"].triplets(ctx["config"], nodes, edges, edges * real / span_edges))
