"""Host input pipeline: 1 - sum(real_edges) / sum(edge_slots) over the
``collate`` spans of the trace; the counts ride on the spans as arguments,
taken where the loader pads. The inside twin of ``padded_edge_share``."""

from lib import spans


def read(ctx):
    s = spans.load(ctx)
    args = [e[3] for e in spans.named(s["host"], "collate")] if s else []
    slots = sum(a.get("edge_slots", 0) for a in args)
    return 100.0 * (1.0 - sum(a.get("real_edges", 0) for a in args) / slots) if slots else None
