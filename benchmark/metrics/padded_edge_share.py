"""Host input pipeline: the share of edge slots in the window's batches that
held no real edge: 1 - sum(real edges) / sum(padded edge slots), counted
where the loader collates (on the host, from sample sizes and the bucket)."""


def read(ctx):
    slots = sum(c[0][1] for c in ctx["collated"])
    real = sum(c[2] for c in ctx["collated"])
    return 100.0 * (1.0 - real / slots) if slots else None
