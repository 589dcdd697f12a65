"""Triplet counts of a traced run, from the program's ``collate`` spans
(``graphs/batching.py`` puts ``real_triplets`` and ``triplet_slots`` on them
where the bucket has a triplet dimension). Shared by the triplet metrics."""

from __future__ import annotations

from lib import spans


def counts(ctx) -> tuple[int, int, int]:
    """(real triplets, triplet slots, real edges) summed over the ``collate``
    spans that carry triplet counts; zeros where none does."""
    def total():
        s = spans.load(ctx)
        args = [e[3] for e in spans.named(s["host"], "collate")] if s else []
        args = [a for a in args if "real_triplets" in a and "triplet_slots" in a]
        return (sum(a["real_triplets"] for a in args), sum(a["triplet_slots"] for a in args),
                sum(a.get("real_edges", 0) for a in args))

    return spans._kept(ctx, "_triplet_counts", total)
