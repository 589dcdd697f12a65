"""Attention counts of a traced run, from the program's ``collate`` spans
(``graphs/batching.py`` notes ``attention_slots`` and ``attention_pairs`` on
them where the samples carry Laplacian encodings: the query x key slots a GPS
stack's per-graph attention runs at the width collate certified, and the real
pairs among them, per head and layer). Shared by the attention metrics."""

from __future__ import annotations

from lib import spans


def counts(ctx) -> tuple[int, int, int]:
    """(real pairs, slots, real edges) summed over the ``collate`` spans that
    carry attention counts; zeros where none does."""
    def total():
        s = spans.load(ctx)
        args = [e[3] for e in spans.named(s["host"], "collate")] if s else []
        args = [a for a in args if "attention_pairs" in a and "attention_slots" in a]
        return (sum(a["attention_pairs"] for a in args), sum(a["attention_slots"] for a in args),
                sum(a.get("real_edges", 0) for a in args))

    return spans._kept(ctx, "_attention_counts", total)


def window_pairs(ctx) -> float | None:
    """Real attention pairs of the window's steps: the spans' pairs an edge
    (the producer collates ahead of the loop, so its spans are not the
    window's steps one for one) times the real edges of the window's batches."""
    pairs, _, edges = counts(ctx)
    if not pairs or not edges:
        return None
    return pairs / edges * sum(c[2] for c in ctx["collated"])
