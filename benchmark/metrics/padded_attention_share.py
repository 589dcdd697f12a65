"""Host input pipeline: the share of the query x key slots the window's
batches run their per-graph attention on that are no real pair of atoms:
1 - sum(attention_pairs) / sum(attention_slots) over the ``collate`` spans of
the trace (``graphs/batching.py`` counts both where the samples carry
Laplacian encodings). None where the spans carry no attention counts."""

from lib import attention_spans


def read(ctx):
    pairs, slots, _ = attention_spans.counts(ctx)
    return 100.0 * (1.0 - pairs / slots) if slots else None
