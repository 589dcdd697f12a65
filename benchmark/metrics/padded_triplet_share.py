"""Host input pipeline: the share of triplet slots in the window's batches
that held no real triplet: 1 - sum(real_triplets) / sum(triplet_slots) over
the ``collate`` spans of the trace (``graphs/batching.py`` counts both where
it pads). None where the spans carry no triplet counts."""

from lib import triplet_spans


def read(ctx):
    real, slots, _ = triplet_spans.counts(ctx)
    return 100.0 * (1.0 - real / slots) if slots else None
