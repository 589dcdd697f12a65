"""Step program: device self time a step of the operations scoped under a
SchNet conv layer's ``aggregate`` (``lin1`` and the gather-multiply-sum: the
Mosaic kernel ``fused_gather_scatter`` where the batch's certificate holds,
else XLA's gather, multiply and segment_sum), any pass, mean over the chips."""

from lib import scope_time


def read(ctx):
    return scope_time.scope_ms(ctx, "aggregate")
