"""Step program: device self time a step of the operations scoped under a GPS
layer's ``attention`` (the q / k / v / out projections, the scatter into dense
per-graph blocks, scores, softmax, weighted sum, gather back), any pass, every
layer (one name under a scanned stack), mean over the chips."""

from lib import scope_time


def read(ctx):
    return scope_time.scope_ms(ctx, "attention")
