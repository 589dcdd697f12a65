"""Step program: device self time a step of the operations scoped under a GPS
layer's ``local`` (the architecture's own conv: EGNN's edge, coordinate and
node networks, its row reads and sums), any pass, every layer, mean over the
chips."""

from lib import scope_time


def read(ctx):
    return scope_time.scope_ms(ctx, "local")
