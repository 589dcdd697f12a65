"""Step program: device self time a step of the operations scoped under a
conv layer's ``interaction/triplets`` (``lin_down``, the ``idx_kj`` gather,
the product with the projected spherical basis, the sum onto ``idx_ji``,
``lin_up``), any pass, mean over the chips."""

from lib import scope_time


def read(ctx):
    return scope_time.scope_ms(ctx, "interaction", "triplets")
