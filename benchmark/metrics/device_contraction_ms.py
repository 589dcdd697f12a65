"""Step program: device self time a step of the operations scoped under a
conv layer's ``product_basis/contraction`` (monomials of the A-basis, the
product with U, the per-element weights), any pass, mean over the chips."""

from lib import scope_time


def read(ctx):
    return scope_time.scope_ms(ctx, "product_basis", "contraction")
