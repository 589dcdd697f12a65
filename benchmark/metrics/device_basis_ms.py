"""Step program: device self time a step of the operations scoped under
``geometry`` (edge vectors, triplet angles) and ``basis`` (rbf, the radial
part of sbf on the edges, its gather and the Legendre part on the triplets):
what a model call computes once for all its layers. Any pass, mean over the
chips."""

from lib import scope_time


def read(ctx):
    parts = [scope_time.scope_ms(ctx, name) for name in ("geometry", "basis")]
    found = [p for p in parts if p is not None]
    return sum(found) if found else None
