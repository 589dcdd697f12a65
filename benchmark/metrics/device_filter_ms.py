"""Step program: device self time a step of the operations scoped under a
SchNet conv layer's ``filter`` (``filter1``, shifted softplus, ``filter2``,
the cutoff window: the dense filter network on the E edge rows), any pass,
mean over the chips."""

from lib import scope_time


def read(ctx):
    return scope_time.scope_ms(ctx, "filter")
