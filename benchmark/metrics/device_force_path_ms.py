"""Step program: device time per step of the operations whose pass tag holds
the inner ``transpose(jvp(``: the position gradient and the parameter
gradient through it, i.e. what energy-conserving forces cost over an
energy-only step."""

from lib import spans


def read(ctx):
    d = spans.device_by_scope(ctx)
    return d["force_path"] if d else None
