"""Step program: device time per step of the operations whose scope lies
under ``HydraModel.conv_block`` (flax's module path in ``op_name``), any
pass, mean over the chips."""

from lib import spans


def read(ctx):
    d = spans.device_by_scope(ctx)
    return d["conv"] if d else None
