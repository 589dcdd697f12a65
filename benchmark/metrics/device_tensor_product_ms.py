"""Step program: device self time a step of the operations scoped under a
conv layer's ``interaction/tensor_product`` (the gather of sender features,
the product with the edge harmonics and radial weights, the sum at the
receivers), any pass, mean over the chips."""

from lib import scope_time


def read(ctx):
    return scope_time.scope_ms(ctx, "interaction", "tensor_product")
