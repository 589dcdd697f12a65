"""Kernels: device time of Mosaic (``tpu_custom_call``) operations over
device-busy time, from the profiler's trace."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t.get("busy_s"):
        return None
    return 100.0 * t["mosaic_s"] / t["busy_s"]
