"""Step program: device-busy time (union of the device's operation intervals
in the profiler's trace, averaged over the chips) per step of the window."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t.get("busy_s") or not ctx["steps"]:
        return None
    return 1e3 * t["busy_s"] / ctx["steps"]
