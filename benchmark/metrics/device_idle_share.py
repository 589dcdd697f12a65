"""Device: 1 - device-busy time / traced window (busy averaged over chips)."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t.get("busy_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / ctx["window_s"])
