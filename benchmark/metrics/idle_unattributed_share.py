"""Device: the share of the first chip's idle time that no program span other
than ``train`` covers. Prints the idle seconds by span: each device gap split
over the program's spans that overlap it, innermost span first, the loop's
thread before the loader's."""

from lib import spans

ORDER = (*spans.LOOP_SPANS, "collate", "transfer", "train", "none")


def read(ctx):
    idle = spans.idle_by_span(ctx)
    total = sum(idle.values()) if idle else 0.0
    if not total:
        return None
    names = [n for n in ORDER if n in idle] + sorted(set(idle) - set(ORDER))
    ctx["say"](f"device idle by program span, s of {total:.4f}: " + ", ".join(
        f"{'train self' if n == 'train' else n} {idle[n]:.4f}" for n in names))
    return 100.0 * (idle.get("train", 0.0) + idle.get("none", 0.0)) / total
