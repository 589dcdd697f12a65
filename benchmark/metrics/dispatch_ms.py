"""Staging and dispatch: median host time inside the step call, from the
benchmark's wrapper round the step handed to ``train_epoch``."""

import statistics


def read(ctx):
    return 1e3 * statistics.median(ctx["dispatch_s"]) if ctx["dispatch_s"] else None
