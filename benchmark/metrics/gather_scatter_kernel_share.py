"""Kernels: the share of the traced window's steps whose gather-multiply-sum
ran the Mosaic kernel ``fused_gather_scatter``, read from what ran: a step is
one execution of the step program on the first chip (``XLA Modules``), and it
ran the kernel when a ``tpu_custom_call`` scoped under a conv layer's
``aggregate`` started inside it. The other steps took XLA's gather, multiply
and ``segment_sum``. Beside it on the log: the steps whose batch carried the
layout certificate ``gs_fits`` (``BatchMeta``, kept where the loader collates),
which is what the program routes by. None where the trace holds no step
program; 0 where it holds steps and no such call."""

import bisect

from lib import spans, trace


def read(ctx):
    loaded, events = spans.load(ctx), ctx.get("events")
    if not loaded or not events or not events.get("devices") or not events.get("modules"):
        return None
    chip = sorted(events["modules"])[0]
    steps = [(s, s + d) for n, s, d in events["modules"][chip]
             if any(f in n for f in spans.STEP_FUNCTIONS)]
    if not steps:
        return None
    kernel, starts = {}, []
    for text, start, _ in events["devices"].get(chip, ()):
        if text not in kernel:
            scope = spans.scope_of(text, loaded["scopes"]) if trace.MOSAIC in text else None
            kernel[text] = bool(scope and "aggregate" in scope["path"])
        if kernel[text]:
            starts.append(start)
    starts.sort()
    ran = sum(1 for a, b in steps if bisect.bisect_left(starts, b) > bisect.bisect_left(starts, a))
    certified = [c[4].gs_fits for c in ctx["collated"] if len(c) > 4 and hasattr(c[4], "gs_fits")]
    ctx["say"](f"gather-multiply-sum: {ran} of {len(steps)} traced steps hold a Mosaic call under "
               f"aggregate ({len(starts)} calls); gs_fits held on {sum(map(bool, certified))} of "
               f"{len(certified)} collated batches")
    return 100.0 * ran / len(steps)
