"""Host input pipeline: median, over the window's ``dispatch`` spans, of the
time from the end of the batch's ``transfer`` (the newest of that ``batch``
index that ended before the call; ``collate``'s end where the loader does not
transfer) to the start of its ``dispatch``: how long a finished batch waited
for the loop. About ``stage``'s length where the loader sets the pace. None
where ``transfer`` spans carry no ``batch``. Says the quartiles, and the share
of ``dataload`` spans that found no finished batch (``ready`` 0), on an
earlier line."""

from lib import host_spans, spans


def read(ctx):
    s = spans.load(ctx)
    waits = host_spans.leads(s["host"]) if s else None
    if not waits:
        return None
    ready = host_spans.found_ready(s["host"])
    q1, q2, q3 = host_spans.quartiles(waits)
    ctx["say"](f"lead of a finished batch over {len(waits)} steps, ms: quartiles "
               f"{1e-6 * q1:.3f} / {1e-6 * q2:.3f} / {1e-6 * q3:.3f}, max {1e-6 * max(waits):.3f}; "
               + (f"{100.0 * sum(1 for r in ready if r == 0) / len(ready):.1f}% of {len(ready)} "
                  f"dataload spans found nothing ready" if ready else "no dataload span says ready"))
    return 1e-6 * host_spans.median_or_nan(waits)
