"""Host input pipeline: the share of the ``collate`` spans' time that is
certification: sum of their ``certify_us`` (``graphs/batching.py::_batch_meta``,
the layout certificates' O(E) host scans) over the sum of their durations.
None where no ``collate`` span carries its phases. Says the phase table on an
earlier line: median us and share of ``fetch``, ``fill``, ``triplets`` (the
child spans), ``certify`` and what they leave."""

from lib import host_spans, spans


def read(ctx):
    s = spans.load(ctx)
    rows = host_spans.collate_phases(s["host"]) if s else []
    total = sum(r["total"] for r in rows)
    if not total:
        return None
    ctx["say"](f"collate phases over {len(rows)} batches, median us (% of collate's time): "
               + ", ".join(
                   f"{k} {host_spans.median_or_nan(r[k] for r in rows):.0f} "
                   f"({100.0 * sum(r[k] for r in rows) / total:.1f}%)"
                   for k in (*host_spans.PHASES, "rest"))
               + f"; collate {host_spans.median_or_nan(r['total'] for r in rows):.0f}")
    return 100.0 * sum(r["certify"] for r in rows) / total
