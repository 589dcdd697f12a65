"""Host input pipeline: bytes the ``transfer`` spans moved to the device over
the time they took (``PrefetchLoader._transfer``: one ``device_put`` a leaf),
in GB/s. None where no ``transfer`` span says its bytes. Says leaves a batch,
median bytes, median ms and us a leaf on an earlier line: a cost that stays
the same while the bytes change is paid by the call, not by the byte."""

from lib import host_spans, spans


def read(ctx):
    s = spans.load(ctx)
    moved = host_spans.transfers(s["host"]) if s else []
    ns = sum(t[2] for t in moved)
    if not ns:
        return None
    leaves = sum(t[1] for t in moved)
    ctx["say"](f"transfer over {len(moved)} batches: {leaves / len(moved):.1f} leaves a batch, "
               f"median {host_spans.median_or_nan(t[0] for t in moved):.0f} B in "
               f"{1e-6 * host_spans.median_or_nan(t[2] for t in moved):.3f} ms, "
               + (f"{1e-3 * ns / leaves:.1f} us a leaf" if leaves else "no leaf count"))
    return sum(t[0] for t in moved) / ns  # B/ns = GB/s
