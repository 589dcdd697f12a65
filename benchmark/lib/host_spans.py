"""The host threads' account, from the program's spans of a traced run: what
``collate`` spent by phase, what ``transfer`` moved, how long the producer
waited for a queue slot (``handoff``) and a finished batch for the loop, the
collector's pauses (``gc``), the loop's drop of the donated state
(``release``), and the time no span names. Shared by the seven metrics that
read them (``collate_certify_share``, ``transfer_gb_per_s``,
``loader_handoff_share``, ``loader_lead_ms``, ``gc_pause_share``,
``loop_release_ms``, ``host_unnamed_share``).

Pure functions of ``lib/spans.py::load``'s ``host`` dict
(``{thread: [(start_ns, end_ns, span, args), ...]}``), checked in
``benchmark/tests/test_host_spans.py``. A program that does not write a span
or an argument (a commit before the one that added it) makes its reader
return None; nothing here raises for it.
"""

from __future__ import annotations

import bisect
import statistics

from lib import spans
from lib import trace as trace_lib

BUSY = ("collate", "transfer")  # what ``loader_busy_share`` calls the loader's work
PHASES = ("fetch", "fill", "triplets", "certify")
ACCOUNT = ("busy", "triplets", "handoff", "gc", "other", "unnamed", "absent")  # of a producer slot


def window(host: dict) -> tuple[float, float] | None:
    """First ``train`` span's start and last one's end (``spans.window_ns``'s)."""
    trains = spans.named(host, "train")
    return (min(e[0] for e in trains), max(e[1] for e in trains)) if trains else None


def producer_events(host: dict) -> dict:
    """The threads other than the loop's, with their spans."""
    main = spans.loop_thread(host)
    return {t: ev for t, ev in host.items() if t != main}


def median_or_nan(values) -> float:
    values = list(values)
    return statistics.median(values) if values else float("nan")


def quartiles(values) -> tuple:
    values = sorted(values)
    return tuple(statistics.quantiles(values, n=4)) if len(values) > 1 else (values[0],) * 3


# -- collate's phases ------------------------------------------------------------

def collate_phases(host: dict) -> list:
    """One dict a ``collate`` span that carries its phases, in us: ``total``
    (the span), ``fetch`` / ``fill`` / ``certify`` (its arguments),
    ``triplets`` (the ``triplets`` spans inside it, on its thread) and
    ``rest`` (what the four leave); ``at`` is the span's start, ns."""
    out = []
    for events in host.values():
        built = [e for e in events if e[2] == "triplets"]
        for start, end, span, args in events:
            if span != "collate" or "certify_us" not in args:
                continue
            row = {"at": start, "total": (end - start) * 1e-3, "fetch": args.get("fetch_us", 0),
                   "fill": args.get("fill_us", 0), "certify": args["certify_us"],
                   "triplets": sum(t[1] - t[0] for t in built
                                   if start <= t[0] and t[1] <= end) * 1e-3}
            row["rest"] = row["total"] - sum(row[k] for k in PHASES)
            out.append(row)
    return out


# -- transfer ----------------------------------------------------------------------

def transfers(host: dict) -> list:
    """``(bytes, leaves, ns)`` of every ``transfer`` span that says what it moved."""
    return [(e[3]["bytes"], e[3].get("leaves", 0), e[1] - e[0])
            for e in spans.named(host, "transfer") if "bytes" in e[3]]


# -- the producer's account ---------------------------------------------------------

def lives(events: list) -> list:
    """``[(start, end), ...]`` of one producer thread's line: from a life's
    first span to its last. ``background_iter``'s worker ends its life with
    the ``handoff`` of the end marker (the one with no ``batch``), and the
    next epoch's worker may come back on the same line; a line with no such
    marker (pool workers; a program that writes no ``handoff``) is one life."""
    out, start, end = [], None, None
    for a, b, span, args in sorted(events, key=lambda e: e[0]):
        start, end = (a, b) if start is None else (start, max(end, b))
        if span == "handoff" and "batch" not in args:
            out.append((start, end))
            start = None
    if start is not None:
        out.append((start, end))
    return out


def producer_account(host: dict) -> dict | None:
    """``{"threads": producer threads side by side (at least 1), "slot": ns of
    window x threads, "busy" / "triplets" / "handoff" / "gc" / "other" /
    "unnamed" / "absent": ns}``: every instant of the window on the threads
    other than the loop's, by the innermost span open there. ``unnamed`` is
    what no span covers inside a thread's life (``lives``), ``absent`` the
    rest of the slot: no producer thread exists (the one-thread prefetcher's
    worker ends with its epoch's last batch, while the loop still drains)."""
    win = window(host)
    if win is None:
        return None
    threads = max(1, spans.producers(host))
    out = {"threads": threads, "slot": (win[1] - win[0]) * threads, "busy": 0.0,
           "triplets": 0.0, "handoff": 0.0, "gc": 0.0, "other": 0.0}
    alive = 0.0
    for events in producer_events(host).values():
        alive += sum(max(0.0, min(b, win[1]) - max(a, win[0])) for a, b in lives(events))
        for a, b, span in spans.innermost(events):
            a, b = max(a, win[0]), min(b, win[1])
            if b > a:
                key = "busy" if span in BUSY else span if span in out else "other"
                out[key] += b - a
    out["unnamed"] = alive - sum(out[k] for k in ACCOUNT[:5])
    out["absent"] = out["slot"] - alive
    return out


def loop_train_self(host: dict) -> float:
    """ns of the loop thread under ``train`` and no other span."""
    main = spans.loop_thread(host)
    if main is None:
        return 0.0
    return sum(b - a for a, b, span in spans.innermost(host[main]) if span == "train")


# -- how long a finished batch waited for the loop ------------------------------------

def leads(host: dict) -> list | None:
    """ns from the end of a batch's ``transfer`` to the start of its
    ``dispatch``, a ``dispatch`` span of the window: the newest ``transfer``
    of the same ``batch`` that ended before it (the index recurs every epoch).
    ``collate``'s end where the run holds no ``transfer`` span at all (a
    loader that does not transfer). None where ``transfer`` spans carry no
    ``batch``: nothing pairs them."""
    finished = spans.named(host, "transfer")
    if finished and not any("batch" in e[3] for e in finished):
        return None
    ends = {}
    for e in finished or spans.named(host, "collate"):
        if "batch" in e[3]:
            ends.setdefault(e[3]["batch"], []).append(e[1])
    for stamps in ends.values():
        stamps.sort()
    out = []
    for e in spans.named(host, "dispatch"):
        stamps = ends.get(e[3].get("batch"), [])
        at = bisect.bisect_right(stamps, e[0])
        if at:
            out.append(e[0] - stamps[at - 1])
    return out


def found_ready(host: dict) -> list:
    """What each ``dataload`` span found finished when it asked (``ready``)."""
    return [e[3]["ready"] for e in spans.named(host, "dataload") if "ready" in e[3]]


# -- the collector -------------------------------------------------------------------

def gc_watched() -> bool:
    """Whether the program's collector hook is registered in this process
    (False for a program that has none)."""
    from hydragnn_tpu.utils import tracer

    return bool(getattr(tracer, "gc_watched", lambda: False)())


def gc_pauses(host: dict) -> list:
    """``(start, end, generation, collected, thread, the span it sat in)`` of
    every ``gc`` span, longest first."""
    out = []
    for thread, events in host.items():
        for start, end, span, args in events:
            if span != "gc":
                continue
            around = [e for e in events if e[2] != "gc" and e[0] <= start and end <= e[1]]
            inside = max(around, key=lambda e: e[0])[2] if around else "none"
            out.append((start, end, args.get("generation"), args.get("collected"), thread, inside))
    return sorted(out, key=lambda p: p[0] - p[1])


def union_ns(intervals) -> float:
    return trace_lib.length(trace_lib.union(list(intervals)))
