"""The readers of the host threads' account (``lib/host_spans.py`` and the
seven metrics on it) on a dict small enough to work every value out by hand:
two epochs of two batches, one producer thread an epoch as the one-thread
prefetcher starts them, times written in us and scaled to the trace's ns."""

import pytest

from lib import host_spans, spans
from lib.cells import load_module

READERS = ("collate_certify_share", "transfer_gb_per_s", "loader_handoff_share",
           "loader_lead_ms", "gc_pause_share", "loop_release_ms", "host_unnamed_share")
US = 1000  # ns


def ev(start, end, span, **args):
    return (start * US, end * US, span, args)


def recorded():
    loop = [
        ev(0, 400, "train"),
        ev(0, 100, "dataload", batch=0, ready=0), ev(100, 110, "stage", batch=0),
        ev(110, 130, "dispatch", batch=0), ev(130, 140, "release", batch=0),
        ev(140, 150, "backpressure", batch=0),
        ev(150, 160, "dataload", batch=1, ready=1), ev(160, 170, "stage", batch=1),
        ev(170, 190, "dispatch", batch=1), ev(190, 194, "release", batch=1),
        ev(194, 200, "backpressure", batch=1),
        ev(210, 400, "drain"),                     # 200..210 under train alone
        ev(400, 420, "reduce"),
        ev(500, 1000, "train"),
        ev(500, 600, "dataload", batch=0, ready=0), ev(600, 610, "stage", batch=0),
        ev(610, 630, "dispatch", batch=0), ev(630, 636, "release", batch=0),
        ev(636, 640, "backpressure", batch=0),
        ev(640, 650, "dataload", batch=1, ready=1), ev(650, 660, "stage", batch=1),
        ev(660, 700, "dispatch", batch=1), ev(700, 702, "release", batch=1),
        ev(720, 1000, "drain"),                    # 702..720 under train alone
    ]
    first = [  # the first epoch's producer: a hole 96..100, a collection inside collate
        ev(5, 65, "collate", batch=0, fetch_us=5, fill_us=20, certify_us=25),
        ev(65, 95, "transfer", batch=0, leaves=3, bytes=3000),
        ev(95, 96, "handoff", batch=0),
        ev(100, 150, "collate", batch=1, fetch_us=4, fill_us=16, certify_us=20),
        ev(104, 110, "triplets", edges=8, triplets=20),
        ev(120, 124, "gc", generation=2, collected=7),
        ev(150, 160, "transfer", batch=1, leaves=3, bytes=1000),
        ev(160, 162, "handoff", batch=1), ev(162, 163, "handoff"),
    ]
    second = [  # the second epoch's: the same batch indices again; a long wait for a slot
        ev(505, 565, "collate", batch=0, fetch_us=5, fill_us=20, certify_us=25),
        ev(565, 595, "transfer", batch=0, leaves=3, bytes=3000),
        ev(595, 596, "handoff", batch=0),
        ev(596, 646, "collate", batch=1, fetch_us=4, fill_us=16, certify_us=20),
        ev(646, 656, "transfer", batch=1, leaves=3, bytes=1000),
        ev(656, 700, "handoff", batch=1), ev(700, 701, "handoff"),
    ]
    return {"loop#0": sorted(loop, key=lambda e: (e[0], -e[1])),
            "worker#1": sorted(first, key=lambda e: (e[0], -e[1])), "worker#2": second}


def context(host, said=None, watched=True):
    return {"say": (said if said is not None else []).append, "_gc_watched": watched,
            "_spans": {"host": host, "scopes": {}}}


def read_all(ctx):
    return {name: load_module("metrics", name).read(ctx) for name in READERS}


# By hand, in us. Window 0..1000, one producer thread at a time. Collate: 60 + 50
# + 60 + 50 = 220 long, certified 25 + 20 + 25 + 20 = 90. Transfers: 8,000 B in 30 +
# 10 + 30 + 10 = 80 us. Handoff: 1 + 2 + 1 and 1 + 44 + 1 = 50. Producer busy (collate
# and transfer self time): 60 + 30 + (50 - 6 triplets - 4 gc) + 10 = 140 and 150;
# triplets 6, gc 4: 350 named. The first producer lives 5..163 with the hole 96..100,
# the second 505..701: 4 unnamed, 646 of the slot's 1,000 with no producer alive. Leads: 110 - 95,
# 170 - 160, 610 - 595 (NOT 610 - 95), 660 - 656 = 15, 10, 15, 4. Releases 10, 4, 6, 2.
# The loop under train alone: 200..210 and 702..720.
BY_HAND = {
    "collate_certify_share": 100.0 * 90 / 220,
    "transfer_gb_per_s": 8000 / 80e3,
    "loader_handoff_share": 100.0 * 50 / 1000,
    "loader_lead_ms": 12.5e-3,
    "gc_pause_share": 100.0 * 4 / 1000,
    "loop_release_ms": 5e-3,
    "host_unnamed_share": 100.0 * (28 + 4) / 2000,
}


def test_every_reader_on_the_hand_made_run():
    said = []
    values = read_all(context(recorded(), said))
    for name, expected in BY_HAND.items():
        assert values[name] == pytest.approx(expected, rel=1e-12), name
    text = "\n".join(said)
    assert ("collate phases over 4 batches, median us (% of collate's time): fetch 4 (8.2%), "
            "fill 18 (32.7%), triplets 0 (2.7%), certify 22 (40.9%), rest 10 (15.5%); "
            "collate 55") in text
    assert "3.0 leaves a batch, median 2000 B in 0.020 ms, 6.7 us a leaf" in text
    assert ("producer account, % of the window x 1 thread(s): busy 29.00, triplets 0.60, "
            "handoff 5.00, gc 0.40, other 0.00, unnamed 0.40, absent 64.60") in text
    assert host_spans.quartiles(host_spans.leads(recorded())) == (5.5 * US, 12.5 * US, 15 * US)
    assert "max 0.015; 50.0% of 4 dataload spans found nothing ready" in text
    assert ("collector pauses: 1 of generation 2; median 0.004 ms, longest 0.004 ms "
            "(generation 2, 7 collected) inside collate on worker#1") in text
    assert "release over 4 steps: max 0.010 ms; % of the window: release 2.20" in text
    assert ("loop thread under train alone 2.80; 1 producer thread(s) under no span 0.40, "
            "not alive 64.60") in text


def test_the_phases_and_the_account_add_up():
    host = recorded()
    for row in host_spans.collate_phases(host):
        assert sum(row[k] for k in (*host_spans.PHASES, "rest")) == pytest.approx(row["total"])
    assert [r["triplets"] for r in host_spans.collate_phases(host)] == [0, 6, 0, 0]
    account = host_spans.producer_account(host)
    assert account["slot"] == 1000 * US and account["threads"] == 1
    assert sum(account[k] for k in host_spans.ACCOUNT) == account["slot"]
    assert host_spans.lives(host["worker#1"]) == [(5 * US, 163 * US)]
    # the accepted reader's own number is the account's busy term
    busy = load_module("metrics", "loader_busy_share").read(context(host))
    assert busy == pytest.approx(100.0 * account["busy"] / account["slot"])


def test_the_lead_takes_the_newest_transfer_before_the_dispatch():
    assert sorted(host_spans.leads(recorded())) == [4 * US, 10 * US, 15 * US, 15 * US]
    # a transfer of the same index that ends AFTER the dispatch began is the next epoch's
    host = {"loop#0": [ev(0, 100, "train"), ev(50, 60, "dispatch", batch=0)],
            "worker#1": [ev(10, 20, "transfer", batch=0), ev(55, 70, "transfer", batch=0)]}
    assert host_spans.leads(host) == [30 * US]


def test_a_run_with_no_collection_reads_zero_and_a_program_with_no_hook_nothing():
    host = {t: [e for e in events if e[2] != "gc"] for t, events in recorded().items()}
    said = []
    assert load_module("metrics", "gc_pause_share").read(context(host, said)) == 0.0
    assert said == ["collector pauses: none"]
    assert load_module("metrics", "gc_pause_share").read(context(host, watched=False)) is None


def test_a_loader_that_does_not_transfer():
    """The plain loader, collating on the loop's thread inside ``dataload``:
    the lead runs from ``collate``'s end, and no thread is a producer."""
    host = {"loop#0": [
        ev(0, 100, "train"),
        ev(0, 50, "dataload", batch=0), ev(2, 48, "collate", batch=0, fetch_us=1, fill_us=30,
                                           certify_us=10),
        ev(50, 55, "stage", batch=0), ev(60, 70, "dispatch", batch=0),
        ev(70, 71, "release", batch=0), ev(80, 100, "drain")]}
    values = read_all(context(host))
    assert values["loader_lead_ms"] == pytest.approx(12e-3)
    assert values["transfer_gb_per_s"] is None and values["loader_handoff_share"] is None
    assert values["collate_certify_share"] == pytest.approx(100.0 * 10 / 46)
    # under train alone: 55..60, 71..80; no producer thread in the denominator
    assert values["host_unnamed_share"] == pytest.approx(100.0 * 14 / 100)


def test_unnamed_time_counts_a_hole_between_two_spans_of_a_producer():
    host = {"loop#0": [ev(0, 100, "train"), ev(10, 20, "dispatch", batch=0)],
            "worker#1": [ev(0, 40, "collate", batch=0), ev(60, 100, "collate", batch=1)]}
    said = []
    value = load_module("metrics", "host_unnamed_share").read(context(host, said))
    assert value == pytest.approx(100.0 * (90 + 20) / 200)
    assert said == ["unnamed host time, % of the window: loop thread under train alone 90.00; "
                    "1 producer thread(s) under no span 20.00, not alive 0.00"]
    # two epochs' workers on one line of the profile: the time between them is no hole
    again = [ev(200, 240, "collate", batch=0), ev(240, 241, "handoff", batch=0), ev(241, 242, "handoff")]
    line = host["worker#1"] + [ev(100, 101, "handoff")] + again
    assert host_spans.lives(line) == [(0, 101 * US), (200 * US, 242 * US)]


def test_a_program_without_the_new_spans_and_arguments_reads_nothing():
    """The commit before these spans: ``collate`` and ``transfer`` as they were,
    no ``handoff``, ``release`` or ``gc``, no hook. Six readers return None;
    the unnamed share is the one that reads what is there (more of it)."""
    old = {"collate": ("batch", "real_edges", "edge_slots"), "dataload": ("batch",)}
    host = {}
    for thread, events in recorded().items():
        host[thread] = [
            (a, b, span, {k: v for k, v in args.items() if k in old.get(span, ("batch",))}
             if span != "transfer" else {})
            for a, b, span, args in events if span not in ("handoff", "release", "gc")]
    values = read_all(context(host, watched=False))
    assert [n for n, v in values.items() if v is not None] == ["host_unnamed_share"]
    assert values["host_unnamed_share"] > BY_HAND["host_unnamed_share"]


def test_every_reader_gives_none_on_an_empty_trace():
    for found in (None, {"host": {}, "scopes": {}}):
        ctx = {"say": lambda msg: None, "_spans": found, "_gc_watched": True}
        assert read_all(ctx) == dict.fromkeys(READERS)


def test_the_hook_is_looked_for_in_the_program():
    import gc

    from hydragnn_tpu.utils import tracer

    before = tracer.gc_watched()
    try:
        tracer.watch_gc()
        assert host_spans.gc_watched()
        gc.callbacks.remove(tracer._on_gc)
        assert not host_spans.gc_watched()
    finally:
        if before:
            tracer.watch_gc()
