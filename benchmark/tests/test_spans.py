"""The readers of the program's spans and scopes: the pure functions on
cases small enough to count by hand, and every reader on a recorded dict
(``tools/dump_spans.py``: the two sides of an epoch boundary of a traced
EGNN run on the chip; its 18 host spans are few enough to work the host
readers' values out by hand, below) and on an empty one."""

import json
import os

import pytest

from lib import spans
from lib.cells import load_module

READERS = (  # benchmark/metrics/<name>.py that read what lib/spans.py hands out
    "loop_dispatch_ms", "loop_device_wait_share", "epoch_turnaround_ms", "loader_busy_share",
    "loader_padded_edge_share", "idle_unattributed_share", "device_conv_ms",
    "device_force_path_ms", "device_unscoped_share", "step_program_lowerings",
    "step_program_compile_s",
)
CONV = "jit(train_step)/{}/HydraModel.encode/HydraModel.conv_block/graph_convs_2/edge_mlp/dense_0/dot_general"


def test_self_time():
    # 0..10 holds 2..5 (which holds 3..4) and 6..8
    assert spans.self_times([(0, 10), (2, 5), (3, 4), (6, 8)]) == [5, 2, 1, 2]
    assert spans.self_times([(6, 8), (0, 10)]) == [2, 8]  # in the order given
    assert spans.self_times([]) == []


def test_innermost_pieces():
    events = [(0, 100, "train", {}), (10, 30, "dataload", {}), (12, 20, "transfer", {}),
              (30, 35, "dispatch", {})]
    assert spans.innermost(events) == [
        (0, 10, "train"), (10, 12, "dataload"), (12, 20, "transfer"), (20, 30, "dataload"),
        (30, 35, "dispatch"), (35, 100, "train")]


def test_gap_attribution():
    loop = [(0, 100, "train", {}), (10, 30, "dataload", {}), (30, 35, "dispatch", {}),
            (35, 60, "backpressure", {})]
    worker = [(5, 25, "collate", {}), (62, 70, "transfer", {})]
    gaps = [(0, 8), (12, 20), (58, 66), (90, 95), (100, 104)]
    got = spans.attribute(gaps, [loop, worker])
    # 0..8: the worker's collate from 5, train before it; 12..20: the loop's dataload wins over
    # the worker's collate; 58..66: backpressure to 60, train to 62, the worker's transfer after;
    # 90..95: train alone; 100..104: nothing
    assert got == {"collate": 3, "dataload": 8, "backpressure": 2, "transfer": 4,
                   "train": 5 + 2 + 5, "none": 4}
    assert sum(got.values()) == sum(b - a for a, b in gaps)


def test_device_gaps_and_producers():
    assert spans.device_gaps([["a", 0, 4], ["b", 3, 3], ["c", 10, 2], ["d", 12, 1]]) == [(6, 10)]
    host = {"loop": [(0, 9, "dispatch", {})],
            "w1": [(0, 4, "collate", {}), (6, 9, "collate", {})], "w2": [(3, 5, "collate", {})],
            "w3": [(20, 30, "collate", {})]}
    assert spans.loop_thread(host) == "loop"
    assert spans.producers(host) == 2
    assert spans.self_time_of(host, ("collate",)) == 4 + 3 + 2 + 10


@pytest.mark.parametrize("tag,name,force", [
    ("jvp(jvp(HydraModel))", "forward", False),
    ("jvp(transpose(jvp(HydraModel)))", "forces", True),
    ("transpose(jvp(jvp(HydraModel)))", "grad.forward", False),
    ("transpose(jvp(transpose(jvp(HydraModel))))", "grad.forces", True),
    ("transpose(jvp(transpose(jvp(jvp(HydraModel)))))", "grad.forces", True),
])
def test_pass_tags(tag, name, force):
    scope = spans.parse_scope(CONV.format(tag) + ":")  # the TPU plane's tf_op ends in ":"
    assert scope["root"] == "HydraModel" and scope["op"] == "dot_general"
    assert scope["path"] == ("HydraModel.encode", "HydraModel.conv_block", "graph_convs_2",
                             "edge_mlp", "dense_0")
    assert spans.pass_name(scope["tag"]) == name
    assert spans.force_path(scope["tag"]) is force
    assert spans.module(scope) == "graph_convs_2/edge_mlp"


@pytest.mark.parametrize("op_name,module,pass_name", [
    ("jit(train_step)/jvp(jvp(HydraModel))/HydraModel.encode/HydraModel.conv_block/graph_convs_0"
     "/edge_mlp/jit(silu)/mul", "graph_convs_0/edge_mlp", "forward"),
    ("jit(train_step)/jvp(jvp(HydraModel))/HydraModel.encode/HydraModel.conv_block/graph_convs_1"
     "/jit(clip)/max", "graph_convs_1/(self)", "forward"),
    ("jit(train_step)/jvp(jvp(HydraModel))/HydraModel.encode/HydraModel.conv_block/graph_convs_1"
     "/fused_segment_sum/pallas_call", "graph_convs_1/fused_segment_sum", "forward"),
    ("jit(train_step)/transpose(jvp(jvp(HydraModel)))/HydraModel.encode/HydraModel.conv_block"
     "/jit(silu)/mul", "conv_block/(self)", "grad.forward"),
    ("jit(train_step)/transpose(jvp(jvp(HydraModel)))/HydraModel.decode/head0_branch-0/dense_2"
     "/dot_general", "heads", "grad.forward"),
    ("jit(train_step)/jvp(mlip_loss)/reduce_sum", "mlip_loss", "forward"),
    ("jit(train_step)/transpose(jvp(mlip_loss))/mul", "mlip_loss", "grad.forward"),
    ("jit(train_step)/optimizer/sub", "optimizer", "-"),
    ("jit(train_step)/jvp(transpose(jvp()))/neg", "unscoped", "forces"),
    ("jit(train_step)/mul", "unscoped", "-"),
])
def test_module_rows(op_name, module, pass_name):
    scope = spans.parse_scope(op_name)
    assert spans.module(scope) == module
    assert spans.pass_name(scope["tag"]) == pass_name


def test_names_the_compiler_made_have_no_scope():
    assert spans.parse_scope("gather:") is None
    assert spans.parse_scope("state.params['head']['kernel']") is None
    assert spans.parse_scope(None) is None
    assert spans.module(None) == "unscoped"
    mosaic = f'%x.1 = f32[8] custom-call(), {spans.MOSAIC}'
    unnamed = spans.parse_scope("jit(train_step)/jvp(jvp(HydraModel))/HydraModel.encode"
                                "/HydraModel.conv_block/graph_convs_0/pallas_call")
    assert spans.module(unnamed, mosaic) == "graph_convs_0/pallas_call"
    assert spans.scope_of('%f = f32[] add(), metadata={op_name="jit(f)/optimizer/add"}', {})[
        "path"] == ("optimizer",)


def recorded():
    path = os.path.join(os.path.dirname(__file__), "data", "recorded_spans.json")
    with open(path) as f:
        return json.load(f)


def context(rec, said):
    host = {t: [tuple(e) for e in ev] for t, ev in rec["host"].items()}
    return {"events": {"devices": rec["devices"]}, "steps": rec["steps"], "say": said.append,
            "_spans": {"host": host, "scopes": rec["scopes"]}}


# The recorded dict, worked out by hand (times in ns). Loop thread: ``train``
# 41873969..3132894090 and 3166927608..6102055288 (window 6060181319);
# ``dispatch`` 18308910 (batch 99), 2854320, 1815580, 1300530 long: the lower
# quartile is 1300530 + (1815580 - 1300530) / 4, five times that is 7.1 ms,
# so the first call was held and the median of the other three is 1815580;
# ``drain`` 2256232794..3132840750 (876607956), ``backpressure`` 17800,
# ``reduce`` 3132928290..3164783238; the new epoch's first ``dataload``
# 3166940158..3326841742, ``stage`` 3326893252..3327867952, ``dispatch`` from
# 3327905382. Loader thread: three ``collate`` (125614816, 7922310, 8292569
# long; real 19872 + 14528 + 19456 of 21632 + 15616 + 21632 edge slots) and
# two ``transfer`` (10082379, 7609269), none nested. Device: operations from
# 3127924880 to 3341229489 with 197793785 of gaps, 197786924 of it the one
# gap 3131723868..3329510792.
WINDOW = 6102055288 - 41873969
LONGEST_GAP = (3131723868.0, 3329510792.0)
BY_HAND = {
    "loop_dispatch_ms": 1.81558,
    "loop_device_wait_share": 100.0 * (17800 + 876607956 + 18308910 + 2854320 + 1815580
                                       + 1300530 - 4 * 1815580) / WINDOW,
    "epoch_turnaround_ms": (3327905382 - 3132840750) * 1e-6,
    "loader_busy_share": 100.0 * (125614816 + 7922310 + 8292569 + 10082379 + 7609269) / WINDOW,
    "loader_padded_edge_share": 100.0 * (1.0 - (19872 + 14528 + 19456) / (21632 + 15616 + 21632)),
    "step_program_lowerings": 11,           # the dict's record: 11 of each, 40 + 9.5 + 30.5 s
    "step_program_compile_s": 80.0,
}
# the longest gap, split by hand: the loop's spans first (drain to 3132840750,
# reduce, dataload, stage, dispatch from 3327905382), then the loader's second
# collate (3326682032..3334604342) over the two pieces of ``train`` self time
# on either side of ``stage``, then ``train`` itself, the rest under no span
GAP_BY_HAND = {
    "drain": 3132840750 - 3131723868, "reduce": 3164783238 - 3132928290,
    "dataload": 3326841742 - 3166940158, "stage": 3327867952 - 3326893252,
    "dispatch": 3329510792 - 3327905382,
    "collate": (3326893252 - 3326841742) + (3327905382 - 3327867952),
    "train": (3132894090 - 3132840750) + (3166940158 - 3166927608),
    "none": (3132928290 - 3132894090) + (3166927608 - 3164783238),
}
# not worked out by hand: 3,000 device operations grouped by scope. Recorded
# values, so that the grouping does not drift unseen; their sum is checked.
RECORDED = {"device_conv_ms": 14.898374, "device_force_path_ms": 8.301671,
            "device_unscoped_share": 3.8555269533069296}


def test_gap_attribution_on_the_recorded_boundary():
    host = context(recorded(), [])["_spans"]["host"]
    main = spans.loop_thread(host)
    threads = [host[main]] + [ev for t, ev in host.items() if t != main]
    assert sum(GAP_BY_HAND.values()) == LONGEST_GAP[1] - LONGEST_GAP[0]
    assert spans.attribute([LONGEST_GAP], threads) == GAP_BY_HAND


def test_every_reader_on_the_recorded_dict(monkeypatch):
    rec, said = recorded(), []
    ctx = context(rec, said)
    monkeypatch.setattr(spans, "step_compiles", lambda: {
        k: tuple(v) for k, v in rec["step_compiles"].items()})
    values = {name: load_module("metrics", name).read(ctx) for name in READERS}
    for name, expected in {**BY_HAND, **RECORDED}.items():
        assert values[name] == pytest.approx(expected, rel=1e-9), name
    assert "dispatch: 3 calls returned at once, 1 held by the runtime (25.0%)" in said
    # the other 2,590 gaps are 6861 ns in all: the longest decides the share
    idle = spans.idle_by_span(ctx)
    assert sum(idle.values()) == pytest.approx(197793785e-9)
    assert values["idle_unattributed_share"] == pytest.approx(
        100.0 * (GAP_BY_HAND["train"] + GAP_BY_HAND["none"]) / 197793785, abs=6861 / 1977937.85)
    table = spans.device_by_scope(ctx)
    assert sum(table["table"].values()) == pytest.approx(table["total"])
    assert table["total"] == pytest.approx((3341229489 - 3127924880 - 197793785) * 1e-6)
    assert any(line.startswith("device idle by program span") for line in said)
    assert any(line.startswith("device ms per step by pass x module") for line in said)


def test_every_reader_gives_none_on_an_empty_trace(monkeypatch):
    monkeypatch.setattr(spans, "step_compiles", lambda: None)
    for spans_found in (None, {"host": {}, "scopes": {}}):
        ctx = {"events": {"devices": {}}, "steps": 0, "say": lambda msg: None,
               "_spans": spans_found}
        for name in READERS:
            assert load_module("metrics", name).read(ctx) is None, name
