"""The reduction from trace to numbers, on a trace small enough to count by
hand and on a recorded one."""

import json
import os

import pytest

from lib import trace

MS = 1e6  # ns


def test_reduction_by_hand():
    t = {
        "devices": {
            "/device:TPU:0": [
                ["fusion.1", 0 * MS, 4 * MS],
                ['%k.7 = f32[8] custom-call(), custom_call_target="tpu_custom_call"', 3 * MS, 3 * MS],
                ["%fusion.2 = f32[8] fusion(%x)", 10 * MS, 4 * MS],  # 10..14
                ["%fusion.9 = f32[8] fusion(%custom-call.7)", 12 * MS, 1 * MS],  # inside it
                ["fusion.1", 30 * MS, 2 * MS],
            ],
            "/device:TPU:1": [["fusion.1", 0 * MS, 10 * MS]],
        },
        "host": [["bench_dataload", 15 * MS, 14 * MS], ["bench_dispatch", 6.2 * MS, 3 * MS]],
    }
    r = trace.reduce(t)
    assert r["devices"] == 2
    # device 0: [0,6] + [10,14] + [30,32] = 12 ms; device 1: 10 ms
    assert r["busy_s"] == pytest.approx(11e-3)
    assert r["mosaic_s"] == pytest.approx(1.5e-3)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(8e-3)]
    # gaps on device 0: 14..30 (the loader), 6..10 (dispatch)
    assert r["idle_gaps"][0] == ["dataload", pytest.approx(16e-3)]
    assert r["idle_gaps"][1] == ["dispatch", pytest.approx(4e-3)]


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert trace.length(trace.union([(5, 7), (0, 2), (1, 3)])) == 5


def test_recorded_trace():
    """A few hundred events of a traced run on the chip (``tools/dump_trace.py``),
    with the numbers this code gave when it was recorded."""
    path = os.path.join(os.path.dirname(__file__), "data", "recorded_trace.json")
    with open(path) as f:
        rec = json.load(f)
    r = trace.reduce(rec["trace"])
    for key, want in rec["expected"].items():
        assert r[key] == pytest.approx(want, rel=1e-9), key
    assert 0 < r["busy_s"] and r["device_ops"]


def test_finish_stamps_against_the_device_step_ends():
    """Three steps end on the device 10 and 30 ms apart; the host stamps
    them on a clock with another zero, the last one 2 ms late."""
    t = {"modules": {"/device:TPU:0": [
        ["jit_train_step(11)", 0 * MS, 5 * MS], ["jit__copy(3)", 6 * MS, 1 * MS],
        ["jit_train_step(12)", 8 * MS, 7 * MS], ["jit_train_step(11)", 40 * MS, 5 * MS]]}}
    ends = trace.step_ends(t, ("train_step", "guarded_step"))
    assert ends == [5 * MS, 15 * MS, 45 * MS]
    gaps = trace.stamp_disagreement([100.0051, 100.0151, 100.0471], ends)
    assert gaps["device_interval"] == pytest.approx([0.010, 0.030])
    assert gaps["interval"] == pytest.approx([0.0, 0.002], abs=1e-9)
    assert gaps["late"] == pytest.approx([0.0, 0.0, 0.002], abs=1e-9)
    assert trace.stamp_disagreement([1.0, 2.0], ends) is None  # not the same steps
    assert trace.step_ends({"devices": {}}, ("train_step",)) == []
