"""The harness on a model that keeps batch statistics (``MaskedBatchNorm``
feature layers): the state carries the program's own initial statistics, the
step wrapper keeps their copies, a fourth number ``stats_norm`` is compared,
and a step that withholds them comes out not ``correct``. No cell has such a
model yet, so the stack is EGNN's rehearsal cell with ``mpnn_type`` SAGE, and
what stands in for the plain reference is the sound program's own numbers,
recorded on a first run (an objective file for such a model comes with the
configuration that needs it). And: a weight rule without ``unit_mean`` makes
the values it made before the key was there."""

import argparse
import copy
import json
import os

import numpy as np
import pytest

import run as bench
from lib import check, weights
from lib.cells import ROOT, Cell
from lib.program import Program

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "egnn_mlip_mptrj.fill"
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


def with_batch_norm(config: dict) -> dict:
    config = copy.deepcopy(config)
    config["NeuralNetwork"]["Architecture"]["mpnn_type"] = "SAGE"
    config["weights"] = dict(config["weights"], unit_mean=["/scale"])
    config["limits"] = dict(config["limits"], stats_norm=1e-5)
    return config


def build(seed=5):
    cell = Cell(CELL, rehearse=True)
    config = with_batch_norm(cell.config)
    graphs = cell.generator.generate(cell.traffic["params"], seed)
    return Program(config, cell.traffic, graphs,
                   lambda sh: weights.make_weights(sh, seed, config["weights"]))


def test_the_state_carries_statistics_and_the_probe_keeps_them():
    prog = build()
    stats0 = weights.flat_dict(prog.stats0)
    assert stats0 and all(k.endswith(("/mean", "/var")) for k in stats0)
    for k, v in stats0.items():  # the program's own initial values: nothing random
        assert np.array_equal(v, np.zeros_like(v) if k.endswith("/mean") else np.ones_like(v))
    scales = {k: v for k, v in weights.flat_dict(prog.params0).items() if k.endswith("/scale")}
    assert scales and all(abs(float(np.mean(v)) - 1.0) < 0.02 and np.std(v) > 0
                          for v in scales.values())
    prog.step.capture = 2
    prog.steps(prog.plan(0)[:2])  # two steps through train_epoch
    assert len(prog.step.captured) == 2 and all(len(c) == 4 for c in prog.step.captured)
    first, second = (weights.flat_dict(c[2]) for c in prog.step.captured)
    assert set(first) == set(second) == set(stats0)
    for k in stats0:  # they move with every batch, and the copies are not one buffer
        assert not np.array_equal(first[k], stats0[k]) and not np.array_equal(second[k], first[k])
    assert weights.flat_dict(prog.state.batch_stats).keys() == stats0.keys()
    numbers = check.program_numbers(prog.step.captured, weights.flat_dict(prog.params0),
                                    weights.flat_dict, bench.first_moment, 0.9)
    assert set(numbers["stats_norm"]) == set(stats0)


@pytest.fixture
def stats_cell(monkeypatch):
    """EGNN's cell turned into one with batch statistics; its ``follow``
    answers what the FIRST run's program read (the stand-in reference)."""
    said = {"handed": [], "numbers": None}
    real_init, real_numbers = Cell.__init__, check.program_numbers

    def follow(node_energy, hp, opt, params0, steps, input_scale, stats0=None):
        said["handed"].append(stats0)
        return copy.deepcopy(said["numbers"])

    def init(self, workload, rehearse=False):
        real_init(self, workload, rehearse)
        self.config, self.follow = with_batch_norm(self.config), follow

    def numbers(*a, **kw):
        out = real_numbers(*a, **kw)
        if said["numbers"] is None:
            said["numbers"] = copy.deepcopy(out)
        return out

    monkeypatch.setattr(Cell, "__init__", init)
    monkeypatch.setattr(check, "program_numbers", numbers)
    return said


def test_a_run_compares_the_statistics_and_withholding_them_is_not_correct(stats_cell):
    args = argparse.Namespace(workload=CELL, seed=2**31 + 41, seconds=0.5, trace=0)
    sound = bench.run(args, require_chip=False)
    assert sound["correct"] is True
    assert list(sound["compared"]) == ["loss", "grad_norm", "change_norm", "stats_norm",
                                       "nonfinite_losses"]
    assert sound["compared"]["stats_norm"]["limit"] == 1e-5
    handed = stats_cell["handed"][0]  # the objective file gets the initial values
    assert handed and all(k.endswith(("/mean", "/var")) for k in handed)

    def mutate(prog):  # a step that trains, and hands the statistics back as it got them
        import jax
        import jax.numpy as jnp

        real = prog.step.step

        def step(state, batch):
            kept = jax.tree.map(jnp.copy, state.batch_stats)  # the call donates its state
            new, metrics = real(state, batch)
            return new._replace(batch_stats=kept), metrics

        prog.step.step = step

    withheld = bench.run(args, require_chip=False, mutate=mutate)
    assert withheld["correct"] is False
    c = withheld["compared"]["stats_norm"]
    assert c["value"] > 100 * c["limit"]  # by the statistics alone, and not by rounding


def test_statistics_the_program_does_not_hand_over_are_not_correct():
    want = {"losses": [1.0], "grad_norm": {"a": 1.0}, "change_norm": {"a": 1.0},
            "stats_norm": {"norm/mean": 2.0, "norm/var": 3.0}}
    limits = {"loss": 1e-3, "grad_norm": 1e-3, "change_norm": 1e-3, "stats_norm": 1e-3}
    ok, rows = check.compare(copy.deepcopy(want), want, limits)
    assert ok and [r["name"] for r in rows] == ["loss", "grad_norm", "change_norm", "stats_norm"]
    got = {k: v for k, v in want.items() if k != "stats_norm"}
    ok, rows = check.compare(got, want, limits)
    assert not ok and rows[-1]["name"] == "stats_norm" and rows[-1]["value"] == float("inf")
    # a cell without statistics compares the three numbers it always did
    ok, rows = check.compare(got, got, limits)
    assert ok and [r["name"] for r in rows] == ["loss", "grad_norm", "change_norm"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_weights_without_the_key_are_what_they_were(cell_name):
    """``data/seeded_weights.json``: three leaves of every cell's rehearsal
    model at seed 2**31 + 7, written by the tree before ``unit_mean`` came."""
    with open(os.path.join(HERE, "data", "seeded_weights.json")) as f:
        held = json.load(f)[cell_name]
    cell = Cell(cell_name, rehearse=True)
    assert "unit_mean" not in cell.config["weights"]
    graphs = cell.generator.generate(cell.traffic["params"], 7)
    prog = Program(cell.config, cell.traffic, graphs,
                   lambda sh: weights.make_weights(sh, 2**31 + 7, cell.config["weights"]))
    assert prog.stats0 == {}
    flat = weights.flat_dict(prog.params0)
    assert len(flat) == held["leaves"]
    for name, values in held["first"].items():
        got = [float(x).hex() for x in np.asarray(flat[name]).ravel()[:len(values)]]
        assert got == values, name
