"""Every cell, end to end at a tiny size on the CPU (the chip's look skipped):
the result object's schema, ``correct`` true on a sound run, ``correct`` false
with the timed path broken underneath, and false with the reference computed
one precision step down in the program's place (the control)."""

import argparse
import json
import os

import numpy as np
import pytest

import run as bench
from lib.cells import ROOT, Cell

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def args(cell, trace=0, seed=2**31 + 17):
    return argparse.Namespace(workload=cell, seed=seed, seconds=0.5, trace=trace)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct_and_has_the_schema(cell):
    result = bench.run(args(cell), require_chip=False)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device", "compared"}
    assert list(result)[-1] == "compared"  # the contract's: each number beside its limit, last
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    for c in result["compared"].values():
        assert c["value"] <= c["limit"]
    names = {m["name"] for m in Cell(cell).end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert set(result["metrics"]) == names
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    json.dumps(result)


def test_unchanged_state_is_not_correct():
    """The timed path broken underneath: a step that returns its state
    unchanged. The norm of the parameters' change is there to catch it."""
    def mutate(prog):
        real = prog.step.step
        prog.step.step = lambda state, batch: (state, real(state, batch)[1])

    result = bench.run(args(CELLS[0]), require_chip=False, mutate=mutate)
    assert result["correct"] is False


def test_part_of_the_batch_left_out_is_not_correct():
    """The loss is there to catch it: the step sees its batch with the last
    graph's targets and mask zeroed."""
    def mutate(prog):
        import jax.numpy as jnp

        real = prog.step.step

        def step(state, batch):
            g = jnp.asarray(batch.n_node)
            last = int(np.flatnonzero(np.asarray(g) > 0)[-1])
            mask = jnp.asarray(batch.graph_mask).at[last].set(0.0)
            node_mask = jnp.asarray(batch.node_mask) * (jnp.asarray(batch.batch) != last)
            return real(state, batch.replace(graph_mask=mask, node_mask=node_mask))

        prog.step.step = step

    result = bench.run(args(CELLS[0]), require_chip=False, mutate=mutate)
    assert result["correct"] is False


CONTROL_CELLS = sorted({w["config"]: w["name"] for w in BENCH["workloads"]}.values())
# (cell, seed) -> why its emulated ``high`` does not stand ten times clear of the sound run.
# Empty since PR 42: MACE's rehearsal at seed 2 (6.0 x on the old rehearsal's three batches
# of one shape) stands clear on the re-sized one, which drives two padded shapes.
CONTROL_KNOWN = {}


@pytest.mark.parametrize("cell_name,seed", [
    pytest.param(c, s, marks=pytest.mark.xfail(reason=CONTROL_KNOWN[c, s], strict=True))
    if (c, s) in CONTROL_KNOWN else (c, s) for c in CONTROL_CELLS for s in (1, 2, 3)])
def test_control_in_lower_precision_is_not_correct(cell_name, seed):
    """The control: the reference computed in a lower matmul precision, put in
    the program's place and held to the configuration's own limits.

    There is no TPU here, so the passes are emulated (``reference/mlip.py``).
    One bfloat16 pass — what the program's dense layers do on a TPU when
    nothing sets a precision — has to fail the limits on every seed. Three
    passes (``high``, the step just below the ``highest`` the configurations
    state) have to stand well clear of a sound run, ten times its gap on
    every seed; on the chip the real ``high`` reads about ten times further
    out than this emulation and fails the limits there on every seed read
    (PERF.md, section 2)."""
    import jax

    from lib import check, weights
    from lib.program import Program

    jax.config.update("jax_default_matmul_precision", "highest")
    cell = Cell(cell_name, rehearse=True)
    hp = cell.reference.hyperparameters(cell.config)
    opt = dict(cell.config["optimizer_reference"], learning_rate=float(
        cell.config["NeuralNetwork"]["Training"]["Optimizer"]["learning_rate"]))
    scale = float(cell.config["input_scale"])
    graphs = cell.generator.generate(cell.traffic["params"], seed)
    prog = Program(cell.config, cell.traffic, graphs,
                   lambda sh: weights.make_weights(sh, seed, cell.config["weights"]))
    params0 = weights.flat_dict(prog.params0)
    checked = bench.check_entries(prog, bench.signatures(prog, 2), 3)
    steps = [[[graphs[j] for j in prog.corpus_index[chunk]]] for chunk, _ in checked]
    prog.step.capture = len(checked)
    prog.steps(checked)
    sound = check.program_numbers(prog.step.captured, params0, weights.flat_dict,
                                  bench.first_moment, opt["b1"])
    want = cell.follow(cell.reference.node_energy, hp, opt, params0, steps, scale)
    gaps = {}
    for name, got in (("sound", sound), ("high", None), ("default", None)):
        if got is None:
            got = cell.follow(cell.reference.node_energy, dict(hp, emulate=name), opt,
                              params0, steps, scale)
        ok, rows = check.compare(got, want, cell.config["limits"])
        gaps[name] = (ok, {r["name"]: r["value"] for r in rows})
    assert gaps["sound"][0] is True
    assert gaps["default"][0] is False
    assert gaps["high"][1]["grad_norm"] > 10 * gaps["sound"][1]["grad_norm"]
