"""The SchNet cell at its rehearsal size on the CPU: ``correct`` on a sound
run; not ``correct`` with the program's cutoff window dropped, nor with its
``filter2`` biases zeroed (the plain reference keeps both); the emulated
three-pass product (``high``) put in the program's place stands over the
rehearsal's ``grad_norm`` limit; ``ops/schnet.py`` against a hand count at the
published widths; the count of traced steps that ran the gather-multiply-sum's
kernel."""

import argparse

import pytest

import run as bench

CELL = "schnet_mlip_oc20.fill"


def args(seed=2**31 + 37):
    return argparse.Namespace(workload=CELL, seed=seed, seconds=0.5, trace=0)


def test_rehearsal_is_correct():
    result = bench.run(args(), require_chip=False)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    compared = result["compared"]
    assert all(compared[k]["value"] < 0.5 * compared[k]["limit"]
               for k in ("loss", "grad_norm", "change_norm")), compared


def test_a_dropped_cutoff_is_not_correct(monkeypatch):
    """W_ji without its cosine window: every edge weighs as if at distance 0."""
    import jax.numpy as jnp

    from hydragnn_tpu.models import schnet

    def mutate(prog):
        monkeypatch.setattr(schnet, "cosine_cutoff",
                            lambda dist, cutoff: jnp.where(dist <= cutoff, 1.0, 0.0) + 0.0 * dist)

    result = bench.run(args(), require_chip=False, mutate=mutate)
    assert result["correct"] is False
    compared = result["compared"]  # by a wide margin, not by rounding
    assert max(compared[k]["value"] / compared[k]["limit"]
               for k in ("loss", "grad_norm", "change_norm")) > 100.0, compared


def test_zeroed_filter_biases_are_not_correct():
    """The program's state with every ``filter2`` bias zeroed; the reference is
    handed the seeded weights as they were made."""
    import jax
    import jax.numpy as jnp

    def mutate(prog):
        params = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.zeros_like(leaf)
            if "filter2" in jax.tree_util.keystr(path) and leaf.ndim == 1 else leaf,
            prog.state.params)
        prog.state = prog.state._replace(params=params)

    result = bench.run(args(), require_chip=False, mutate=mutate)
    assert result["correct"] is False


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_emulated_high_stands_over_a_limit(seed):
    """The control: the reference with its products made from three bfloat16
    passes, put in the program's place and held to the rehearsal's own limits,
    fails ``grad_norm``; one pass fails all three. (On the chip the real ``high``
    is read through ``tools/controls.py`` against the cell's limits.)"""
    import jax

    from lib import check, weights
    from lib.cells import Cell
    from lib.program import Program

    jax.config.update("jax_default_matmul_precision", "highest")
    cell = Cell(CELL, rehearse=True)
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
    want = cell.follow(cell.reference.node_energy, hp, opt, params0, steps, scale)
    verdicts = {}
    for emulate in ("high", "default"):
        got = cell.follow(cell.reference.node_energy, dict(hp, emulate=emulate), opt, params0,
                          steps, scale)
        ok, rows = check.compare(got, want, cell.config["limits"])
        verdicts[emulate] = (ok, {r["name"] for r in rows if not r["ok"]})
    assert verdicts["high"][0] is False and "grad_norm" in verdicts["high"][1], verdicts
    assert verdicts["default"] == (False, {"loss", "grad_norm", "change_norm"}), verdicts


def test_ops_by_hand():
    """``ops/schnet.py`` at the published widths (hidden 1024, 256 filters, 200
    Gaussians, 5 layers, head 512), one atom with 50 edges."""
    from lib.cells import Cell, load_module

    ops = load_module("ops", "schnet")
    cfg = Cell(CELL).config
    w = ops.widths(cfg)
    assert w == {"hidden": 1024, "filters": 256, "gaussians": 200, "layers": 5, "input": 1,
                 "head": [512, 1]}
    # the filter network: 200 x 256 + 256 x 256 multiply-adds an edge a layer; it reads the
    # 200 Gaussians, writes and re-reads 256, writes 256
    assert 200 * 256 + 256 * 256 == 116_736
    assert ops.filter_forward(w, 1.0) == (5 * 116_736, 5 * (200 + 3 * 256))
    assert ops.filter_forward(w, 50.0)[0] == 5 * 5_836_800  # 5.84M an atom a layer
    # lin1 (1 x 256 in the first layer, 1024 x 256 after) + 50 x 256 products-and-sums;
    # the node row in, the lin1 row out, 50 filter rows and 50 gathered rows in, the sum out
    assert ops.aggregate_forward(w, 1.0, 50.0) == (
        (1 * 256 + 12_800) + 4 * (1024 * 256 + 12_800),
        (1 + 256 + 25_600 + 256) + 4 * (1024 + 256 + 25_600 + 256))
    macs, elems = ops.forward(w, 1.0, 50.0)
    # + geometry and smearing once, lin2 five times, the head
    assert macs == 50 * (9 + 400) + 5 * 5_836_800 + (256 + 4 * 262_144 + 5 * 12_800) \
        + 5 * 256 * 1024 + (1024 * 512 + 512)
    assert elems == 50 * 212 + 5 * 50 * 968 + ((1 + 256) + 4 * (1024 + 256) + 5 * 25_856) \
        + 5 * (256 + 1024) + (1024 + 512) + (512 + 1)
    assert 31.5e6 < macs < 32.5e6  # ~32M multiply-adds an atom a forward pass
    assert 5 * 5_836_800 / macs > 0.9  # nine tenths of it the filter network
    assert ops.needed(cfg, 1.0, 50.0, 1.0) == (9 * 2 * macs, 9 * 4 * elems)
    assert ops.filter(cfg, 1.0, 50.0) == (9 * 2 * 5 * 5_836_800, 9 * 4 * 5 * 50 * 968)
    assert ops.aggregate(cfg, 1.0, 50.0) == tuple(
        f * v for f, v in zip((18, 36), ops.aggregate_forward(w, 1.0, 50.0)))
