"""The DimeNet++ cell at its rehearsal size on the CPU: ``correct`` on a sound
run, not ``correct`` with the program's Legendre part set to 1 (an
angle-blind program: every triplet still exchanges, no angle is read); the
shape functions against a hand count on a 3-atom chain; the objective that
fills every graph into one shape (``reference/mlip_padded.py``) against
``reference/mlip.py`` on the same steps."""

import argparse

import pytest

import run as bench

CELL = "dimenetpp_mlip_oc20.fill"


def args(seed=2**31 + 31):
    return argparse.Namespace(workload=CELL, seed=seed, seconds=0.5, trace=0)


def test_rehearsal_is_correct():
    result = bench.run(args(), require_chip=False)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    compared = result["compared"]
    assert all(compared[k]["value"] < 0.1 * compared[k]["limit"]
               for k in ("loss", "grad_norm", "change_norm")), compared


def test_an_angle_blind_program_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    from hydragnn_tpu.models import dimenet

    monkeypatch.setattr(
        dimenet, "angular_on_triplets",
        # [T] cosines on the flat list, [E, K] on the block layout
        lambda cos, s, r: jnp.ones(cos.shape + (s * r,), cos.dtype) + 0.0 * cos[..., None])
    result = bench.run(args(), require_chip=False)
    assert result["correct"] is False
    compared = result["compared"]  # by a wide margin, not by rounding
    assert max(compared[k]["value"] / compared[k]["limit"]
               for k in ("loss", "grad_norm", "change_norm")) > 10.0, compared


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_filled_graphs_give_what_whole_steps_give(seed):
    """Three steps of four graphs each (2-20 atoms, so every graph but the
    largest is filled): losses, first gradient and change of every leaf agree
    with ``mlip.py`` to float32 rounding (AdamW turns a gradient entry near zero
    into a whole step, so the change is held ten times looser), and it ran ONE
    shape."""
    import jax

    from lib import check, weights
    from lib.cells import Cell, load_module
    from lib.program import Program

    cell = Cell(CELL, rehearse=True)
    assert cell.config["objective"] == "mlip_padded"
    jax.config.update("jax_default_matmul_precision", cell.config["precision"]["matmul"])
    graphs = cell.generator.generate(cell.traffic["params"], seed)
    prog = Program(cell.config, cell.traffic, graphs,
                   lambda sh: weights.make_weights(sh, seed, cell.config["weights"]))
    params0 = weights.flat_dict(prog.params0)
    steps = [[[graphs[j] for j in prog.corpus_index[chunk]]] for chunk, _ in prog.plan(0)[:3]]
    hp = cell.reference.hyperparameters(cell.config)
    opt = dict(cell.config["optimizer_reference"], learning_rate=1e-4)
    scale = float(cell.config["input_scale"])
    filled = load_module("reference", "mlip_padded")
    shapes = {len(g["z"]) for step in steps for g in step[0]}
    assert len(shapes) > 3
    want = load_module("reference", "mlip").follow(
        cell.reference.node_energy, hp, opt, params0, steps, scale)
    got = filled.follow(cell.reference.node_energy, hp, opt, params0, steps, scale)
    assert filled._graph_terms._cache_size() == 1
    ok, rows = check.compare(got, want, {"loss": 1e-6, "grad_norm": 3e-6, "change_norm": 3e-5})
    assert ok, rows


def test_ops_by_hand():
    """``ops/dimenet.py`` on the chain 0 - 1 - 2: edges 0>1, 1>0, 1>2, 2>1;
    triplets (2>1, 1>0) and (0>1, 1>2), every other pair is an exact reverse."""
    from lib.cells import load_module

    ops = load_module("ops", "dimenet")
    s = {"F": 1, "H": 4, "O": 4, "I": 2, "B": 2, "S": 2, "R": 3, "layers": 1, "residual": 3,
         "output_layers": 3, "head": [4, 1]}
    nodes, edges, triplets = 3.0, 4.0, 2.0
    # lin_down + lin_up: 4 edges x 2 x (4 x 2); a triplet: 6 x 2 + 2 x 2 + 2 multiply-adds
    # moved: an edge's H + I rows in and out of both linears, a triplet's basis row (6), its
    # gathered row (2) and its summed row (2), and the 4 x 2 sums written
    assert ops.triplets_forward(s, edges, triplets) == (64 + 36, 48 + 20 + 8)
    assert ops.reckoned_triplets(nodes, edges) <= triplets  # the lower bound of ``needed``
    # geometry + basis: an edge 5 + 4 x 3 + 20 x 6, a triplet 20 + 2 x 2 + 6
    assert ops.basis_forward(s, edges, triplets) == (4 * 137 + 2 * 30, 4 * 23 + 2 * 19)
    macs, elems = ops.forward(s, nodes, edges, triplets)
    assert macs > 100 + 608 and elems > 76 + 130
    flop, nbytes = ops.triplets({"NeuralNetwork": {
        "Architecture": {"hidden_dim": 4, "out_emb_size": 4, "int_emb_size": 2, "basis_emb_size": 2,
                         "num_spherical": 2, "num_radial": 3, "num_conv_layers": 1,
                         "num_before_skip": 1, "num_after_skip": 2, "num_output_layers": 3,
                         "output_heads": {"node": {"num_headlayers": 1, "dim_headlayers": [4]}}},
        "Variables_of_interest": {"input_node_features": [0]}}}, nodes, edges, triplets)
    assert (flop, nbytes) == (9 * 2 * 100, 9 * 4 * 76)  # 9 x the forward pass, 2 FLOP, 4 B
