"""The MACE cell at its rehearsal size on the CPU: ``correct`` on a sound
run, not ``correct`` with one coupling path of the program dropped (the
plain reference keeps all ten)."""

import argparse

import run as bench

CELL = "mace_mlip_mptrj.fill"


def args(seed=2**31 + 29):
    return argparse.Namespace(workload=CELL, seed=seed, seconds=0.5, trace=0)


def test_rehearsal_is_correct():
    result = bench.run(args(), require_chip=False)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0


def test_a_dropped_path_is_not_correct(monkeypatch):
    from hydragnn_tpu.models import mace

    real = mace.coupling_tensor
    monkeypatch.setattr(
        mace, "coupling_tensor",
        lambda l1, l2, l3: real(l1, l2, l3) * (0.0 if (l1, l2, l3) == (1, 2, 3) else 1.0))
    mace.couplings.cache_clear()  # the program keeps each layer's couplings once built
    try:
        result = bench.run(args(), require_chip=False)
    finally:
        mace.couplings.cache_clear()
    assert result["correct"] is False


def test_ops_by_hand():
    """``ops/mace.py`` at 2 channels, one atom, one edge."""
    from lib.cells import load_module

    ops = load_module("ops", "mace")
    s = {"C": 2, "layers": 2, "max_ell": 3, "node_ell": 1, "nu": 3, "n_b": 10,
         "radial": [64, 64, 64], "head": [16, 1]}
    # layer 1: 4 paths from 0e, 16 outputs; couplings 16 x 16, product 2 x 16 x 2
    # layer 2: 10 paths from 0e + 1o, 40 outputs, 88 coupling entries; 2 x (16 x 2 + 24 x 4)
    assert ops.tensor_product_forward(s, 1.0, 1.0) == (
        (256 + 64) + (1408 + 256),
        ((2 + 8 + 16 + 32) + 32 + 32) + ((8 + 20 + 16 + 80) + 80 + 80))
    # monomials 16, 136, 816; eta 1, 4, 8 to L = 0 and 1, 3, 12 to each of L = 1's three rows
    to_scalar = 17 + 4 * 137 + 8 * 817
    to_vector = 3 * (17 + 3 * 137 + 12 * 817)
    assert ops.contraction_forward(s, 1.0) == (
        2 * ((136 + 816 + to_scalar + to_vector) + (136 + 816 + to_scalar)),
        2 * ((16 + 29 + 4) + (16 + 13 + 1)))
    macs, elems = ops.forward(s, 1.0, 1.0)
    assert macs > 1984 + 93604 and elems > 406 + 158
