"""Each ``ops/<arch>.py`` against a count made by hand at a tiny size."""

from lib.cells import load_module


def config(arch, hidden, layers, head, **extra):
    a = {"hidden_dim": hidden, "num_conv_layers": layers,
         "output_heads": {"node": {"num_headlayers": len(head), "dim_headlayers": head}}}
    a.update(extra)
    return {"NeuralNetwork": {"Architecture": a,
                              "Variables_of_interest": {"input_node_features": [0]}}}


def test_egnn_by_hand():
    ops = load_module("ops", "egnn")
    cfg = config("egnn", 4, 2, [4], equivariance=True)
    n, e = 3.0, 5.0
    # layer 0 (w=1): phi_e 5*(3*4+16)=140, phi_x 5*(16+4)=100, phi_h 3*(5*4+16)=108
    # layer 1 (w=4, last: no phi_x): phi_e 5*(9*4+16)=260, phi_h 3*(8*4+16)=144
    # head 4->4->1: 3*(16+4)=60
    macs = 140 + 100 + 108 + 260 + 144 + 60
    # elements: l0 phi_e 5*(3+4+8)=75, phi_x 5*(8+5)+15+9=89, phi_h 3*(5+4+8)=51, sum 20+12=32
    #           l1 phi_e 5*(9+4+8)=105, phi_h 3*(8+4+8)=60, sum 32; head 3*(8+5)=39
    elems = 75 + 89 + 51 + 32 + 105 + 60 + 32 + 39
    assert ops.forward(ops.widths(cfg), n, e) == (macs, elems)
    assert ops.needed(cfg, n, e, 1) == (9 * 2 * macs, 9 * 4 * elems)


def test_painn_by_hand():
    ops = load_module("ops", "painn")
    cfg = config("painn", 2, 2, [2], num_radial=3)
    n, e = 2.0, 4.0
    # block 0 (w=1, not last, m=3): filter 4*3*3=36; phi 2*(1+3)=8; messages 4*3*1*2=24;
    #   U,V 2*3*2=12; a 2*(2+3)=10; lift 2*(2+4)=12; vector lift 2*3*1*2=12
    # block 1 (w=2, last, m=2): filter 4*3*6=72; phi 2*(4+12)=32; messages 4*3*2*2=48;
    #   U,V 2*3*2*4=48; a 2*(8+8)=32; lift 2*(4+4)=16
    # head 2->2->1: 2*(4+2)=12
    macs = (36 + 8 + 24 + 12 + 10 + 12 + 12) + (72 + 32 + 48 + 48 + 32 + 16) + 12
    got_macs, got_elems = ops.forward(ops.widths(cfg), n, e)
    assert got_macs == macs
    # elements of block 0: filter 4*(3+3)=24; phi 2*(2+4)=12; messages 4*6+2*4*3+2*3+4+2=60;
    #   U,V 2*3*2*2=24; a 2*(3+4)=14; lift 2*(3+4)=14; vector lift 2*3*3=18
    # block 1: filter 4*(3+6)=36; phi 2*(4+8)=24; messages 4*12+2*4*6+2*6+8+4=120;
    #   U,V 2*3*2*4=48; a 2*(6+6)=24; lift 2*(4+4)=16; head 2*(4+3)=14
    elems = (24 + 12 + 60 + 24 + 14 + 14 + 18) + (36 + 24 + 120 + 48 + 24 + 16) + 14
    assert got_elems == elems


def test_step_mfu_beside_the_roofline():
    """The whole step's share of the compute peak from the same needed work
    as the roofline: never above it, silent where there is no trace."""
    ops = load_module("ops", "egnn")
    cfg = config("egnn", 4, 2, [4], equivariance=True)
    peaks = {"bf16_flops_per_s": 2e6, "hbm_bytes_per_s": 1e6}
    ctx = {"trace": {"busy_s": 0.5}, "peaks": peaks, "ops": ops, "config": cfg, "chips": 1,
           "collated": [("shape", 3.0, 5.0, 1)], "say": lambda msg: None}
    flop, nbytes = ops.needed(cfg, 3.0, 5.0, 1)
    mfu = load_module("metrics", "step_mfu").read(ctx)
    assert mfu == 100.0 * flop / 2e6 / 0.5 and mfu > 0
    roofline = load_module("metrics", "step_roofline_share").read(ctx)
    assert roofline == 100.0 * max(flop / 2e6, nbytes / 1e6) / 0.5 >= mfu
    assert load_module("metrics", "step_mfu").read(dict(ctx, trace=None)) is None
    assert load_module("metrics", "step_mfu").read(dict(ctx, peaks=None)) is None
