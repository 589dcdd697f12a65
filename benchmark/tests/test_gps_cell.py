"""The GPS cell at its rehearsal size on the CPU: ``correct`` on a sound run
(whose stack is scanned, as the cell's is); not ``correct`` with the
attention's key mask dropped, nor with the batch statistics frozen (the plain
reference keeps both); the emulated three-pass product (``high``) put in the
program's place stands over the rehearsal's limits; ``ops/gps.py`` against a
hand count for two small graphs (``test_ops.py`` is the accepted benchmark's
file, so the count lives here); the attention readers on a recorded dict."""

import argparse

import pytest

import run as bench

CELL = "gps_egnn_mlip_oc20.fill"
NUMBERS = ("loss", "grad_norm", "change_norm", "stats_norm")


def args(seed=2**31 + 41):
    return argparse.Namespace(workload=CELL, seed=seed, seconds=0.5, trace=0)


def test_rehearsal_is_correct_and_its_stack_is_scanned():
    seen = {}

    def look(prog):
        seen["scan"] = prog.model.spec.scan_conv_layers
        seen["stats"] = sorted(prog.stats0)

    result = bench.run(args(), require_chip=False, mutate=look)
    assert seen["scan"] is True and seen["stats"] == [f"graph_convs_{i}" for i in range(3)]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    compared = result["compared"]
    assert list(compared) == [*NUMBERS, "nonfinite_losses"]
    assert all(compared[k]["value"] < 0.5 * compared[k]["limit"] for k in NUMBERS), compared


def test_a_dropped_attention_mask_is_not_correct(monkeypatch):
    """Softmax over every slot of a dense block: the padded keys (zero rows,
    logit 0) take their share of every real atom's weights."""
    import jax

    from hydragnn_tpu.ops import fused_softmax

    def mutate(prog):
        monkeypatch.setattr(fused_softmax, "_auto_enabled", lambda: True)
        monkeypatch.setattr(fused_softmax, "fused_masked_softmax",
                            lambda logits, mask: jax.nn.softmax(logits, axis=-1))

    result = bench.run(args(), require_chip=False, mutate=mutate)
    assert result["correct"] is False
    compared = result["compared"]  # by a wide margin, not by rounding
    assert max(compared[k]["value"] / compared[k]["limit"] for k in NUMBERS) > 100.0, compared


def test_frozen_batch_statistics_are_not_correct():
    """A step that trains, and hands the statistics back as it got them: only
    ``stats_norm`` sees it, since a training-mode forward never reads them."""
    def mutate(prog):
        import jax
        import jax.numpy as jnp

        real = prog.step.step

        def step(state, batch):
            kept = jax.tree.map(jnp.copy, state.batch_stats)  # the call donates its state
            new, metrics = real(state, batch)
            return new._replace(batch_stats=kept), metrics

        prog.step.step = step

    result = bench.run(args(), require_chip=False, mutate=mutate)
    assert result["correct"] is False
    compared = result["compared"]
    assert [k for k in NUMBERS if compared[k]["value"] > compared[k]["limit"]] == ["stats_norm"]
    assert compared["stats_norm"]["value"] > 100 * compared["stats_norm"]["limit"]


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_emulated_high_stands_over_a_limit(seed):
    """The control: the reference with its dense products made from three
    bfloat16 passes, put in the program's place and held to the rehearsal's
    own limits, fails ``grad_norm``; one pass fails every number. (On the chip
    the real ``high`` is read through ``tools/controls.py``.)"""
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
    assert verdicts["default"] == (False, set(NUMBERS)), verdicts


def test_ops_by_hand():
    """``ops/gps.py`` at hidden 4, 2 heads, 3 encodings, 2 layers, head [4]:
    two graphs of 2 and 3 atoms (5 atoms, 10 edges), so 2 x 2 + 3 x 3 = 13
    real attention pairs."""
    from lib.cells import load_module

    ops = load_module("ops", "gps")
    cfg = {"NeuralNetwork": {
        "Architecture": {"hidden_dim": 4, "global_attn_heads": 2, "pe_dim": 3,
                         "num_conv_layers": 2, "equivariance": True,
                         "output_heads": {"node": {"num_headlayers": 1, "dim_headlayers": [4]}}},
        "Variables_of_interest": {"input_node_features": [0]}}}
    w = ops.widths(cfg)
    assert w == {"hidden": 4, "heads": 2, "encodings": 3, "layers": 2, "input": 1,
                 "coordinate_updates": True, "head": [4, 1]}
    n, e, p = 5.0, 10.0, 13.0
    # softmax: 2 heads x 13 pairs read and written, a layer
    assert ops.softmax_forward(w, p) == (0.0, 2 * (2 * 2 * 13))
    # attention a layer: q, k, v, out 5 x 4 x 16 = 320, scores and sum 2 x 13 x 4 = 104;
    # elements: projections 5 x 4 x 8 = 160, q k v read + out written 5 x 16 = 80, softmax 52
    assert ops.attention_forward(w, n, p) == (2 * (320 + 104), 2 * (160 + 80 + 52))
    # local, layer 0: phi_e 10 x (13 x 4 + 16) = 680, phi_x 10 x (16 + 4) = 200,
    #   phi_h 5 x (32 + 16) = 240; layer 1 (last: no phi_x): 680 + 240
    # elements, layer 0: phi_e 10 x (13 + 4 + 8) = 250, phi_x 10 x (8 + 5) + 30 + 15 = 175,
    #   phi_h 5 x (12 + 8) = 100, sum 40 + 20 = 60; layer 1: 250 + 100 + 60
    assert ops.local_forward(w, n, e) == (680 + 200 + 240 + 680 + 240,
                                          250 + 175 + 100 + 60 + 250 + 100 + 60)
    # embedding 5 x (4 + 12 + 32) = 240; rel_pos_emb 10 x 12 and feed_forward 5 x 64 a layer;
    # head 4 -> 4 -> 1: 5 x (16 + 4) = 100
    macs = 240 + 2040 + 848 + 2 * (120 + 320) + 100
    # elements: embedding 5 x (5 + 7 + 12) = 120; a layer: rel_pos_emb 10 x 7 = 70,
    # feed_forward 5 x 24 = 120, three norms 3 x 5 x 8 = 120; head 5 x (8 + 5) = 65
    elems = 120 + 995 + 584 + 2 * (70 + 120 + 120) + 65
    assert ops.forward(w, n, e, p) == (macs, elems)
    assert ops.attention(cfg, n, p) == (9 * 2 * 848, 9 * 4 * 584)
    assert ops.softmax(cfg, p) == (0.0, 9 * 4 * 104)
    # ``needed`` is handed no pairs: their lower bound, 5 x 5 / 2 graphs = 12.5
    assert ops.needed(cfg, n, e, 2) == tuple(
        9 * k * v for k, v in zip((2, 4), ops.forward(w, n, e, 12.5)))


def test_attention_readers_on_recorded_spans():
    """``padded_attention_share`` and the window's real pairs from the
    ``collate`` spans' counters; nothing to read -> None, and nothing raised."""
    from lib import attention_spans
    from lib.cells import load_module

    share = load_module("metrics", "padded_attention_share")
    spans = {"host": {"producer#0": [
        (0.0, 1.0, "collate", {"real_edges": 60, "attention_slots": 4 * 64, "attention_pairs": 29}),
        (2.0, 3.0, "collate", {"real_edges": 40, "attention_slots": 4 * 64, "attention_pairs": 21}),
        (4.0, 5.0, "transfer", {"bytes": 10})]}, "scopes": {}}
    ctx = {"_spans": spans, "collated": [("shape", 5, 60, 2, None), ("shape", 5, 60, 2, None)]}
    assert attention_spans.counts(ctx) == (50, 512, 100)
    assert share.read(ctx) == 100.0 * (1.0 - 50 / 512)
    assert attention_spans.window_pairs(ctx) == 50 / 100 * 120
    # a program without the counters (the parent commit), or a run without a trace
    bare = {"host": {"producer#0": [(0.0, 1.0, "collate", {"real_edges": 60})]}, "scopes": {}}
    for empty in ({"_spans": bare, "collated": []}, {"_spans": None, "collated": []}):
        assert share.read(empty) is None and attention_spans.window_pairs(empty) is None
        for name in ("attention_roofline_share", "masked_softmax_roofline_share",
                     "device_attention_ms", "device_local_ms"):
            assert load_module("metrics", name).read(dict(empty, ops=load_module("ops", "gps"),
                                                          events=None, steps=0)) is None


def test_scope_readers_find_operations_inside_the_scanned_body():
    """``data/gps_scan_op_names.json``: operation names of the cell's scanned
    step as the TPU compiler leaves them (compiled for a v5e without a chip):
    the nine scanned layers are ONE name, ``graph_convs_0``, under
    ``while/body``, the tenth is ``graph_convs_9`` outside it; the readers of
    ``lib/spans.py`` and ``lib/scope_time.py`` place both, in every pass."""
    import json
    import os

    from lib import scope_time, spans

    with open(os.path.join(os.path.dirname(__file__), "data", "gps_scan_op_names.json")) as f:
        names = json.load(f)["op_names"]
    rows = {}
    for name in names:
        scope = spans.parse_scope(name)
        assert scope is not None and scope["root"] == "HydraModel", name
        inside = "while" in scope["path"]
        rows.setdefault((spans.pass_name(scope["tag"]), spans.module(scope)), set()).add(inside)
        if "graph_convs" in name:
            assert ("HydraModel.conv_block" in scope["path"]) and \
                inside == ("graph_convs_0" in scope["path"]), name
    parts = ("local", "attention", "feed_forward", "norm", "rel_pos_emb")
    for layer, inside in (("graph_convs_0", True), ("graph_convs_9", False)):
        for part in parts:
            assert rows[("forward", f"{layer}/{part}")] == {inside}
        for part in parts[:4]:  # the encodings' embedding has no position in it
            assert rows[("grad.forces", f"{layer}/{part}")] == {inside}
    held = lambda name, *segments: scope_time._holds(spans.parse_scope(name)["path"], segments)
    assert sum(held(n, "attention") for n in names) >= 5
    assert sum(held(n, "softmax") for n in names) == 2  # the Mosaic call and its VJP's select
    assert all(held(n, "attention") for n in names if held(n, "softmax"))
    assert not any(held(n, "local") and held(n, "attention") for n in names)
    # the scan's own slices and stacks carry no conv block's name
    assert rows[("forward", "embedding")] == {True}
