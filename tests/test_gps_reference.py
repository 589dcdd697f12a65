"""GPS round EGNN (hidden 32, 4 heads of 8, 3 layers, 10 Laplacian encodings,
6 neighbours) against the benchmark's plain reference
(``benchmark/reference/gps.py``: a Python loop over the layers, flat
``[N, N]`` masked softmax, batch norm with explicit masked moments; imports
nothing of the program) on padded batches of unlike periodic structures of
3-20 atoms: per-atom energies, forces and the moved batch statistics of one
training-mode call, and three AdamW steps of ``make_mlip_train_step`` against
``reference/mlip_batch.py::follow`` (loss, every gradient leaf, the parameters'
change, the statistics) on each route the attention can take (dense per-graph
blocks by the collate certificate, the flat form) and with the stack scanned.
A control in lower matmul precision fails the same tolerances; attention never
crosses graphs; the eigenvectors' sign rule is one rule on both sides and the
same across two collations of one structure.
"""

import copy
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.config import update_config
from hydragnn_tpu.graphs.batching import collate, compute_pad_spec
from hydragnn_tpu.models import create_model_config
from hydragnn_tpu.models.mlip import make_mlip_train_step
from hydragnn_tpu.preprocess.encodings import attach_lap_pe, fix_signs, laplacian_pe
from hydragnn_tpu.train import select_optimizer
from hydragnn_tpu.train.step import TrainState

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K = 6  # neighbours an atom


def _bench(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location("bench_" + "_".join(parts)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _bench("reference", "gps.py")
ref_batch = _bench("reference", "mlip_batch.py")
weights = _bench("lib", "weights.py")
check = _bench("lib", "check.py")
crystals = _bench("generators", "crystals.py")
program = _bench("lib", "program.py")
cells = _bench("lib", "cells.py")

CRYSTALS = {"count": 12, "radius": 6.0, "max_neighbours": K, "volume_per_atom": 14.0,
            "n_species": 83, "sizes": {"seed": 0, "median": 8, "sigma": 0.6, "min": 3,
                                       "max": 20, "max_at": 3}}
# float32 on the CPU against float32 on the CPU, ``lib/check.py``'s four numbers. Sound
# readings, the largest over the three routes: 1.4e-7 / 9.3e-7 / 2.5e-5 / 3.5e-5 (the
# statistics are the loosest: a running mean of features that a batch norm has just centred
# is a small number made of large ones, and ``change_norm``'s worst leaf is a norm's scale,
# whose gradient is a like sum). The emulated three-pass product (``high``) reads 7.9e-6 /
# 3.2e-5 / 1.6e-5 / 1.8e-5 and fails by the loss and the first gradient, whose limits stand
# 5-7 x over the sound reading and 6-8 x under it; one pass (``default``) reads 2.1e-3 /
# 1.5e-2 / 1.1e-2 / 3.5e-4 and fails every number
LIMITS = {"loss": 1e-6, "grad_norm": 5e-6, "change_norm": 1e-4, "stats_norm": 2e-4}
ROUTES = ("dense", "flat", "dense_scanned")


def bench_config(scan=False):
    """The cell's configuration at its rehearsal sizes."""
    with open(os.path.join(ROOT, "benchmark", "configs", "gps_egnn_mlip_oc20.json")) as f:
        cfg = json.load(f)
    cfg = cells._merge(cfg, cfg["rehearse"])
    arch = cfg["NeuralNetwork"]["Architecture"]
    assert (arch["hidden_dim"], arch["global_attn_heads"], arch["num_conv_layers"],
            arch["pe_dim"], arch["max_neighbours"]) == (32, 4, 3, 10, K)
    cfg["NeuralNetwork"]["Training"]["scan_conv_layers"] = scan
    return cfg


class Case:
    """Model, seeded weights, three padded batches of four structures, the
    reference's blocks of the same structures."""

    def __init__(self):
        self.bench_cfg = bench_cfg = bench_config()
        self.graphs = crystals.generate(CRYSTALS, 2**31 + 23)
        self.samples = program.to_samples(self.graphs, bench_cfg["input_scale"])
        for s in self.samples:
            attach_lap_pe(s, 10)
        self.models = {}
        for scan in (False, True):
            cfg = bench_config(scan)
            cfg = {k: copy.deepcopy(cfg[k]) for k in program.PROGRAM_KEYS if k in cfg}
            self.models[scan] = create_model_config(update_config(cfg, self.samples))
        self.model = self.models[False]
        self.chunks = [list(range(i, i + 4)) for i in (0, 4, 8)]
        self.pad = compute_pad_spec(self.samples, 4)
        self.batches = [self.collated(chunk) for chunk in self.chunks]
        self.batch = self.batches[0]
        self.real_n = sum(self.samples[i].num_nodes for i in self.chunks[0])
        variables = jax.eval_shape(
            lambda: self.model.init(jax.random.PRNGKey(0), self.batch, train=False))
        self.params = weights.make_weights(variables["params"], 13, bench_cfg["weights"])
        self.stats = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), variables["batch_stats"])
        self.stats = jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf + (jax.tree_util.keystr(path).endswith("['var']")),
            self.stats)
        self.flat = weights.flat_dict(self.params)
        self.flat_stats = weights.flat_dict(self.stats)
        self.hp = ref.hyperparameters(bench_cfg)
        self.optimizer = select_optimizer({"type": "AdamW", "learning_rate": 1e-4})
        self.opt = dict(bench_cfg["optimizer_reference"], learning_rate=1e-4)
        self.steps = [[[self.graphs[i] for i in chunk]] for chunk in self.chunks]
        self._followed = {}

    def collated(self, chunk, pad=None):
        return jax.tree.map(jnp.asarray, collate([self.samples[i] for i in chunk],
                                                 pad or self.pad))

    def routed(self, batch, route):
        """``flat``: the certificate says a graph may outgrow the dense
        blocks' width, so the step takes the flat masked form."""
        if route != "flat":
            return batch
        return batch.replace(meta=batch.meta._replace(max_n_node=2 * self.pad.n_node))

    def block(self, chunk):
        graphs = [self.graphs[i] for i in chunk]
        shape = ref_batch.one_shape([[graphs]])
        b = ref_batch.block_of(graphs, self.bench_cfg["input_scale"], shape,
                               far=2.0 * self.hp["radius"],
                               extras=functools.partial(ref.encodings, hp=self.hp))
        return {k: jnp.asarray(v) for k, v in b.items()}

    def followed(self, emulate=""):
        if emulate not in self._followed:
            self._followed[emulate] = ref_batch.follow(
                ref.node_energy, dict(self.hp, emulate=emulate), self.opt, self.flat, self.steps,
                self.bench_cfg["input_scale"], stats0=self.flat_stats)
        return self._followed[emulate]

    def trained(self, route):
        """Three steps of the program's own train step; ``lib/check.py``'s numbers."""
        import optax

        model = self.models[route.endswith("scanned")]
        step = make_mlip_train_step(model, self.optimizer)
        state = TrainState(params=jax.tree.map(jnp.copy, self.params),
                           batch_stats=jax.tree.map(jnp.copy, self.stats),
                           opt_state=self.optimizer.init(self.params),
                           step=jnp.zeros((), jnp.int32))
        captured = []
        for batch in self.batches:
            state, metrics = step(state, self.routed(batch, route))
            captured.append(jax.device_get(
                (state.params, state.opt_state, state.batch_stats, metrics["loss"])))
        return check.program_numbers(captured, self.flat, weights.flat_dict,
                                     lambda s: optax.tree_utils.tree_get(s, "mu"), self.opt["b1"])


@pytest.fixture(scope="module")
def case():
    return Case()


def test_the_batch_is_what_the_issue_asks(case):
    sizes = [s.num_nodes for s in case.samples]
    assert min(sizes) >= 3 and max(sizes) == 20 and len(set(sizes)) > 3
    assert all(s.num_edges == K * s.num_nodes for s in case.samples)
    spec = case.model.spec
    assert (spec.global_attn_engine, spec.global_attn_heads, spec.mpnn_type) == ("GPS", 4, "EGNN")
    # dense blocks as wide as the corpus's largest structure; the batches' certificate holds
    assert spec.max_graph_nodes >= 20 and spec.max_graph_nodes < case.pad.n_node
    assert all(b.meta.max_n_node <= spec.max_graph_nodes for b in case.batches)
    assert sorted(weights.flat_dict(case.stats)) == sorted(ref.initial_stats(case.flat))
    layer = {k.split("/", 1)[1] for k in case.flat if k.startswith("graph_convs_1/")}
    assert {"rel_pos_emb/kernel", "local/edge_mlp/dense_0/kernel", "attn/q/kernel",
            "norm1/scale", "norm3/bias", "mlp_0/kernel"} <= layer
    assert case.flat["graph_convs_1/local/edge_mlp/dense_0/kernel"].shape == (2 * 32 + 1 + 32, 32)
    assert case.flat["graph_convs_1/mlp_0/kernel"].shape == (32, 64)
    # the last layer moves no coordinates: it has no gate, which is what ends a scanned run
    assert "local/coord_mlp_mlp_0/kernel" in layer
    assert "graph_convs_2/local/coord_mlp_mlp_0/kernel" not in case.flat


def test_encodings_are_one_rule_on_both_sides(case):
    for g, s in zip(case.graphs, case.samples):
        made = ref.encodings(g, case.hp)
        assert np.array_equal(made["pe"], s.extras["pe"])
        assert np.array_equal(made["rel_pe"], s.extras["rel_pe"])
        assert np.array_equal(
            made["pe"], laplacian_pe(g["senders"], g["receivers"], len(g["z"]), 10))
        for column in made["pe"].T:  # the rule itself
            if np.abs(column).max() > 0:
                near = np.flatnonzero(np.abs(column) >= np.abs(column).max() * (1 - 1e-6))
                assert column[near[0]] > 0
    # a tie: +a and -a of equal size; a plain argmax picks by the last bit, the rule by the index
    tied = np.array([[0.5, -0.5], [-0.5 * (1 + 1e-8), 0.5 * (1 - 1e-8)], [0.1, 0.0]])
    for module in (ref, None):
        fixed = (module.fix_signs if module else fix_signs)(tied)
        assert fixed[0, 0] > 0 and fixed[0, 1] > 0
    small = ref.encodings({"z": np.arange(3), "senders": np.array([0, 1, 2]),
                           "receivers": np.array([1, 2, 0])}, case.hp)["pe"]
    assert small.shape == (3, 10) and not small[:, 2:].any()  # too few atoms: zero columns


def test_encodings_are_the_same_across_two_collations(case):
    """Structure 5 beside other neighbours, at another offset, under another
    pad: the rows it brings are the rows it brought."""
    a = case.collated([4, 5, 6, 7])
    b = case.collated([5, 0, 11], compute_pad_spec(case.samples, 3))
    n5, n4 = case.samples[5].num_nodes, case.samples[4].num_nodes
    e5, e4 = case.samples[5].num_edges, case.samples[4].num_edges
    assert np.array_equal(a.pe[n4:n4 + n5], b.pe[:n5])
    assert np.array_equal(a.rel_pe[e4:e4 + e5], b.rel_pe[:e5])
    assert np.abs(np.asarray(b.pe[:n5])).max() > 0


@pytest.mark.parametrize("route", ROUTES[:2])
def test_energies_forces_and_statistics_match_the_reference(case, route):
    """One training-mode call. fp32 tolerances: 2e-5 of the largest value for
    the energies, 1e-4 for forces and statistics (measured 1.3e-6 / 6e-6 /
    2e-5: ten dense layers and three normalisations a layer deep, summed in
    different orders)."""
    batch, n = case.routed(case.batch, route), case.real_n
    variables = {"params": case.params, "batch_stats": case.stats}

    def program_energy(pos):
        out, upd = case.model.apply(variables, batch.replace(pos=pos), train=True,
                                    mutable=["batch_stats"])
        e = out[0][:, 0] * batch.node_mask
        return e.sum(), (e, upd["batch_stats"])

    (_, (e, stats)), de = jax.jit(jax.value_and_grad(program_energy, has_aux=True))(batch.pos)
    block = case.block(case.chunks[0])

    def reference_energy(pos):
        e, new = ref.node_energy(case.flat, case.hp, block, pos, case.flat_stats)
        return (e * block["atom"]).sum(), (e, new)

    (_, (e_ref, stats_ref)), de_ref = jax.jit(
        jax.value_and_grad(reference_energy, has_aux=True))(block["pos"])
    assert np.abs(e_ref[:n]).max() > 1e-2 and np.abs(de_ref[:n]).max() > 1e-2
    np.testing.assert_allclose(e[:n], e_ref[:n], rtol=0, atol=2e-5 * np.abs(e_ref[:n]).max())
    np.testing.assert_allclose(de[:n], de_ref[:n], rtol=0, atol=1e-4 * np.abs(de_ref[:n]).max())
    assert np.all(np.asarray(de[n:]) == 0.0)  # a padded atom feels nothing, exactly
    got = weights.flat_dict(stats)
    assert set(got) == set(stats_ref) and len(got) == 3 * 3 * 2
    for name, want in stats_ref.items():
        assert not np.allclose(want, case.flat_stats[name])  # they moved
        np.testing.assert_allclose(got[name], want, rtol=0,
                                   atol=1e-4 * max(np.abs(want).max(), 1e-2), err_msg=name)


@pytest.mark.parametrize("route", ROUTES)
def test_three_optimizer_steps_match_the_reference(case, route):
    """Loss of each step, first gradient, the parameters' change and the batch
    statistics after the third AdamW step, leaf by leaf, as a benchmark run
    compares them; the three routes give one step."""
    got = case.trained(route)
    ok, rows = check.compare(got, case.followed(), LIMITS)
    assert ok, rows
    assert [r["name"] for r in rows] == ["loss", "grad_norm", "change_norm", "stats_norm"]


def test_lower_precision_control_fails_the_same_tolerances(case):
    """The reference with its dense products made from one bfloat16 pass and
    from three (``high``), put in the program's place: one pass fails every
    number, three fail by the loss and the first gradient."""
    want = case.followed()
    ok, rows = check.compare(case.followed("default"), want, LIMITS)
    assert not ok and not any(r["ok"] for r in rows), rows
    ok, rows = check.compare(case.followed("high"), want, LIMITS)
    assert not ok and [r["name"] for r in rows if not r["ok"]] == ["loss", "grad_norm"], rows


def test_the_leaves_the_loss_cannot_move(case):
    """Five biases a layer have a gradient of rounding alone; the reference
    names them, and leaves them out of ``change_norm`` only after its own
    first gradient shows each under a thousandth of the median leaf's."""
    want = case.followed()
    named = ref.unmoved(case.hp)
    assert len(named) == 5 * 3 and set(named) <= set(case.flat)
    floor = 1e-3 * np.median(list(want["grad_norm"].values()))
    assert all(want["grad_norm"][k] < floor for k in named)
    assert set(want["grad_norm"]) - set(want["change_norm"]) == set(named)


@pytest.mark.parametrize("route", ROUTES[:2])
def test_attention_never_crosses_graphs(case, route):
    """Inference mode (running statistics: batch statistics couple a step's
    graphs by design). Moving the second structure's atoms and features
    leaves every other structure's energies exactly where they were; and
    attention does reach across a structure: an atom's energy moves when an
    atom beyond its neighbours' neighbours does."""
    batch = case.routed(case.batch, route)
    variables = {"params": case.params, "batch_stats": case.stats}
    energies = jax.jit(lambda b: case.model.apply(variables, b, train=False)[0][:, 0])
    e0 = np.asarray(energies(batch))
    second = np.asarray(batch.batch) == 1
    moved = batch.replace(x=jnp.where(second[:, None], batch.x * 1.5, batch.x),
                          pos=jnp.where(second[:, None], batch.pos + 0.3, batch.pos))
    e1 = np.asarray(energies(moved))
    real = np.asarray(batch.node_mask) > 0
    assert np.array_equal(e1[~second & real], e0[~second & real])
    assert np.abs(e1[second] - e0[second]).max() > 1e-4


def test_an_unmasked_softmax_is_another_model(case):
    """The control of the benchmark's limits: with every key allowed the
    energies move by far more than rounding."""
    block = case.block(case.chunks[0])
    e, _ = ref.node_energy(case.flat, case.hp, block, block["pos"], case.flat_stats)
    one_graph = dict(block, graph=jnp.where(block["atom"] > 0, 0, block["graph"]))
    e_blind, _ = ref.node_energy(case.flat, case.hp, one_graph, block["pos"], case.flat_stats)
    n = case.real_n
    assert np.abs(np.asarray(e_blind - e)[:n]).max() > 1e-2 * np.abs(np.asarray(e)[:n]).max()
