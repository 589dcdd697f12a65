"""SchNet (hidden 32, 16 filters, 20 Gaussians, 5 layers, 8 neighbours) against
the benchmark's plain reference (``benchmark/reference/schnet.py``: gather,
multiply, ``jax.ops.segment_sum``; imports nothing of the program) on one
padded batch of periodic structures of 2-20 atoms: per-atom energies, forces,
the parameter gradient of the force loss, and three AdamW steps of
``make_mlip_train_step`` against ``reference/mlip_padded.py::follow``. Every
comparison on each route the gather-multiply-sum can take: XLA's form, and the
Pallas kernel (interpreted here) with the layout certificate ``gs_fits`` True
(kernel alone), False (the declared pair ``segment.gather`` / ``segment_sum``:
XLA's sums at 16 filters) and None (both behind a ``lax.cond``). A control in lower matmul precision fails the same tolerances;
a rotated and translated copy gives the same energies and rotated forces.
"""

import copy
import importlib.util
import json
import os

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.config import update_config
from hydragnn_tpu.graphs.batching import PadSpec, collate
from hydragnn_tpu.models import create_model_config
from hydragnn_tpu.models.mlip import make_mlip_train_step
from hydragnn_tpu.train import select_optimizer
from hydragnn_tpu.train.step import TrainState

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K = 8  # neighbours an atom


def _bench(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location("bench_" + "_".join(parts)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _bench("reference", "schnet.py")
ref_mlip = _bench("reference", "mlip.py")
ref_padded = _bench("reference", "mlip_padded.py")
weights = _bench("lib", "weights.py")
check = _bench("lib", "check.py")
crystals = _bench("generators", "crystals.py")
program = _bench("lib", "program.py")
cells = _bench("lib", "cells.py")

CRYSTALS = {"count": 12, "radius": 6.0, "max_neighbours": K, "volume_per_atom": 14.0,
            "n_species": 83, "sizes": {"seed": 0, "median": 6, "sigma": 0.6, "min": 2,
                                       "max": 20, "max_at": 3}}
# (forced kernel flag, gs_fits): the routes of ``ops/fused_scatter.py::gather_scatter_sum``
ROUTES = {"xla": ("0", "as collated"), "kernel": ("1", True), "kernel_refused": ("1", False),
          "kernel_or_xla_in_program": ("1", None)}
# fp32 tolerances of the three-step comparison (``lib/check.py``'s three numbers): 5-7 x
# the largest sound reading over the four routes (1.5e-7 / 7.2e-7 / 7.8e-6). One emulated
# bfloat16 pass reads 6.5e-5 / 5.7e-3 / 7.1e-3, three passes 5.9e-7 / 2.3e-5 / 9.6e-6
LIMITS = {"loss": 1e-6, "grad_norm": 4e-6, "change_norm": 5e-5}


def bench_config():
    """The cell's configuration at its rehearsal sizes (hidden 32, 16 filters,
    20 Gaussians, 8 neighbours; ``filter1`` seeded for 20 Gaussians)."""
    with open(os.path.join(ROOT, "benchmark", "configs", "schnet_mlip_oc20.json")) as f:
        cfg = json.load(f)
    cfg = cells._merge(cfg, cfg["rehearse"])
    arch = cfg["NeuralNetwork"]["Architecture"]
    assert (arch["hidden_dim"], arch["num_filters"], arch["num_gaussians"],
            arch["max_neighbours"]) == (32, 16, 20, K)
    return cfg


class Case:
    """Model, seeded weights, three padded batches of four structures (264
    node slots: the kernel's window is 256 rows), and jitted functions of
    program and reference."""

    def __init__(self):
        bench_cfg = bench_config()
        self.bench_cfg = bench_cfg
        cfg = {k: copy.deepcopy(bench_cfg[k]) for k in program.PROGRAM_KEYS if k in bench_cfg}
        self.graphs = crystals.generate(CRYSTALS, 2**31 + 11)
        self.samples = program.to_samples(self.graphs, bench_cfg["input_scale"])
        self.cfg = update_config(cfg, self.samples)
        self.model = create_model_config(self.cfg)
        self.chunks = [list(range(i, i + 4)) for i in (0, 4, 8)]
        self.pad = PadSpec(n_node=264, n_edge=1024, n_graph=5)
        self.batches = [self.collated(chunk) for chunk in self.chunks]
        self.batch = self.batches[0]
        self.real_n = sum(self.samples[i].num_nodes for i in self.chunks[0])
        shapes = jax.eval_shape(
            lambda: self.model.init(jax.random.PRNGKey(0), self.batch, train=False))
        self.params = weights.make_weights(shapes["params"], 13, bench_cfg["weights"])
        self.flat = weights.flat_dict(self.params)
        self.hp = ref.hyperparameters(bench_cfg)
        self.real = {k: jnp.asarray(v) for k, v in ref_mlip.concat(
            [self.graphs[i] for i in self.chunks[0]], bench_cfg["input_scale"]).items()}
        self.optimizer = select_optimizer({"type": "AdamW", "learning_rate": 1e-4})
        self.opt = dict(bench_cfg["optimizer_reference"], learning_rate=1e-4)
        self.steps = [[[self.graphs[i] for i in chunk]] for chunk in self.chunks]
        self._reference = None
        self._followed = {}

    def collated(self, chunk, pad=None):
        return jax.tree.map(jnp.asarray, collate([self.samples[i] for i in chunk],
                                                 pad or self.pad))

    def routed(self, batch, fits):
        return batch if fits == "as collated" else batch.replace(
            meta=batch.meta._replace(gs_fits=fits))

    def program(self, batch):
        """(node energies, forces, d force-loss / d params), traced anew: the
        route is read from the environment and the batch's meta at trace time."""
        def energies(p, pos):
            return self.model.apply({"params": p}, batch.replace(pos=pos), train=False)[0][:, 0]

        def forces_of(p):
            return -jax.grad(lambda pos: (energies(p, pos) * batch.node_mask).sum())(batch.pos)

        def force_loss(p):
            return (((forces_of(p) - batch.forces_y) ** 2) * batch.node_mask[:, None]).sum()

        return jax.device_get(jax.jit(lambda p: (
            energies(p, batch.pos), forces_of(p), jax.grad(force_loss)(p)))(self.params))

    @property
    def reference(self):
        if self._reference is None:
            b = self.real

            def energies(p, pos):
                return ref.node_energy(p, self.hp, b["x"], pos, b["senders"], b["receivers"],
                                       b["shifts"])

            def forces_of(p):
                return -jax.grad(lambda pos: energies(p, pos).sum())(b["pos"])

            self._reference = jax.device_get(jax.jit(lambda p: (
                energies(p, b["pos"]), forces_of(p),
                jax.grad(lambda q: ((forces_of(q) - b["forces"]) ** 2).sum())(p)))(self.flat))
        return self._reference

    def followed(self, emulate=""):
        if emulate not in self._followed:
            self._followed[emulate] = ref_padded.follow(
                ref.node_energy, dict(self.hp, emulate=emulate), self.opt, self.flat, self.steps,
                self.bench_cfg["input_scale"])
        return self._followed[emulate]

    def trained(self, fits):
        """Three steps of the program's own train step; ``lib/check.py``'s numbers."""
        step = make_mlip_train_step(self.model, self.optimizer)
        state = TrainState(params=jax.tree.map(jnp.copy, self.params), batch_stats={},
                           opt_state=self.optimizer.init(self.params),
                           step=jnp.zeros((), jnp.int32))
        captured = []
        for batch in self.batches:
            state, metrics = step(state, self.routed(batch, fits))
            captured.append(jax.device_get((state.params, state.opt_state, metrics["loss"])))
        import optax

        return check.program_numbers(captured, self.flat, weights.flat_dict,
                                     lambda s: optax.tree_utils.tree_get(s, "mu"), self.opt["b1"])


@pytest.fixture(scope="module")
def case():
    return Case()


@pytest.fixture(params=sorted(ROUTES))
def route(request, monkeypatch):
    flag, fits = ROUTES[request.param]
    monkeypatch.setenv("HYDRAGNN_FUSED_SCATTER", flag)
    return request.param, fits


def test_the_batch_is_what_the_issue_asks(case):
    sizes = [s.num_nodes for s in case.samples]
    assert min(sizes) >= 2 and max(sizes) == 20 and len(set(sizes)) > 3
    assert all(s.num_edges == K * s.num_nodes for s in case.samples)
    assert all(b.meta.gs_fits is True for b in case.batches)  # collate certifies these
    assert case.model.spec.activation == "shifted_softplus"
    assert set(case.flat) == {
        f"graph_convs_{i}/{leaf}" for i in range(5) for leaf in (
            "filter1/kernel", "filter1/bias", "filter2/kernel", "filter2/bias", "lin1/kernel",
            "lin2/kernel", "lin2/bias")} | {
        f"head0_branch-0/dense_{i}/{leaf}" for i in (0, 1) for leaf in ("kernel", "bias")}
    assert case.flat["graph_convs_0/lin1/kernel"].shape == (1, 16)  # no atom embedding
    assert case.flat["graph_convs_1/lin1/kernel"].shape == (32, 16)


def test_the_kernel_is_in_the_program_where_the_route_says(case, route, monkeypatch):
    """What each route traces: the Pallas call alone, XLA's form alone, or both
    behind a ``lax.cond``."""
    from hydragnn_tpu.ops import fused_scatter as fs

    name, fits = route
    seen = []
    for fn in ("_pallas_gather_scatter", "reference_gather_scatter", "pair_gather_scatter"):
        inner = getattr(fs, fn)
        monkeypatch.setattr(fs, fn, lambda *a, _inner=inner, _fn=fn: (seen.append(_fn),
                                                                       _inner(*a))[1])
    batch = case.routed(case.batch, fits)
    case.model.apply({"params": case.params}, batch, train=False)
    assert set(seen) == {
        "xla": {"reference_gather_scatter"}, "kernel": {"_pallas_gather_scatter"},
        "kernel_refused": {"pair_gather_scatter"},  # XLA's sums at 16 filters, on the pair
        "kernel_or_xla_in_program": {"_pallas_gather_scatter", "reference_gather_scatter"},
    }[name]


@pytest.mark.parametrize("what", ["energy", "forces", "force_loss_gradient"])
def test_program_matches_reference(case, route, what):
    """fp32 tolerances: 1e-5 of the largest value for energies, 5e-5 for forces
    and for every leaf of the grad-of-grad (measured 3e-7 / 2e-6 / 8e-6: the two
    sides sum 8 neighbours in different orders, and the kernel sums them as
    three-term bf16 splits on one-hot products)."""
    _, fits = route
    got = case.program(case.routed(case.batch, fits))
    want = case.reference
    n = case.real_n
    if what == "energy":
        assert np.abs(want[0]).max() > 1e-3
        np.testing.assert_allclose(got[0][:n], want[0], rtol=0, atol=1e-5 * np.abs(want[0]).max())
    elif what == "forces":
        assert np.abs(want[1]).max() > 1e-2
        np.testing.assert_allclose(got[1][:n], want[1], rtol=0, atol=5e-5 * np.abs(want[1]).max())
        assert np.all(got[1][n:] == 0.0)  # a padded atom feels nothing, exactly
    else:
        flat = weights.flat_dict(got[2])
        assert set(flat) == set(want[2])
        for leaf, g in want[2].items():
            # every weight reaches the forces but the last bias, an energy offset
            assert np.abs(g).max() > 0 or leaf == "head0_branch-0/dense_1/bias", leaf
            assert np.abs(flat[leaf] - g).max() <= 5e-5 * np.abs(g).max() + 1e-9, leaf


def test_three_optimizer_steps_match_the_reference(case, route):
    """Loss of each step, first gradient and the parameters' change after the
    third AdamW step, leaf by leaf, as a benchmark run compares them."""
    _, fits = route
    ok, rows = check.compare(case.trained(fits), case.followed(), LIMITS)
    assert ok, rows


def test_lower_precision_control_fails_the_same_tolerances(case):
    """The reference with its products made from one bfloat16 pass (what a dense
    layer does on a TPU when nothing sets a precision) and from three (``high``),
    put in the program's place: one pass fails all three numbers, three passes
    fail by the first gradient (as on the chip: PERF.md section 2)."""
    want = case.followed()
    ok, rows = check.compare(case.followed("default"), want, LIMITS)
    assert not ok and not any(r["ok"] for r in rows), rows
    ok, rows = check.compare(case.followed("high"), want, LIMITS)
    assert not ok and [r["name"] for r in rows if not r["ok"]] == ["grad_norm"], rows


def test_a_dropped_cutoff_is_another_model(case):
    """The control of the benchmark's limits: without the cosine window the
    energies move by tens of percent, not by rounding."""
    b = case.real
    e = case.reference[0]
    e_bare = ref.node_energy(case.flat, dict(case.hp, no_cutoff=1), b["x"], b["pos"],
                             b["senders"], b["receivers"], b["shifts"])
    assert np.abs(np.asarray(e_bare) - e).max() > 0.1 * np.abs(e).max()


@pytest.mark.parametrize("what", ["nodes", "edges"])
def test_padding_adds_nothing(case, what):
    e0, f0, g0 = case.program(case.batch)
    pad = {"nodes": PadSpec(n_node=400, n_edge=1024, n_graph=5),
           "edges": PadSpec(n_node=264, n_edge=2048, n_graph=5)}[what]
    e1, f1, g1 = case.program(case.collated(case.chunks[0], pad))
    n = case.real_n
    np.testing.assert_allclose(e1[:n], e0[:n], rtol=0, atol=2e-6 * np.abs(e0).max())
    np.testing.assert_allclose(f1[:n], f0[:n], rtol=0, atol=5e-6 * np.abs(f0).max())
    assert np.all(f1[n:] == 0.0)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * max(np.abs(b).max(), 1e-12))


def _rotation(seed: int, improper: bool):
    q, _ = np.linalg.qr(np.random.default_rng(seed).normal(size=(3, 3)))
    q = q * np.sign(np.linalg.det(q))
    return jnp.asarray(-q if improper else q, jnp.float32)


@pytest.mark.parametrize("improper", [False, True])
def test_energy_invariant_forces_equivariant(case, improper):
    """A rotated (or reflected) and translated copy: the same energies, the
    rotated forces."""
    e0, f0, _ = case.program(case.batch)
    r = _rotation(3, improper)
    shift = jnp.asarray([1.5, -0.25, 3.0], jnp.float32)
    moved = case.batch.replace(pos=case.batch.pos @ r.T + shift,
                               edge_shifts=case.batch.edge_shifts @ r.T)
    e1, f1, _ = case.program(moved)
    np.testing.assert_allclose(e1, e0, rtol=0, atol=2e-5 * np.abs(e0).max())
    np.testing.assert_allclose(f1, f0 @ np.asarray(r).T, rtol=0, atol=1e-4 * np.abs(f0).max())


def test_geometry_and_smearing_are_traced_once_a_call(case):
    """The first layer makes the edge basis and the later layers are handed it:
    one exponential of an ``[E, 20]`` array in the forward pass, not five, and
    one scatter of ``[E, 3]`` cotangents onto the atoms an endpoint in the force
    pass, not one a layer (the compiler merges the former by itself, never the
    latter: PERF.md section 6)."""
    text = str(jax.make_jaxpr(
        lambda p: case.model.apply({"params": p}, case.batch, train=False))(case.params))
    assert text.count("f32[1024,20] = exp") == 1
    assert text.count("cos ") == 1  # the cutoff window
    force = str(jax.make_jaxpr(jax.grad(lambda pos: case.model.apply(
        {"params": case.params}, case.batch.replace(pos=pos), train=False)[0][0].sum()))(
            case.batch.pos))
    assert len(re.findall(r"f32\[\d+,3\] = scatter-add", force)) == 2


def test_tpu_step_without_gs_fits_sums_rows_in_the_tiled_kernel_in_every_pass(case, monkeypatch):
    """128 filters (whole lanes) and a batch whose ``gs_fits`` certificate
    failed: the energy-and-force step as built for a TPU holds one tiled
    ``fused_segment_sum`` call where XLA's form holds a scatter-add onto
    ``[N, 128]`` rows (the five layers' sums, the transposes of
    ``dout[receivers]`` and of ``h[senders]``: nineteen), none of those
    scatter-adds, and the kernel in each of the four AD passes."""
    from hydragnn_tpu.train import create_train_state

    from test_fused_scatter import _row_scatters
    from test_painn_message_layout import mosaic_calls_by_pass

    cfg = copy.deepcopy(case.cfg)
    cfg["NeuralNetwork"]["Architecture"]["num_filters"] = 128
    model = create_model_config(cfg)
    batch = case.routed(case.batch, False)
    n = batch.num_nodes

    row_scatters = lambda jaxpr: _row_scatters(jaxpr, n, 128)

    state = create_train_state(model, case.optimizer, batch)
    monkeypatch.setenv("HYDRAGNN_FUSED_SCATTER", "0")
    xla = jax.make_jaxpr(make_mlip_train_step(model, case.optimizer))(state, batch)
    layers = cfg["NeuralNetwork"]["Architecture"]["num_conv_layers"]
    assert row_scatters(xla) == 4 * layers - 1 == 19
    monkeypatch.delenv("HYDRAGNN_FUSED_SCATTER")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # routes, interpret off
    step = make_mlip_train_step(model, case.optimizer)
    assert row_scatters(jax.make_jaxpr(step)(state, batch)) == 0
    passes = mosaic_calls_by_pass(step, state, batch)
    assert sum(passes.values()) == 19 and len(passes) == 4, passes
