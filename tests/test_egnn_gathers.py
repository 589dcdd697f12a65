"""EGNN reads node rows on the edges through ``segment.gather``
(``models/egnn.py``). Held here: energies, forces and the parameter gradient
of the force loss equal the plain-indexing layer's; no scatter-add onto
``[N, hidden]`` or ``[N, 3]`` rows is left to autodiff in that gradient, so
plain indexing cannot silently come back; lowered for a TPU, the derivative
passes hold the gathers' transposes as ``fused_segment_sum`` calls; and the
energy is invariant, the forces equivariant, under a rigid motion."""

import collections
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.config import update_config
from hydragnn_tpu.datasets import lennard_jones_data
from hydragnn_tpu.graphs import segment
from hydragnn_tpu.graphs.batching import collate, compute_pad_spec
from hydragnn_tpu.models import create_model_config, init_model
from hydragnn_tpu.models.mlip import (
    energy_force_loss,
    make_energy_and_forces,
    make_mlip_train_step,
)
from hydragnn_tpu.preprocess import apply_variables_of_interest
from hydragnn_tpu.train import create_train_state, select_optimizer

from test_forces import MLIP_CONFIG
from test_painn_message_layout import _equations, mosaic_calls_by_pass

STACKS = [(2, True), (2, False), (3, True), (3, False)]  # layers, equivariance


def build(layers, equivariance, hidden=16, n_samples=4, n_real=3):
    """An LJ EGNN potential and ``n_real`` graphs in a bucket of ``n_samples``:
    padded nodes, edges and graphs beside the dummy ones."""
    cfg = copy.deepcopy(MLIP_CONFIG)
    cfg["NeuralNetwork"]["Architecture"].update(
        num_conv_layers=layers, equivariance=equivariance, hidden_dim=hidden)
    samples = lennard_jones_data(number_configurations=n_samples, cells_per_dim=2, seed=3)
    samples = apply_variables_of_interest(samples, cfg)
    cfg = update_config(cfg, samples)
    model = create_model_config(cfg)
    pad = compute_pad_spec(samples, n_samples)
    batch = jax.tree.map(jnp.asarray, collate(samples[:n_real], pad))
    assert float(batch.edge_mask.sum()) < batch.edge_mask.shape[0]
    return model, batch, cfg


def plain_gather(x, ids, hints=None, fits=None):
    """What the layer read its rows by before: autodiff transposes it itself."""
    return x[ids]


def force_loss(model):
    eaf = make_energy_and_forces(model)

    def loss(params, batch):
        graph_e, forces = eaf({"params": params}, batch)
        return energy_force_loss(model.spec, graph_e, forces, batch)[0]

    return loss


def energy_and_forces(model, params, batch):
    return make_energy_and_forces(model)({"params": params}, batch)


QUANTITIES = {
    "energy": lambda *args: energy_and_forces(*args)[0],
    "forces": lambda *args: energy_and_forces(*args)[1],
    "force_loss_grad": lambda model, params, batch: jax.grad(force_loss(model))(params, batch),
}


@pytest.mark.parametrize("quantity", list(QUANTITIES))
@pytest.mark.parametrize("layers,equivariance", STACKS)
def test_gathered_rows_match_plain_indexing(monkeypatch, layers, equivariance, quantity):
    model, batch, _ = build(layers, equivariance)
    params = init_model(model, batch)["params"]
    got = QUANTITIES[quantity](model, params, batch)
    monkeypatch.setattr(segment, "gather", plain_gather)
    want = QUANTITIES[quantity](model, params, batch)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert np.all(np.isfinite(np.asarray(g)))
        scale = float(jnp.max(jnp.abs(w))) + 1e-30
        np.testing.assert_allclose(np.asarray(g) / scale, np.asarray(w) / scale,
                                   rtol=0, atol=1e-6)


def marker_sum(data, ids, num_segments, fits):
    """``segment._sum`` as a one-hot product: it and its transposes hold no
    scatter-add, so every one left in a jaxpr is autodiff's own."""
    onehot = jax.nn.one_hot(ids, num_segments, dtype=data.dtype)
    return jnp.einsum("en,e...->n...", onehot, data)


def autodiff_scatters(model, params, batch):
    """Scatter-adds of the force loss's parameter gradient, by the row width
    of the ``[N, .]`` array they add into."""
    jaxpr = jax.make_jaxpr(jax.grad(force_loss(model)))(params, batch)
    found = collections.Counter()
    for eqn in _equations(jaxpr.jaxpr):
        if eqn.primitive.name == "scatter-add":
            shape = eqn.invars[0].aval.shape
            if len(shape) == 2 and shape[0] == batch.num_nodes:
                found[shape[1]] += 1
    return found


# (layers, equivariance) -> scatter-adds onto [N, 3] and [N, hidden] that plain
# indexing leaves: the forces pass transposes two position reads a layer and two
# feature reads a layer but the first (whose rows no position moves), the
# parameter gradient of the forward pass as many, less the first layer's, again
# where the layers before moved what is read (positions: with equivariance only)
PLAIN_SCATTERS = {(2, True): (6, 4), (2, False): (4, 4), (3, True): (10, 8), (3, False): (6, 8)}


@pytest.mark.parametrize("layers,equivariance", STACKS)
def test_no_row_scatter_is_left_to_autodiff(monkeypatch, layers, equivariance):
    hidden = 16
    model, batch, _ = build(layers, equivariance, hidden)
    assert len({batch.num_nodes, batch.senders.shape[0], hidden, 3}) == 4
    params = init_model(model, batch)["params"]
    monkeypatch.setattr(segment, "_sum", marker_sum)
    found = autodiff_scatters(model, params, batch)
    assert (found[3], found[hidden]) == (0, 0), found
    # the walker finds what it is there to find
    monkeypatch.setattr(segment, "gather", plain_gather)
    found = autodiff_scatters(model, params, batch)
    assert (found[3], found[hidden]) == PLAIN_SCATTERS[layers, equivariance], found


# fused_segment_sum calls of a 3-layer step lowered for a TPU, by AD pass. Plain
# indexing (the step before): the layers' three feature and two coordinate sums,
# and their transposes' transposes. With segment.gather the forces pass adds the
# transposes of four reads a layer less the first layer's two feature reads, the
# forward pass's parameter gradient those of the layers after the first; the
# parameter gradient of the forces pass transposes those sums into gathers
PASSES = {"forward": "jvp(jvp(HydraModel))",
          "forces": "jvp(transpose(jvp(jvp(HydraModel))))",
          "grad.forward": "transpose(jvp(jvp(HydraModel)))",
          "grad.forces": "transpose(jvp(transpose(jvp(jvp(HydraModel)))))"}
PLAIN_CALLS = {"forward": 5, "forces": 0, "grad.forward": 0, "grad.forces": 5}
GATHER_CALLS = {"forward": 5, "forces": 10, "grad.forward": 8, "grad.forces": 5}


@pytest.mark.parametrize("reads", ["plain", "gather"])
def test_tpu_step_sums_the_gathers_transposes_in_the_kernel(monkeypatch, reads):
    """264 node slots and both certificates held: the resident form takes the
    ``[E, 16]`` and ``[E, 3]`` cotangents alike. Lowered for a TPU (no chip:
    StableHLO), the forces pass and the forward pass's parameter gradient hold
    the reads' transposes as kernel calls, where the plain-indexing step, whose
    counts are pinned beside them, holds none."""
    model, batch, cfg = build(3, True, n_samples=32, n_real=32)
    assert batch.num_nodes >= 128 and batch.num_nodes % 8 == 0
    assert batch.meta.send_fits and batch.meta.recv_fits
    opt = select_optimizer(cfg["NeuralNetwork"]["Training"]["Optimizer"])
    state = create_train_state(model, opt, batch)
    if reads == "plain":
        monkeypatch.setattr(segment, "gather", plain_gather)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # routes, interpret off
    calls = mosaic_calls_by_pass(make_mlip_train_step(model, opt), state, batch)
    assert set(calls) <= set(PASSES.values()), calls
    calls = {name: calls[scope] for name, scope in PASSES.items()}
    assert calls == (PLAIN_CALLS if reads == "plain" else GATHER_CALLS)


def rigid_motion(seed=5):
    q, _ = np.linalg.qr(np.random.default_rng(seed).normal(size=(3, 3)))
    if np.linalg.det(q) < 0:
        q[:, 0] *= -1
    return jnp.asarray(q, jnp.float32), jnp.asarray([0.7, -1.3, 2.1], jnp.float32)


@pytest.mark.parametrize("layers,equivariance", STACKS)
def test_energy_invariant_forces_equivariant(layers, equivariance):
    model, batch, _ = build(layers, equivariance)
    variables = init_model(model, batch)
    eaf = jax.jit(make_energy_and_forces(model))
    rotation, shift = rigid_motion()
    moved = batch.replace(pos=batch.pos @ rotation.T + shift,
                          edge_shifts=batch.edge_shifts @ rotation.T)
    e0, f0 = eaf(variables, batch)
    e1, f1 = eaf(variables, moved)
    assert float(jnp.max(jnp.abs(f0))) > 0
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e0), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f0 @ rotation.T), rtol=1e-3, atol=1e-5)
