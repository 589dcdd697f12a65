"""Edge-sharded long-context execution of full models (parallel/large_graph):
GSPMD partitions every conv stack's gather/transform/scatter over the edge
dimension; parity vs single-device and an end-to-end config-routed run."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hydragnn_tpu
from hydragnn_tpu.config import update_config
from hydragnn_tpu.datasets import deterministic_graph_data
from hydragnn_tpu.graphs.batching import collate, compute_pad_spec
from hydragnn_tpu.graphs.graph import GraphSample
from hydragnn_tpu.graphs.radius import radius_graph
from hydragnn_tpu.models import create_model_config, init_model
from hydragnn_tpu.parallel import make_mesh, shard_state
from hydragnn_tpu.parallel.large_graph import (
    make_edge_sharded_apply,
    make_edge_sharded_train_step,
    put_large_batch,
)
from hydragnn_tpu.preprocess import apply_variables_of_interest
from hydragnn_tpu.train import create_train_state, make_train_step, select_optimizer

from test_config import CI_CONFIG


def build(mpnn_type="GIN", giant=False):
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Architecture"]["mpnn_type"] = mpnn_type
    if giant:
        # one big structure instead of many small ones
        rng = np.random.default_rng(7)
        samples = []
        for i in range(4):
            n = 400
            pos = rng.uniform(0, 12.0, size=(n, 3))
            s, r, sh = radius_graph(pos, radius=2.5, max_neighbours=10)
            x = np.concatenate(
                [rng.integers(0, 3, (n, 1)), rng.normal(size=(n, 3))], axis=1
            ).astype(np.float32)
            samples.append(
                GraphSample(
                    x=x, pos=pos, senders=s, receivers=r, edge_shifts=sh,
                    graph_y=rng.normal(size=(1,)),
                    node_y=rng.normal(size=(n, 1)),
                )
            )
    else:
        samples = deterministic_graph_data(number_configurations=8, seed=13)
    samples = apply_variables_of_interest(samples, cfg)
    cfg = update_config(cfg, samples)
    model = create_model_config(cfg)
    pad = compute_pad_spec(samples, len(samples))
    batch = collate(samples, pad)
    return model, batch, cfg


@pytest.mark.parametrize("mpnn_type", ["GIN", "SAGE", "PNA"])
def test_edge_sharded_forward_matches_single_device(mpnn_type):
    model, host_batch, _ = build(mpnn_type, giant=True)
    mesh = make_mesh(n_data=8, n_branch=1)
    dev_batch = jax.tree.map(jnp.asarray, host_batch)
    variables = init_model(model, dev_batch)

    single = model.apply(variables, dev_batch, train=False)
    sharded_batch = put_large_batch(host_batch, mesh)
    sharded = make_edge_sharded_apply(model, mesh)(variables, sharded_batch)
    for a, b in zip(jax.tree.leaves(single), jax.tree.leaves(sharded)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5
        )


def test_edge_sharded_train_step_matches_single_device():
    model, host_batch, cfg = build("GIN", giant=True)
    mesh = make_mesh(n_data=8, n_branch=1)
    # SGD: parameter deltas stay proportional to gradients, so cross-device
    # reduction-order noise can't flip near-zero Adam updates
    opt = select_optimizer({"type": "SGD", "learning_rate": 0.01})
    dev_batch = jax.tree.map(jnp.asarray, host_batch)

    state0 = create_train_state(model, opt, dev_batch)
    step_single = make_train_step(model, opt)
    s1, m1 = step_single(state0, dev_batch)

    state0b = create_train_state(model, opt, dev_batch)
    state0b = shard_state(state0b, mesh)
    step_sharded = make_edge_sharded_train_step(model, opt, mesh)
    s2, m2 = step_sharded(state0b, put_large_batch(host_batch, mesh))

    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-4)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5)


def test_edge_sharding_reachable_from_config(monkeypatch):
    """NeuralNetwork.Architecture.edge_sharding routes run_training through
    the long-context path end-to-end on the 8-device mesh."""
    monkeypatch.setenv("HYDRAGNN_AUTO_PARALLEL", "1")
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Architecture"]["edge_sharding"] = True
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 3
    samples = deterministic_graph_data(number_configurations=48, seed=19)
    state, model, aug = hydragnn_tpu.run_training(cfg, samples=samples)
    assert int(np.asarray(state.step)) > 0
    err, tasks, trues, preds = hydragnn_tpu.run_prediction(
        cfg, state, model, samples=samples
    )
    assert np.isfinite(err)


def test_node_and_edge_sharded_forward_matches_single_device():
    """Fully-sharded giant-graph mode (nodes AND edges split over the mesh):
    at-rest node memory is 1/D per device; results identical."""
    model, host_batch, _ = build("GIN", giant=True)
    mesh = make_mesh(n_data=8, n_branch=1)
    dev_batch = jax.tree.map(jnp.asarray, host_batch)
    variables = init_model(model, dev_batch)

    single = model.apply(variables, dev_batch, train=False)
    sharded_batch = put_large_batch(host_batch, mesh, shard_nodes=True)
    # node arrays actually sharded (leading-dim split)
    x_shard = sharded_batch.x.addressable_shards[0].data
    assert x_shard.shape[0] == sharded_batch.x.shape[0] // 8
    sharded = make_edge_sharded_apply(model, mesh)(variables, sharded_batch)
    # padding may extend N; compare the common (real) prefix per output kind
    for a, b in zip(jax.tree.leaves(single), jax.tree.leaves(sharded)):
        n = min(a.shape[0], b.shape[0])
        np.testing.assert_allclose(
            np.asarray(a)[:n], np.asarray(b)[:n], rtol=5e-4, atol=5e-5
        )


def test_full_sharding_reachable_from_config(monkeypatch):
    monkeypatch.setenv("HYDRAGNN_AUTO_PARALLEL", "1")
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Architecture"]["edge_sharding"] = "full"
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 2
    samples = deterministic_graph_data(number_configurations=32, seed=29)
    state, model, aug = hydragnn_tpu.run_training(cfg, samples=samples)
    assert int(np.asarray(state.step)) > 0


def test_edge_sharding_takes_a_capped_dimenet_config(monkeypatch):
    """A cap on an atom's edges makes the loader's triplet dimension a dense
    ``[E, K]`` block read through the batch's static meta; the edge-sharded
    placement drops the meta, so ``put_large_batch`` hands the step the same
    slots as flat lists and the configuration trains as it did before the
    block existed."""
    monkeypatch.setenv("HYDRAGNN_AUTO_PARALLEL", "1")
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Architecture"].update(mpnn_type="DimeNet", edge_sharding=True)
    assert cfg["NeuralNetwork"]["Architecture"]["max_neighbours"]
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 2
    samples = deterministic_graph_data(number_configurations=48, seed=19)
    state, model, aug = hydragnn_tpu.run_training(cfg, samples=samples)
    assert int(np.asarray(state.step)) > 0
    assert all(np.all(np.isfinite(np.asarray(p))) for p in jax.tree.leaves(state.params))
