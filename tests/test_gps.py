"""GPS global attention tests: forward, same-graph masking, LapPE, training.

Reference coverage: the GPS variants of ``tests/test_graphs.py`` (every arch x
GPS) and the LapPE pipeline in ``serialized_dataset_loader.py:183-189``.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hydragnn_tpu
from hydragnn_tpu.config import update_config
from hydragnn_tpu.datasets import deterministic_graph_data
from hydragnn_tpu.graphs.batching import collate, compute_pad_spec
from hydragnn_tpu.models import create_model_config, init_model
from hydragnn_tpu.preprocess import apply_variables_of_interest
from hydragnn_tpu.preprocess.encodings import attach_lap_pe, laplacian_pe

from test_config import CI_CONFIG


def build_gps(mpnn_type="GIN", pe_dim=2, heads=2):
    cfg = copy.deepcopy(CI_CONFIG)
    arch = cfg["NeuralNetwork"]["Architecture"]
    arch.update(
        {
            "mpnn_type": mpnn_type,
            "global_attn_engine": "GPS",
            "global_attn_heads": heads,
            "pe_dim": pe_dim,
            "num_gaussians": 10,
            "num_filters": 8,
            "num_radial": 5,
        }
    )
    samples = deterministic_graph_data(number_configurations=8, seed=17)
    samples = apply_variables_of_interest(samples, cfg)
    for s in samples:
        attach_lap_pe(s, pe_dim)
    cfg = update_config(cfg, samples)
    model = create_model_config(cfg)
    pad = compute_pad_spec(samples, 4)
    batch = jax.tree.map(jnp.asarray, collate(samples[:4], pad))
    return model, batch, cfg


def test_laplacian_pe_properties():
    samples = deterministic_graph_data(number_configurations=2, seed=3)
    s = samples[0]
    pe = laplacian_pe(s.senders, s.receivers, s.num_nodes, 3)
    assert pe.shape == (s.num_nodes, 3)
    assert np.all(np.isfinite(pe))
    # eigenvectors are orthogonal (non-degenerate ones)
    gram = pe.T @ pe
    np.testing.assert_allclose(gram, np.diag(np.diag(gram)), atol=1e-4)


@pytest.mark.parametrize("arch", ["GIN", "SAGE", "PNA", "EGNN"])
def test_gps_forward_and_grad(arch):
    model, batch, _ = build_gps(arch)
    variables = init_model(model, batch)
    out = model.apply(variables, batch, train=False)
    assert np.all(np.isfinite(np.asarray(out[0])))

    def loss_fn(params):
        pred = model.apply(
            {"params": params, "batch_stats": variables.get("batch_stats", {})},
            batch,
            train=False,
        )
        tot, _ = model.loss(pred, batch)
        return tot

    g = jax.grad(loss_fn)(variables["params"])
    gmax = max(float(jnp.abs(x).max()) for x in jax.tree.leaves(g))
    assert np.isfinite(gmax) and gmax > 0


def test_gps_attention_is_graph_local():
    """Perturbing graph B's nodes must not change graph A's outputs."""
    model, batch, cfg = build_gps("GIN")
    variables = init_model(model, batch)
    out0 = model.apply(variables, batch, train=False)

    # perturb features of graph 1's nodes only
    sel = np.asarray(batch.batch) == 1
    x2 = np.asarray(batch.x).copy()
    x2[sel] += 10.0
    out1 = model.apply(variables, batch.replace(x=jnp.asarray(x2)), train=False)
    # graph 0's prediction unchanged, graph 1's changed
    np.testing.assert_allclose(
        float(out0[0][0, 0]), float(out1[0][0, 0]), rtol=1e-5
    )
    assert abs(float(out0[0][1, 0]) - float(out1[0][1, 0])) > 1e-6


def test_gps_end_to_end_training():
    cfg = copy.deepcopy(CI_CONFIG)
    arch = cfg["NeuralNetwork"]["Architecture"]
    arch.update(
        {"global_attn_engine": "GPS", "global_attn_heads": 2, "pe_dim": 2}
    )
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 30
    samples = deterministic_graph_data(number_configurations=200, seed=19)
    state, model, aug = hydragnn_tpu.run_training(cfg, samples=samples)
    err, tasks, trues, preds = hydragnn_tpu.run_prediction(
        cfg, state, model, samples=samples
    )
    rmse = float(np.sqrt(np.mean((trues[0] - preds[0]) ** 2)))
    assert rmse < 0.35, f"GPS-GIN failed to converge: RMSE {rmse:.3f}"


def test_gps_preserves_inner_stack_norm_flags():
    """Regression: with GPS on, feature-layer norms must follow the inner
    arch's contract (SchNet uses Identity feature layers, GPS or not)."""
    model, batch, _ = build_gps("SchNet")
    variables = init_model(model, batch)
    assert not any(
        k.startswith("feature_norm") for k in variables["params"]
    ), "GPS wrapper reintroduced feature norms for a no-norm architecture"


def test_gps_edge_model_consumes_rel_pe():
    """Edge-capable convs under GPS must receive relative-PE edge encodings
    (regression: rel_pe used to be computed but never read)."""
    model, batch, _ = build_gps("PNA")
    variables = init_model(model, batch)
    flat = jax.tree_util.tree_flatten_with_path(variables["params"])[0]
    names = {"/".join(str(p) for p in path) for path, _ in flat}
    assert any("rel_pos_emb" in n for n in names), "rel_pe embedding missing"


def test_dense_block_attention_matches_flat():
    """The dense [G, N_max] path must reproduce the flat O(N^2) masked path
    exactly — same module, n_max toggled."""
    from hydragnn_tpu.models.gps import GraphMultiheadAttention

    model, batch, cfg = build_gps("GIN")
    n_max = cfg["NeuralNetwork"]["Architecture"]["max_graph_nodes"]
    assert n_max and n_max % 8 == 0

    h = jnp.asarray(
        np.random.default_rng(0).normal(size=(batch.num_nodes, 8)).astype(np.float32)
    )
    flat = GraphMultiheadAttention(channels=8, heads=2, n_max=0)
    dense = GraphMultiheadAttention(channels=8, heads=2, n_max=n_max)
    variables = flat.init(jax.random.PRNGKey(0), h, batch)
    out_flat = flat.apply(variables, h, batch)
    out_dense = dense.apply(variables, h, batch)
    mask = np.asarray(batch.node_mask) > 0
    np.testing.assert_allclose(
        np.asarray(out_flat)[mask], np.asarray(out_dense)[mask], rtol=1e-4, atol=1e-5
    )


def test_dense_attention_oversize_graph_falls_back():
    """A graph larger than n_max must flip (in-program) to the flat path and
    still be exact."""
    from hydragnn_tpu.models.gps import GraphMultiheadAttention

    model, batch, _ = build_gps("GIN")
    h = jnp.asarray(
        np.random.default_rng(1).normal(size=(batch.num_nodes, 8)).astype(np.float32)
    )
    flat = GraphMultiheadAttention(channels=8, heads=2, n_max=0)
    tiny = GraphMultiheadAttention(channels=8, heads=2, n_max=4)  # < real graph size
    variables = flat.init(jax.random.PRNGKey(0), h, batch)
    assert int(jnp.max(batch.n_node)) > 4
    out_flat = flat.apply(variables, h, batch)
    out_tiny = tiny.apply(variables, h, batch)
    np.testing.assert_allclose(
        np.asarray(out_flat), np.asarray(out_tiny), rtol=1e-5, atol=1e-6
    )


def build_gps_performer(mpnn_type="GIN"):
    cfg = copy.deepcopy(CI_CONFIG)
    arch = cfg["NeuralNetwork"]["Architecture"]
    arch.update(
        {
            "mpnn_type": mpnn_type,
            "global_attn_engine": "GPS",
            "global_attn_type": "performer",
            "global_attn_heads": 2,
            "pe_dim": 2,
        }
    )
    samples = deterministic_graph_data(number_configurations=8, seed=17)
    samples = apply_variables_of_interest(samples, cfg)
    for s in samples:
        attach_lap_pe(s, 2)
    cfg = update_config(cfg, samples)
    model = create_model_config(cfg)
    pad = compute_pad_spec(samples, 4)
    batch = jax.tree.map(jnp.asarray, collate(samples[:4], pad))
    return model, batch, cfg


def test_performer_forward_and_grad():
    model, batch, _ = build_gps_performer()
    variables = init_model(model, batch)
    out = model.apply(variables, batch, train=False)
    assert np.all(np.isfinite(np.asarray(out[0])))

    def loss_fn(params):
        pred = model.apply(
            {"params": params, "batch_stats": variables.get("batch_stats", {})},
            batch,
            train=False,
        )
        tot, _ = model.loss(pred, batch)
        return tot

    g = jax.grad(loss_fn)(variables["params"])
    gmax = max(float(jnp.abs(x).max()) for x in jax.tree.leaves(g))
    assert np.isfinite(gmax) and gmax > 0


def test_performer_attention_is_graph_local():
    model, batch, _ = build_gps_performer()
    variables = init_model(model, batch)
    out0 = model.apply(variables, batch, train=False)
    sel = np.asarray(batch.batch) == 1
    x2 = np.asarray(batch.x).copy()
    x2[sel] += 10.0
    out1 = model.apply(variables, batch.replace(x=jnp.asarray(x2)), train=False)
    np.testing.assert_allclose(float(out0[0][0, 0]), float(out1[0][0, 0]), rtol=1e-5)
    assert abs(float(out0[0][1, 0]) - float(out1[0][1, 0])) > 1e-6


def test_performer_approximates_softmax_attention():
    """With many random features FAVOR+ converges to exact softmax attention;
    check moderate agreement on small graphs."""
    from hydragnn_tpu.models.gps import GraphMultiheadAttention, PerformerAttention

    model, batch, _ = build_gps("GIN")
    rng = np.random.default_rng(2)
    h = jnp.asarray(0.3 * rng.normal(size=(batch.num_nodes, 8)).astype(np.float32))
    exact = GraphMultiheadAttention(channels=8, heads=1, n_max=0)
    approx = PerformerAttention(channels=8, heads=1, num_features=2048)
    variables = exact.init(jax.random.PRNGKey(0), h, batch)
    out_e = exact.apply(variables, h, batch)
    out_a = approx.apply(variables, h, batch)
    mask = np.asarray(batch.node_mask) > 0
    err = np.abs(np.asarray(out_e)[mask] - np.asarray(out_a)[mask])
    scale = np.abs(np.asarray(out_e)[mask]).mean() + 1e-6
    assert err.mean() / scale < 0.15, f"FAVOR+ deviates: {err.mean()/scale:.3f}"
