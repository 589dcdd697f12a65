"""Activation / loss selection parity (reference
``tests/test_loss_and_activation_functions.py`` + ``utils/model/model.py:30-61``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.models.common import (
    _LOSSES,
    get_activation,
    masked_gaussian_nll,
    masked_mae,
    masked_mse,
    masked_rmse,
    masked_smooth_l1,
)

REFERENCE_ACTIVATIONS = [
    "relu", "selu", "prelu", "elu",
    "lrelu_01", "lrelu_025", "lrelu_05", "sigmoid",
]


@pytest.mark.parametrize("name", REFERENCE_ACTIVATIONS)
def test_reference_activation_names_resolve(name):
    act = get_activation(name)
    x = jnp.linspace(-2, 2, 9)
    y = np.asarray(act(x))
    assert y.shape == x.shape and np.all(np.isfinite(y))


def test_leaky_slopes():
    x = jnp.float32(-2.0)
    assert float(get_activation("lrelu_01")(x)) == pytest.approx(-0.2)
    assert float(get_activation("lrelu_025")(x)) == pytest.approx(-0.5)
    assert float(get_activation("lrelu_05")(x)) == pytest.approx(-1.0)
    # torch PReLU default init slope 0.25
    assert float(get_activation("prelu")(x)) == pytest.approx(-0.5)


@pytest.mark.parametrize("name", ["shifted_softplus", "ssp"])
def test_shifted_softplus_is_schnets_activation(name):
    """ssp(x) = ln(1/2 e^x + 1/2): zero at zero, slope 1/2 there, and the one
    function ``models/radial.py`` gives the filter network."""
    from hydragnn_tpu.models.radial import shifted_softplus

    act = get_activation(name)
    assert act is shifted_softplus
    x = jnp.linspace(-3.0, 3.0, 13)
    np.testing.assert_allclose(act(x), np.log(0.5 * np.exp(np.asarray(x)) + 0.5), atol=1e-6)
    assert float(act(jnp.float32(0.0))) == 0.0
    assert float(jax.grad(act)(jnp.float32(0.0))) == pytest.approx(0.5)


def test_unknown_activation_raises_with_catalog():
    with pytest.raises(ValueError, match="relu"):
        get_activation("not_an_activation")


def test_reference_loss_names_present():
    for name in ("mse", "mae", "rmse", "smooth_l1"):
        assert name in _LOSSES


def _data():
    rng = np.random.default_rng(0)
    pred = jnp.asarray(rng.normal(size=(6, 3)).astype(np.float32))
    target = jnp.asarray(rng.normal(size=(6, 3)).astype(np.float32))
    mask = jnp.asarray(np.array([1, 1, 1, 1, 0, 0], np.float32))
    return pred, target, mask


def test_losses_match_torch_semantics():
    import torch

    pred, target, mask = _data()
    tp = torch.tensor(np.asarray(pred)[:4])
    tt = torch.tensor(np.asarray(target)[:4])
    assert float(masked_mse(pred, target, mask)) == pytest.approx(
        float(torch.nn.functional.mse_loss(tp, tt)), rel=1e-5)
    assert float(masked_mae(pred, target, mask)) == pytest.approx(
        float(torch.nn.functional.l1_loss(tp, tt)), rel=1e-5)
    assert float(masked_smooth_l1(pred, target, mask)) == pytest.approx(
        float(torch.nn.functional.smooth_l1_loss(tp, tt)), rel=1e-5)
    assert float(masked_rmse(pred, target, mask)) == pytest.approx(
        float(torch.sqrt(torch.nn.functional.mse_loss(tp, tt))), rel=1e-4)


def test_gaussian_nll_matches_torch():
    import torch

    pred, target, mask = _data()
    var = jnp.asarray(np.abs(np.random.default_rng(1).normal(size=(6, 3))).astype(np.float32) + 0.1)
    ours = float(masked_gaussian_nll(pred, target, mask, var))
    tl = torch.nn.GaussianNLLLoss()
    theirs = float(tl(torch.tensor(np.asarray(pred)[:4]),
                      torch.tensor(np.asarray(target)[:4]),
                      torch.tensor(np.asarray(var)[:4])))
    assert ours == pytest.approx(theirs, rel=1e-4)


def test_masked_rows_do_not_contribute():
    pred, target, mask = _data()
    # corrupt the masked rows wildly: loss must not move
    pred2 = pred.at[4:].set(1e6)
    for fn in (masked_mse, masked_mae, masked_rmse, masked_smooth_l1):
        assert float(fn(pred, target, mask)) == pytest.approx(
            float(fn(pred2, target, mask)), rel=1e-6), fn.__name__


def test_losses_differentiable():
    pred, target, mask = _data()
    for name, fn in _LOSSES.items():
        g = jax.grad(lambda p: fn(p, target, mask))(pred)
        assert np.all(np.isfinite(np.asarray(g))), name
        # padding rows get zero gradient
        assert np.allclose(np.asarray(g)[4:], 0.0), name


def test_smooth_l1_config_trains():
    """loss_function_type: smooth_l1 works through run_training."""
    import copy

    import hydragnn_tpu
    from hydragnn_tpu.datasets import deterministic_graph_data
    from test_config import CI_CONFIG

    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 2
    cfg["NeuralNetwork"]["Training"]["loss_function_type"] = "smooth_l1"
    samples = deterministic_graph_data(number_configurations=40, seed=3)
    state, model, _ = hydragnn_tpu.run_training(cfg, samples)
    assert state is not None
