"""PaiNN's message moves data between nodes and edges as rank-2, component-major
``[., 3F]`` slabs (``models/painn.py``). Held here: parity with the plain
rank-3 formula through the energy, the forces and the parameter gradient of a
force loss; padded edges add nothing; and no gather or scatter-add of the MLIP
train step carries rank-3 node- or edge-sized data, so the layout cannot
silently come back; and, built for a TPU at a size past the resident budget,
every pass of the step sums its ``[E, 3F]`` rows in the ``fused_segment_sum``
kernel, the gathers' transposes among them."""

import collections
import copy
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.models.mlip import make_mlip_train_step
from hydragnn_tpu.models.painn import PainnMessage
from hydragnn_tpu.models.radial import cosine_cutoff, sinc_expansion
from hydragnn_tpu.train import create_train_state, select_optimizer

from test_forces import build_mlip

CUTOFF, NUM_RADIAL = 5.0, 6


@pytest.fixture(scope="module")
def padded_batch():
    """Two LJ graphs padded to a four-graph bucket: dummy node, padded edges."""
    _, batch, _, samples = build_mlip("PAINN", n_samples=4)
    batch = batch.replace(
        node_mask=batch.node_mask.at[2 * samples[0].num_nodes:].set(0.0),
        edge_mask=batch.edge_mask.at[2 * samples[0].num_edges:].set(0.0),
        senders=batch.senders.at[2 * samples[0].num_edges:].set(batch.num_nodes - 1),
        receivers=batch.receivers.at[2 * samples[0].num_edges:].set(batch.num_nodes - 1),
    )
    assert float(batch.edge_mask.sum()) < batch.edge_mask.shape[0]
    return batch


def geometry(pos, batch):
    vec = pos[batch.receivers] - pos[batch.senders] + batch.edge_shifts
    dist = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + 1e-18)
    return dist, vec / dist[:, None]


def message(width):
    return PainnMessage(node_size=width, num_radial=NUM_RADIAL, cutoff=CUTOFF,
                        use_edge_attr=False)


def inputs(batch, width, seed=0):
    ks, kv, kp, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    s = jax.random.normal(ks, (batch.num_nodes, width))
    v = jax.random.normal(kv, (batch.num_nodes, 3, width))
    dist, unit = geometry(batch.pos, batch)
    params = message(width).init(kp, s, v, batch, dist, unit)["params"]
    # biases away from zero, so that no term drops out of the comparison
    params = jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(kw, p.shape) if p.ndim == 1 else p, params)
    return s, v, params


def plain_message(params, s, v, batch, dist, unit):
    """The rank-3 formula, written out: gather [E, 3, F], sum [E, 3, F]."""
    dense = lambda name, x: x @ params[name]["kernel"] + params[name]["bias"]
    filter_w = dense("filter_layer", sinc_expansion(dist, NUM_RADIAL, CUTOFF))
    filter_w = filter_w * cosine_cutoff(dist, CUTOFF)[:, None]
    scalar_out = dense("scalar_mlp_1", jax.nn.silu(dense("scalar_mlp_0", s)))
    gate_v, gate_edge, msg_s = jnp.split(filter_w * scalar_out[batch.receivers], 3, axis=-1)
    v_msg = v[batch.receivers] * gate_v[:, None, :] + gate_edge[:, None, :] * unit[:, :, None]
    em = batch.edge_mask
    n = batch.num_nodes
    ds = jax.ops.segment_sum(msg_s * em[:, None], batch.senders, num_segments=n)
    dv = jax.ops.segment_sum(v_msg * em[:, None, None], batch.senders, num_segments=n)
    return s + ds, v + dv


def slab_message(params, s, v, batch, dist, unit):
    return message(s.shape[-1]).apply({"params": params}, s, v, batch, dist, unit)


def energy(msg_fn, params, pos, s, v, batch):
    """A rotation-invariant scalar of the message's two outputs."""
    s_out, v_out = msg_fn(params, s, v, batch, *geometry(pos, batch))
    mask = batch.node_mask
    return jnp.sum(jnp.tanh(s_out) * mask[:, None]) + 0.5 * jnp.sum(
        jnp.sum(v_out * v_out, axis=1) * mask[:, None])


def forces(msg_fn, params, pos, s, v, batch):
    return -jax.grad(energy, argnums=2)(msg_fn, params, pos, s, v, batch)


def force_loss_grad(msg_fn, params, pos, s, v, batch):
    target = jnp.cos(jnp.arange(pos.size, dtype=pos.dtype)).reshape(pos.shape)
    loss = lambda p: jnp.mean((forces(msg_fn, p, pos, s, v, batch) - target) ** 2)
    return jax.grad(loss)(params)


QUANTITIES = {"energy": energy, "forces": forces, "force_loss_grad": force_loss_grad}


@pytest.mark.parametrize("quantity", list(QUANTITIES))
@pytest.mark.parametrize("width", [4, 128])
def test_slab_message_matches_the_rank3_formula(padded_batch, width, quantity):
    batch = padded_batch
    s, v, params = inputs(batch, width)
    fn = jax.jit(QUANTITIES[quantity], static_argnums=0)
    got = fn(slab_message, params, batch.pos, s, v, batch)
    want = fn(plain_message, params, batch.pos, s, v, batch)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-30
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(np.asarray(g) / scale, np.asarray(w) / scale,
                                   rtol=0, atol=2e-5)


@pytest.mark.parametrize("width", [4, 128])
def test_padded_edges_add_exactly_zero(padded_batch, width):
    batch = padded_batch
    s, v, params = inputs(batch, width)
    dist, unit = geometry(batch.pos, batch)
    s_out, v_out = slab_message(params, s, v, batch, dist, unit)
    # the dummy node, where every padded edge lands, receives exactly nothing
    np.testing.assert_array_equal(np.asarray(s_out[-1]), np.asarray(s[-1]))
    np.testing.assert_array_equal(np.asarray(v_out[-1]), np.asarray(v[-1]))
    # and what a padded edge carries reaches no real node, bit for bit
    pad = batch.edge_mask == 0
    dummy = jnp.arange(batch.num_nodes) == batch.num_nodes - 1
    s_bad, v_bad = slab_message(
        params, jnp.where(dummy[:, None], 1e6, s), jnp.where(dummy[:, None, None], -1e6, v),
        batch, jnp.where(pad, 0.37, dist), jnp.where(pad[:, None], 7.0, unit))
    real = np.asarray(~dummy)
    np.testing.assert_array_equal(np.asarray(s_bad)[real], np.asarray(s_out)[real])
    np.testing.assert_array_equal(np.asarray(v_bad)[real], np.asarray(v_out)[real])
    assert np.all(np.isfinite(np.asarray(s_bad))) and np.all(np.isfinite(np.asarray(v_bad)))


EXCHANGES = ("gather", "scatter-add")


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def rank3_exchanges(closed_jaxpr, sizes):
    """Gather and scatter-add equations with a rank-3 operand, update or result
    whose leading dimension is one of ``sizes`` (the node and edge counts)."""
    found = []
    for eqn in _equations(closed_jaxpr.jaxpr):
        if eqn.primitive.name not in EXCHANGES:
            continue
        for var in list(eqn.invars) + list(eqn.outvars):
            shape = getattr(var.aval, "shape", ())
            if len(shape) == 3 and shape[0] in sizes:
                found.append((eqn.primitive.name, shape))
    return found


def test_the_guard_sees_the_rank3_formula(padded_batch):
    """The walker below finds what it is there to find."""
    batch = padded_batch
    s, v, params = inputs(batch, 4)
    sizes = {batch.num_nodes, batch.senders.shape[0]}
    plain = jax.make_jaxpr(lambda p, pos: force_loss_grad(plain_message, p, pos, s, v, batch))(
        params, batch.pos)
    kinds = {name for name, _ in rank3_exchanges(plain, sizes)}
    assert kinds == set(EXCHANGES)
    slab = jax.make_jaxpr(lambda p, pos: force_loss_grad(slab_message, p, pos, s, v, batch))(
        params, batch.pos)
    assert rank3_exchanges(slab, sizes) == []


def test_mlip_train_step_has_no_rank3_gather_or_scatter():
    model, batch, cfg, _ = build_mlip("PAINN", n_samples=4)
    opt = select_optimizer(cfg["NeuralNetwork"]["Training"]["Optimizer"])
    state = create_train_state(model, opt, batch)
    n, e = batch.num_nodes, batch.senders.shape[0]
    assert len({n, e, 3}) == 3
    jaxpr = jax.make_jaxpr(make_mlip_train_step(model, opt))(state, batch)
    exchanges = [eqn for eqn in _equations(jaxpr.jaxpr) if eqn.primitive.name in EXCHANGES]
    assert len(exchanges) > 8  # the walk reaches the message's, in all four passes
    assert rank3_exchanges(jaxpr, {n, e}) == []


PASSES = 4  # forward, forces, and the transposes of both


def test_tpu_step_sums_rows_in_the_kernel_in_every_pass(monkeypatch):
    """F = 128 and 3,464 nodes: ``[N, 3F]`` overruns the resident budget, so
    the row sums are the tiled form. Lowered for a TPU (no chip: StableHLO),
    each of the four AD passes holds the kernel, and no XLA scatter-add is
    left with a ``[., 384]`` operand: ``segment.gather``'s transposes took
    their place."""
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.datasets import lennard_jones_data
    from hydragnn_tpu.graphs.batching import collate, compute_pad_spec
    from hydragnn_tpu.models import create_model_config
    from hydragnn_tpu.ops import fused_scatter as fs
    from hydragnn_tpu.preprocess import apply_variables_of_interest

    from test_forces import MLIP_CONFIG

    cfg = copy.deepcopy(MLIP_CONFIG)
    cfg["NeuralNetwork"]["Architecture"].update(mpnn_type="PAINN", hidden_dim=128)
    samples = lennard_jones_data(number_configurations=432, cells_per_dim=2, seed=3)
    samples = apply_variables_of_interest(samples, cfg)
    cfg = update_config(cfg, samples)
    model = create_model_config(cfg)
    batch = jax.tree.map(
        jnp.asarray, collate(samples, compute_pad_spec(samples, len(samples))))
    n, e = batch.num_nodes, batch.senders.shape[0]
    assert "VMEM" in fs.scatter_route(jnp.zeros((e, 384)), e, n, 128)
    opt = select_optimizer(cfg["NeuralNetwork"]["Training"]["Optimizer"])
    state = create_train_state(model, opt, batch)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # routes, interpret off
    step = make_mlip_train_step(model, opt)
    wide = [eqn for eqn in _equations(jax.make_jaxpr(step)(state, batch).jaxpr)
            if eqn.primitive.name == "scatter-add" and eqn.invars[0].aval.shape[-1] == 384]
    assert wide == []
    passes = mosaic_calls_by_pass(step, state, batch)
    assert len(passes) == PASSES and min(passes.values()) >= 1, passes


def mosaic_calls_by_pass(step, state, batch, kernel="fused_segment_sum"):
    """``step`` lowered for a TPU (no chip: StableHLO): the Mosaic calls named
    ``kernel``, counted by the AD pass (the scope under ``jit(train_step)``)
    that holds them."""
    text = step.trace(state, batch).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    locations = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, flags=re.M))
    passes = collections.Counter()
    for ref in re.findall(r"tpu_custom_call.*loc\((#loc\d+)\)", text):
        where = locations[ref]
        for _ in range(8):  # a location names its callers by reference
            where = re.sub(r"#loc\d+", lambda m: locations.get(m.group(0), ""), where)
        scope = re.search(rf"jit\(train_step\)/([^/]+)/[^\"]*{kernel}", where)
        assert scope, where[:200]
        passes[scope.group(1)] += 1
    return passes
