"""Pallas fused gather-scatter kernel: parity vs the XLA reference path.

Runs in interpret mode on the CPU test platform (tests/conftest.py forces
JAX_PLATFORMS=cpu); the same kernel compiles natively on TPU.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.ops import fused_gather_scatter, gather_scatter_sum
from hydragnn_tpu.ops.fused_scatter import reference_gather_scatter


def make_edges(rng, n_nodes, n_edges, sorted_recv=True, local_span=24):
    """Receiver-sorted, locality-respecting edges (the collate layout):
    both endpoints of an edge stay within a small node window."""
    centers = np.sort(rng.integers(0, n_nodes, size=n_edges))
    recv = centers
    send = np.clip(
        centers + rng.integers(-local_span, local_span + 1, size=n_edges), 0, n_nodes - 1
    )
    if not sorted_recv:
        perm = rng.permutation(n_edges)
        recv, send = recv[perm], send[perm]
    return send.astype(np.int32), recv.astype(np.int32)


@pytest.mark.parametrize("weight_kind", ["none", "scalar", "vector"])
def test_forward_parity(weight_kind):
    rng = np.random.default_rng(0)
    n, e, c = 512, 700, 64  # e not a block multiple: exercises edge padding
    h = jnp.asarray(rng.normal(size=(n, c)).astype(np.float32))
    send, recv = make_edges(rng, n, e)
    if weight_kind == "none":
        w = None
    elif weight_kind == "scalar":
        w = jnp.asarray(rng.uniform(0.5, 2.0, size=e).astype(np.float32))
    else:
        w = jnp.asarray(rng.uniform(0.5, 2.0, size=(e, c)).astype(np.float32))

    got = fused_gather_scatter(h, jnp.asarray(send), jnp.asarray(recv), n, w, interpret=True)
    want = reference_gather_scatter(h, jnp.asarray(send), jnp.asarray(recv), n, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_forward_parity_bf16():
    rng = np.random.default_rng(1)
    n, e, c = 256, 512, 32
    h = jnp.asarray(rng.normal(size=(n, c))).astype(jnp.bfloat16)
    send, recv = make_edges(rng, n, e)
    got = fused_gather_scatter(h, jnp.asarray(send), jnp.asarray(recv), n, interpret=True)
    want = reference_gather_scatter(h, jnp.asarray(send), jnp.asarray(recv), n, None)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=2e-2, atol=2e-2
    )


def test_unsorted_edges_fall_back_in_program():
    """Blocks spanning the whole node range exceed the window; lax.cond must
    route to the reference path, keeping results exact."""
    rng = np.random.default_rng(2)
    n, e, c = 512, 512, 16
    h = jnp.asarray(rng.normal(size=(n, c)).astype(np.float32))
    send, recv = make_edges(rng, n, e, sorted_recv=False)
    got = fused_gather_scatter(h, jnp.asarray(send), jnp.asarray(recv), n, interpret=True)
    want = reference_gather_scatter(h, jnp.asarray(send), jnp.asarray(recv), n, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("weight_kind", ["scalar", "vector"])
def test_grad_parity(weight_kind):
    rng = np.random.default_rng(3)
    n, e, c = 256, 384, 32
    h = jnp.asarray(rng.normal(size=(n, c)).astype(np.float32))
    send = jnp.asarray(make_edges(rng, n, e)[0])
    send_np, recv_np = make_edges(rng, n, e)
    send, recv = jnp.asarray(send_np), jnp.asarray(recv_np)
    shape = (e, c) if weight_kind == "vector" else (e,)
    w = jnp.asarray(rng.uniform(0.5, 2.0, size=shape).astype(np.float32))

    def loss_fused(h, w):
        out = fused_gather_scatter(h, send, recv, n, w, interpret=True)
        return (out * jnp.cos(jnp.arange(c, dtype=jnp.float32))).sum()

    def loss_ref(h, w):
        out = reference_gather_scatter(h, send, recv, n, w)
        return (out * jnp.cos(jnp.arange(c, dtype=jnp.float32))).sum()

    gh, gw = jax.grad(loss_fused, argnums=(0, 1))(h, w)
    gh_ref, gw_ref = jax.grad(loss_ref, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(gh), np.asarray(gh_ref), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref), rtol=1e-4, atol=1e-4)


def test_small_graph_static_fallback():
    """Graphs smaller than the window skip the kernel entirely (static check)."""
    rng = np.random.default_rng(4)
    n, e, c = 32, 40, 8
    h = jnp.asarray(rng.normal(size=(n, c)).astype(np.float32))
    send, recv = make_edges(rng, n, e, local_span=4)
    got = fused_gather_scatter(h, jnp.asarray(send), jnp.asarray(recv), n, interpret=True)
    want = reference_gather_scatter(h, jnp.asarray(send), jnp.asarray(recv), n, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_gather_scatter_sum_ab_flag(monkeypatch):
    rng = np.random.default_rng(5)
    n, e, c = 512, 512, 16
    h = jnp.asarray(rng.normal(size=(n, c)).astype(np.float32))
    send, recv = (jnp.asarray(a) for a in make_edges(rng, n, e))
    off = gather_scatter_sum(h, send, recv, n, fused=False)
    monkeypatch.setenv("HYDRAGNN_FUSED_SCATTER", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    on = gather_scatter_sum(h, send, recv, n, fused=None)
    np.testing.assert_allclose(np.asarray(on), np.asarray(off), rtol=1e-5, atol=1e-5)


def test_collate_layout_matches_kernel_assumptions():
    """Real batches (radius graphs, collate padding) keep receiver windows
    narrow so the kernel path (not the cond fallback) is actually taken."""
    from hydragnn_tpu.graphs.batching import collate, compute_pad_spec
    from hydragnn_tpu.graphs.graph import GraphSample
    from hydragnn_tpu.graphs.radius import radius_graph
    from hydragnn_tpu.ops.fused_scatter import _window_starts

    rng = np.random.default_rng(6)
    samples = []
    for _ in range(16):
        na = int(rng.integers(9, 30))
        pos = rng.uniform(0, 6.0, size=(na, 3))
        s, r, sh = radius_graph(pos, radius=3.0, max_neighbours=20)
        samples.append(
            GraphSample(
                x=np.ones((na, 1), np.float32), pos=pos, senders=s, receivers=r,
                edge_shifts=sh, graph_y=np.zeros(1), node_y=np.zeros((na, 1)),
            )
        )
    pad = compute_pad_spec(samples, 16)
    b = collate(samples, pad)
    recv = jnp.asarray(b.receivers)
    send = jnp.asarray(b.senders)
    e = recv.shape[0]
    be = 256
    g = e // be
    if g == 0:
        pytest.skip("batch too small for a block")
    _, _, s_fits = _window_starts(send[: g * be], g, be, 256, pad.n_node, 8)
    _, _, r_fits = _window_starts(recv[: g * be], g, be, 256, pad.n_node, 8)
    assert bool(s_fits) and bool(r_fits), "collate layout should fit the kernel window"


def test_gin_training_parity_with_fused_kernel(monkeypatch):
    """One GIN train step with the fused kernel (interpret mode) matches the
    XLA path end-to-end: same loss, same parameter updates."""
    import copy

    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.datasets import deterministic_graph_data
    from hydragnn_tpu.graphs.batching import collate, compute_pad_spec
    from hydragnn_tpu.models import create_model_config
    from hydragnn_tpu.preprocess import apply_variables_of_interest
    from hydragnn_tpu.train import create_train_state, make_train_step, select_optimizer
    from __graft_entry__ import FLAGSHIP_CONFIG

    cfg = copy.deepcopy(FLAGSHIP_CONFIG)
    samples = deterministic_graph_data(number_configurations=8, seed=0)
    samples = apply_variables_of_interest(samples, cfg)
    cfg = update_config(cfg, samples)
    model = create_model_config(cfg)
    pad = compute_pad_spec(samples, 8)
    batch = jax.tree.map(jnp.asarray, collate(samples, pad))
    optimizer = select_optimizer(cfg["NeuralNetwork"]["Training"]["Optimizer"])

    results = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("HYDRAGNN_FUSED_SCATTER", flag)
        state = create_train_state(model, optimizer, batch)
        step = make_train_step(model, optimizer)
        new_state, metrics = step(state, batch)
        results[flag] = (float(metrics["loss"]), new_state.params)

    assert np.isfinite(results["1"][0])
    np.testing.assert_allclose(results["0"][0], results["1"][0], rtol=1e-4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4
        ),
        results["0"][1],
        results["1"][1],
    )


def test_schnet_forward_parity_with_fused_kernel(monkeypatch):
    """SchNet's CFConv uses the vector-weight fused path; forward must match
    the XLA route bit-for-bit-ish."""
    import copy

    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.datasets import deterministic_graph_data
    from hydragnn_tpu.graphs.batching import collate, compute_pad_spec
    from hydragnn_tpu.models import create_model_config, init_model
    from hydragnn_tpu.preprocess import apply_variables_of_interest
    from test_config import CI_CONFIG

    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Architecture"].update(
        {"mpnn_type": "SchNet", "num_gaussians": 10, "num_filters": 8}
    )
    samples = deterministic_graph_data(number_configurations=8, seed=5)
    samples = apply_variables_of_interest(samples, cfg)
    cfg = update_config(cfg, samples)
    model = create_model_config(cfg)
    pad = compute_pad_spec(samples, 8)
    batch = jax.tree.map(jnp.asarray, collate(samples, pad))
    variables = init_model(model, batch)

    outs = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("HYDRAGNN_FUSED_SCATTER", flag)
        outs[flag] = model.apply(variables, batch, train=False)
    for a, b in zip(jax.tree.leaves(outs["0"]), jax.tree.leaves(outs["1"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def _count_kernel_traces(monkeypatch) -> list:
    """Record every trace of a scatter kernel body (the functions that hold
    the pallas_call) — proof of which route a step program took."""
    from hydragnn_tpu.ops import fused_scatter as fs

    seen: list = []
    for name in ("_gather_scatter_or_ref", "_scatter_or_ref"):
        inner = getattr(fs, name)

        def counted(*args, _inner=inner, _name=name):
            seen.append(_name)
            return _inner(*args)

        monkeypatch.setattr(fs, name, counted)
    return seen


def test_mesh_step_routes_forced_kernel_to_xla(monkeypatch):
    """The mesh step vmaps the per-device body and lets GSPMD split the
    stacked axis; GSPMD cannot partition a Mosaic call (on a TPU the step
    used to die with "Mosaic kernels cannot be automatically partitioned").
    So the step is traced under ``routing.xla_only``: even with the kernel
    FORCED on it holds no pallas_call, never raises, and is the XLA
    program."""
    import copy

    import optax

    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.datasets import deterministic_graph_data
    from hydragnn_tpu.graphs.batching import collate, compute_pad_spec
    from hydragnn_tpu.models import create_model_config
    from hydragnn_tpu.parallel import make_mesh, stack_device_batches
    from hydragnn_tpu.parallel.step import (
        make_parallel_train_step,
        put_batch,
        shard_state,
    )
    from hydragnn_tpu.preprocess import apply_variables_of_interest
    from hydragnn_tpu.train import create_train_state

    from test_config import CI_CONFIG

    cfg = copy.deepcopy(CI_CONFIG)
    samples = deterministic_graph_data(number_configurations=64, seed=3)
    samples = apply_variables_of_interest(samples, cfg)
    cfg = update_config(cfg, samples)
    model = create_model_config(cfg)
    pad = compute_pad_spec(samples, 8)
    batches = [collate(samples[i * 8 : (i + 1) * 8], pad) for i in range(8)]
    opt = optax.adamw(1e-3)
    mesh = make_mesh()
    sb = put_batch(stack_device_batches(batches), mesh)
    # the layout IS certified: it is the mesh, not the batch, that routes
    assert sb.meta.gs_fits is True

    kernel_traces = _count_kernel_traces(monkeypatch)
    losses = {}
    for flag in ("1", "0"):
        monkeypatch.setenv("HYDRAGNN_FUSED_SCATTER", flag)
        state = shard_state(create_train_state(model, opt, batches[0]), mesh)
        _, m = make_parallel_train_step(model, opt, mesh)(state, sb)
        losses[flag] = float(m["loss"])
    assert kernel_traces == []
    assert losses["1"] == losses["0"], losses


def test_mlip_step_differentiates_through_forced_kernel(monkeypatch):
    """Force training takes the parameter gradient of forces = -dE/dpos, so
    the outer differentiation passes through the kernels' VJP rules. Those
    call the wrapped op, not the raw scalar-prefetch pallas_call (whose JVP
    is unimplemented — every MLIP step used to die at trace time with
    NotImplementedError once a kernel engaged): the step traces WITH the
    kernel in it and matches the XLA path."""
    import copy
    import importlib.util
    import os

    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.datasets import lennard_jones_data
    from hydragnn_tpu.graphs.batching import collate, compute_pad_spec
    from hydragnn_tpu.models import create_model_config
    from hydragnn_tpu.models.mlip import make_mlip_train_step
    from hydragnn_tpu.train import create_train_state, select_optimizer

    path = os.path.join(os.path.dirname(__file__), "..", "examples",
                        "LennardJones", "LennardJones.py")
    spec = importlib.util.spec_from_file_location("lj_example", path)
    lj = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lj)
    cfg = copy.deepcopy(lj.CONFIG)
    cfg["NeuralNetwork"]["Architecture"].update(hidden_dim=8, num_conv_layers=1)
    # 17 x 8 atoms pad to 144 node slots: past the 128-row window, so the
    # scatter kernel engages
    samples = lennard_jones_data(number_configurations=17, cells_per_dim=2)
    cfg = update_config(cfg, samples)
    model = create_model_config(cfg)
    batch = jax.tree.map(
        jnp.asarray, collate(samples, compute_pad_spec(samples, 17))
    )
    assert batch.num_nodes >= 128 and batch.meta.recv_fits
    optimizer = select_optimizer(cfg["NeuralNetwork"]["Training"]["Optimizer"])

    kernel_traces = _count_kernel_traces(monkeypatch)
    results = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("HYDRAGNN_FUSED_SCATTER", flag)
        state = create_train_state(model, optimizer, batch)
        new_state, metrics = make_mlip_train_step(model, optimizer)(state, batch)
        assert bool(kernel_traces) == (flag == "1")
        results[flag] = (np.asarray(metrics["tasks_loss"]), new_state.params)

    assert np.isfinite(results["1"][0]).all()
    np.testing.assert_allclose(results["0"][0], results["1"][0], rtol=1e-4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5
        ),
        results["0"][1],
        results["1"][1],
    )


# -- fused_segment_sum past the resident budget: the tiled form ---------------------------


def tiled_ids(kind: str, n: int, e: int, rng) -> np.ndarray:
    """Id arrays the tiled form must sum exactly, whatever their order."""
    sorted_ids = np.sort(rng.integers(0, n - 1, size=e))
    if kind == "receivers":  # sorted, as collate leaves them
        ids = sorted_ids
    elif kind == "senders":  # graph-local: unsorted inside a 21-atom molecule
        ids = np.minimum(sorted_ids // 21 * 21 + rng.integers(0, 21, size=e), n - 2)
    elif kind == "shuffled":  # no locality at all: more windows, same sum
        ids = rng.permutation(sorted_ids)
    elif kind == "straddle":  # every block sits across a 128-row boundary
        ids = np.sort(rng.integers(120, 136, size=e)) + 128 * (np.arange(e) // 512 * 7)
    elif kind == "pad":  # real ids, then collate's reserved slot N - 1
        ids = np.concatenate([sorted_ids[: e - e // 3] // 2, np.full(e // 3, n - 1)])
    elif kind == "outside":  # ids past either end are dropped, as XLA drops them
        ids = rng.integers(-40, n + 40, size=e)
    else:
        raise ValueError(kind)
    return ids.astype(np.int32)


TILED_N, TILED_E = 2568, 1300  # N over one accumulator, not whole windows; E not whole blocks


@pytest.mark.parametrize("channels", [128, 384])
@pytest.mark.parametrize(
    "kind", ["receivers", "senders", "shuffled", "straddle", "pad", "outside"])
def test_tiled_sum_matches_segment_sum(kind, channels):
    from hydragnn_tpu.ops import fused_scatter as fs

    rng = np.random.default_rng(7)
    n, e = TILED_N, TILED_E
    block, span = fs._tile_geometry(n, channels)
    assert e % block and n > span and n % 128  # a ragged last block, a sliding accumulator
    ids = jnp.asarray(tiled_ids(kind, n, e, rng))
    data = jnp.asarray(rng.normal(size=(e, channels)).astype(np.float32))
    got = fs._tiled_sum(data, ids, n, True)
    want = jax.ops.segment_sum(data, ids, num_segments=n)
    scale = float(jnp.max(jnp.abs(want)))  # fp32 rounding of a sum, whatever its order
    np.testing.assert_allclose(np.asarray(got) / scale, np.asarray(want) / scale,
                               rtol=0, atol=1e-6)


def test_tiled_sum_bf16_rows_are_one_exact_term():
    from hydragnn_tpu.ops import fused_scatter as fs

    rng = np.random.default_rng(8)
    n, e, c = 1288, 700, 128
    ids = jnp.asarray(tiled_ids("senders", n, e, rng))
    data = jnp.asarray(rng.normal(size=(e, c))).astype(jnp.bfloat16)
    got = fs._tiled_sum(data, ids, n, True)
    want = jax.ops.segment_sum(data.astype(jnp.float32), ids, num_segments=n)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want.astype(jnp.bfloat16), np.float32), rtol=0, atol=0)


def test_segment_sum_route_places_the_accumulator_by_the_budget():
    """Dtype, rank, N, C and E decide; nothing of the batch is read."""
    from hydragnn_tpu.ops import fused_scatter as fs
    from hydragnn_tpu.ops import routing

    small, large = jnp.zeros((512, 384)), jnp.zeros((512, 384))
    assert fs.scatter_route(small, 512, 3408, 128) is None  # resident: 9.98 MiB
    assert "VMEM" in fs.scatter_route(large, 512, 3416, 128)  # 10.01 MiB
    assert fs.scatter_route(large, 512, 3416, 128, tiled=True) is None
    assert fs.scatter_route(large, 512, 21512, 128, tiled=True) is None  # at any N
    assert "channels" in fs.scatter_route(jnp.zeros((512, 3)), 512, 21512, 128, tiled=True)
    assert "rank-3" in fs.scatter_route(jnp.zeros((512, 3, 128)), 512, 21512, 128, tiled=True)
    assert "multiple of 8" in fs.scatter_route(large, 512, 21510, 128, tiled=True)
    assert "VMEM" in fs.scatter_route(jnp.zeros((512, 8192)), 512, 21512, 128, tiled=True)
    with routing.xla_only("mesh step"):
        assert fs.scatter_route(large, 512, 21512, 128, tiled=True) == "mesh step"


def _pair(gather, row_sum, rcv, snd, n):
    return lambda x, w: row_sum(gather(x, rcv) * w, snd, n)


def _plain_pair(rcv, snd, n):
    return _pair(lambda x, i: x[i],
                 lambda d, i, n: jax.ops.segment_sum(d, i, num_segments=n), rcv, snd, n)


def _pair_operands(channels, seed=9):
    """N over the OLD 10 MiB rule at this width: fused_segment_sum itself
    routes the call to the tiled form."""
    from hydragnn_tpu.ops import fused_scatter as fs

    rng = np.random.default_rng(seed)
    n, e = {384: 3416, 128: 10248}[channels], 600
    assert fs.scatter_route(jnp.zeros((e, channels)), e, n, 128) is not None
    rcv = jnp.asarray(tiled_ids("receivers", n, e, rng))
    snd = jnp.asarray(tiled_ids("senders", n, e, rng))
    x = jnp.asarray(rng.normal(size=(n, channels)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(e, channels)).astype(np.float32))
    return n, rcv, snd, x, w


def _force_loss(fn):
    energy = lambda x, w: jnp.sum(jnp.tanh(fn(x, w)))
    return lambda x, w: jnp.sum(jax.grad(energy)(x, w) ** 2)


DERIVATIVES = {
    "forward": lambda fn: fn,
    "vjp": lambda fn: jax.grad(lambda x, w: jnp.sum(jnp.sin(fn(x, w))), argnums=(0, 1)),
    "grad_of_grad": lambda fn: jax.grad(_force_loss(fn), argnums=(0, 1)),
}


def _primitives(closed_jaxpr) -> list:
    """Every equation of the program, a kernel's own body left out."""
    def equations(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            if eqn.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from equations(sub)

    return list(equations(closed_jaxpr.jaxpr))


def _pallas_calls(closed_jaxpr) -> int:
    return sum(eqn.primitive.name == "pallas_call" for eqn in _primitives(closed_jaxpr))


def _row_scatters(closed_jaxpr, n, c) -> int:
    """XLA scatter-adds onto ``[n, c]`` rows."""
    return sum(eqn.primitive.name == "scatter-add" and eqn.outvars[0].aval.shape == (n, c)
               for eqn in _primitives(closed_jaxpr))


def _assert_trees_close(got, want, atol):
    """Leaf by leaf, to ``atol`` of the leaf's largest entry."""
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a) / scale, np.asarray(b) / scale,
                                   rtol=0, atol=atol)


@pytest.mark.parametrize("channels", [128, 384])
@pytest.mark.parametrize("order", list(DERIVATIVES))
def test_gather_sum_pair_is_the_kernel_in_every_derivative(monkeypatch, order, channels):
    """``segment.gather`` and ``segment.segment_sum`` as one pair: forward,
    VJP and the gradient of a force loss each hold the tiled kernel (the
    gather's transpose IS the sum) and agree with plain indexing and XLA's
    sum to fp32 rounding."""
    from hydragnn_tpu.graphs import segment

    monkeypatch.setenv("HYDRAGNN_FUSED_SCATTER", "1")
    n, rcv, snd, x, w = _pair_operands(channels)
    fused = DERIVATIVES[order](_pair(segment.gather, segment.segment_sum, rcv, snd, n))
    plain = DERIVATIVES[order](_plain_pair(rcv, snd, n))
    # forward: the sum; VJP: the gather's transpose; grad of grad: both again
    assert _pallas_calls(jax.make_jaxpr(fused)(x, w)) >= {"forward": 1, "vjp": 1,
                                                           "grad_of_grad": 3}[order]
    _assert_trees_close(jax.jit(fused)(x, w), jax.jit(plain)(x, w), 3e-6)


@pytest.mark.parametrize("flag", ["0", "1"])
def test_gather_vjp_is_segment_sum(monkeypatch, flag):
    """The declared transpose: XLA's sum with the kernel off, the kernel's
    with it on; and grad of grad straight through the pair never has to
    differentiate a raw ``pallas_call`` (no rule exists for one with scalar
    prefetch: it would raise)."""
    from hydragnn_tpu.graphs import segment

    monkeypatch.setenv("HYDRAGNN_FUSED_SCATTER", flag)
    n, rcv, snd, x, w = _pair_operands(384)
    ct = jnp.cos(jnp.arange(w.size, dtype=jnp.float32)).reshape(w.shape)
    _, vjp = jax.vjp(lambda x: segment.gather(x, rcv), x)
    want = jax.ops.segment_sum(ct, rcv, num_segments=n)
    np.testing.assert_allclose(np.asarray(vjp(ct)[0]), np.asarray(want), rtol=0, atol=2e-6)
    assert _pallas_calls(jax.make_jaxpr(lambda ct: vjp(ct)[0])(ct)) == int(flag)

    scalar = lambda fn: lambda s: jnp.sum(jnp.tanh(fn(x * s, w)))
    fused = scalar(_pair(segment.gather, segment.segment_sum, rcv, snd, n))
    plain = scalar(_plain_pair(rcv, snd, n))
    got = jax.grad(jax.grad(fused))(jnp.float32(0.7))
    np.testing.assert_allclose(float(got), float(jax.grad(jax.grad(plain))(jnp.float32(0.7))),
                               rtol=2e-5)


def test_resident_sum_closes_on_the_pair_too(monkeypatch):
    """Under the resident budget the kernel is the one it was; its VJP is the
    same ``segment.gather``, so a transposed sum is the kernel there as well."""
    from hydragnn_tpu.graphs import segment
    from hydragnn_tpu.ops import fused_scatter as fs

    monkeypatch.setenv("HYDRAGNN_FUSED_SCATTER", "1")
    rng = np.random.default_rng(10)
    n, e, c = 512, 700, 64
    assert fs.scatter_route(jnp.zeros((e, c)), e, n, 128) is None
    snd = jnp.asarray(tiled_ids("receivers", n, e, rng))
    m = jnp.asarray(rng.normal(size=(e, c)).astype(np.float32))
    fused = jax.grad(_force_loss(lambda m, _: segment.segment_sum(m * m, snd, n)))
    plain = jax.grad(_force_loss(lambda m, _: jax.ops.segment_sum(m * m, snd, num_segments=n)))
    assert _pallas_calls(jax.make_jaxpr(fused)(m, None)) >= 2
    np.testing.assert_allclose(np.asarray(fused(m, None)), np.asarray(plain(m, None)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("channels", [64, 128])
@pytest.mark.parametrize("order", list(DERIVATIVES))
def test_an_explicit_certificate_keeps_the_resident_kernel_out(monkeypatch, order, channels):
    """``fits=False`` on ``segment.gather`` / ``segment_sum`` (id arrays
    collate certifies nothing about, or a certificate that failed) at a shape
    the resident rule admits: never the resident kernel nor its in-program
    fallback, in any derivative, where the same calls without it hold both.
    Rows of whole lanes (C 128) take the TILED kernel and stay on it through
    grad of grad, one call where XLA's form has one scatter-add; narrower rows
    (C 64) take XLA's sum. Same numbers."""
    from hydragnn_tpu.graphs import segment
    from hydragnn_tpu.ops import fused_scatter as fs

    monkeypatch.setenv("HYDRAGNN_FUSED_SCATTER", "1")
    resident_traces = _count_kernel_traces(monkeypatch)
    rng = np.random.default_rng(12)
    n, e, c = 512, 700, channels
    assert fs.scatter_route(jnp.zeros((e, c)), e, n, 128) is None
    rcv = jnp.asarray(tiled_ids("receivers", n, e, rng))
    snd = jnp.asarray(tiled_ids("senders", n, e, rng))
    x = jnp.asarray(rng.normal(size=(n, c)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(e, c)).astype(np.float32))
    stated = DERIVATIVES[order](_pair(
        lambda x, ids: segment.gather(x, ids, fits=False),
        lambda d, ids, n: segment.segment_sum(d, ids, n, fits=False), rcv, snd, n))
    plain = DERIVATIVES[order](_plain_pair(rcv, snd, n))
    jaxpr = jax.make_jaxpr(stated)(x, w)
    assert resident_traces == []
    assert not any(eqn.primitive.name == "cond" for eqn in _primitives(jaxpr))
    scatters = _row_scatters(jax.make_jaxpr(plain)(x, w), n, c)
    assert scatters >= {"forward": 1, "vjp": 2, "grad_of_grad": 3}[order]
    tiled = c % 128 == 0
    assert _pallas_calls(jaxpr) == (scatters if tiled else 0)
    assert _row_scatters(jaxpr, n, c) == (0 if tiled else scatters)
    dynamic = DERIVATIVES[order](_pair(segment.gather, segment.segment_sum, rcv, snd, n))
    assert _pallas_calls(jax.make_jaxpr(dynamic)(x, w)) >= 1
    assert resident_traces  # the same calls with no certificate stated: the resident kernel
    _assert_trees_close(jax.jit(stated)(x, w), jax.jit(plain)(x, w), 3e-6)


# -- the gather-multiply-sum without a certificate: the pair on the tiled sum ------------


def _gs_operands(weight_kind, seed=14):
    """520 node slots (whole 8s, not whole windows), 700 edges (not whole
    blocks), 128 channels: the resident rule and the tiled route both admit."""
    rng = np.random.default_rng(seed)
    n, e, c = 520, 700, 128
    rcv = jnp.asarray(tiled_ids("receivers", n, e, rng))  # sorted, as collate leaves them
    snd = jnp.asarray(tiled_ids("senders", n, e, rng))  # unsorted inside a structure
    h = jnp.asarray(rng.normal(size=(n, c)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.5, 2.0, size=(e, c) if weight_kind == "vector" else (e,))
                    .astype(np.float32))
    return n, c, snd, rcv, h, w


GS_ENTRIES = {
    # the conv stacks' entry with a batch whose certificate failed, and the op itself
    "gather_scatter_sum": lambda fs, h, s, r, n, w: fs.gather_scatter_sum(
        h, s, r, n, w, hints=types.SimpleNamespace(
            senders=s, receivers=r, meta=types.SimpleNamespace(gs_fits=False))),
    "fused_gather_scatter": lambda fs, h, s, r, n, w: fs.fused_gather_scatter(
        h, s, r, n, w, fits=False),
}


@pytest.mark.parametrize("entry", list(GS_ENTRIES))
@pytest.mark.parametrize("weight_kind", ["scalar", "vector"])
@pytest.mark.parametrize("order", list(DERIVATIVES))
def test_uncertified_gather_scatter_sum_is_the_tiled_pair(monkeypatch, order, weight_kind, entry):
    """No layout certificate: ``segment.gather`` x weight -> ``segment_sum``
    with ``fits=False``. Value, VJP (h and weight) and the gradient of a force
    loss equal ``reference_gather_scatter``'s; every ``[E, C] -> [N, C]`` sum
    (by the sorted receivers forward, by the unsorted senders in the transposes)
    is one tiled ``fused_segment_sum`` call, no scatter-add onto ``[N, C]`` rows
    and no ``cond`` is left."""
    from hydragnn_tpu.ops import fused_scatter as fs

    monkeypatch.setenv("HYDRAGNN_FUSED_SCATTER", "1")
    resident_traces = _count_kernel_traces(monkeypatch)
    n, c, snd, rcv, h, w = _gs_operands(weight_kind)
    assert fs.gather_scatter_route(h, snd.shape[0], n, False) == "the tiled sum takes these rows"
    pair = DERIVATIVES[order](lambda h, w: GS_ENTRIES[entry](fs, h, snd, rcv, n, w))
    plain = DERIVATIVES[order](lambda h, w: reference_gather_scatter(h, snd, rcv, n, w))
    jaxpr = jax.make_jaxpr(pair)(h, w)
    scatters = _row_scatters(jax.make_jaxpr(plain)(h, w), n, c)
    assert scatters >= {"forward": 1, "vjp": 2, "grad_of_grad": 3}[order]
    assert _pallas_calls(jaxpr) == scatters and _row_scatters(jaxpr, n, c) == 0
    assert resident_traces == []
    assert not any(eqn.primitive.name == "cond" for eqn in _primitives(jaxpr))
    _assert_trees_close(jax.jit(pair)(h, w), jax.jit(plain)(h, w), 3e-6)


def test_gather_scatter_route_is_shapes_and_certificate_alone():
    """Where the tiled sum admits the ``[E, C]`` rows the pair runs whatever
    the certificate says; elsewhere the resident kernel needs its certificate
    and its budget."""
    from hydragnn_tpu.ops import fused_scatter as fs
    from hydragnn_tpu.ops import routing

    wide, narrow = jnp.zeros((4504, 256)), jnp.zeros((4504, 64))
    for fits in (True, None, False):
        assert fs.gather_scatter_route(wide, 225024, 4504, fits) == "the tiled sum takes these rows"
    assert fs.gather_scatter_route(narrow, 225024, 4504, True) is None
    assert fs.gather_scatter_route(narrow, 225024, 4504, None) is None
    assert fs.gather_scatter_route(narrow, 225024, 4504, False) == "no layout certificate"
    assert "resident blocks" in fs.gather_scatter_route(jnp.zeros((20488, 64)), 512, 20488, True)
    assert "multiple of 8" in fs.gather_scatter_route(jnp.zeros((4500, 256)), 512, 4500, True)
    with routing.xla_only("mesh step"):
        assert fs.gather_scatter_route(wide, 225024, 4504, True) == "mesh step"


@pytest.mark.parametrize("weight_kind", ["scalar", "vector"])
def test_certified_kernels_filter_cotangent_closes_on_the_pair(monkeypatch, weight_kind):
    """``fused_gather_scatter``'s VJP reads ``h[senders]`` and
    ``dout[receivers]`` through ``segment.gather``: in the gradient of a force
    loss their transposes are tiled sums, not XLA scatter-adds."""
    from hydragnn_tpu.ops import fused_scatter as fs

    monkeypatch.setenv("HYDRAGNN_FUSED_SCATTER", "1")
    n, c, snd, rcv, h, w = _gs_operands(weight_kind)
    rcv = jnp.sort(jnp.minimum(rcv, n - 2))
    snd = jnp.clip(rcv + (snd % 16) - 8, 0, n - 2)  # inside the receivers' window
    kernel = DERIVATIVES["grad_of_grad"](
        lambda h, w: fs.fused_gather_scatter(h, snd, rcv, n, w, fits=True))
    plain = DERIVATIVES["grad_of_grad"](
        lambda h, w: reference_gather_scatter(h, snd, rcv, n, w))
    jaxpr = jax.make_jaxpr(kernel)(h, w)
    assert _row_scatters(jaxpr, n, c) == 0
    _assert_trees_close(jax.jit(kernel)(h, w), jax.jit(plain)(h, w), 2e-5)
