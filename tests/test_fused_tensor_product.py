"""MACE's fused tensor product (``ops/fused_tensor_product.py``) in the Pallas
interpreter against the XLA path on the same operands: the four kernels one
by one, second derivatives through their ``custom_vjp`` rules, and a
two-layer MACE's energies, forces and force-loss parameter gradient with the
kernels on and off. Mosaic's own verdict is ``tests/test_tpu_compile.py``'s,
the chip's ``chip_smoke.py``'s.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.models import mace
from hydragnn_tpu.models.harmonics import coupling_paths
from hydragnn_tpu.ops import fused_tensor_product as ftp
from hydragnn_tpu.ops import routing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
C = 8
N, E = 300, 256  # three 128-node windows, two blocks of 128 edges


def _plan(slab: int, channels: int = C) -> ftp.Plan:
    l_in = {16: 0, 40: 1}[slab]
    paths = tuple(coupling_paths(l_in, 3, 3))
    plan = mace.couplings(paths, (l_in + 1) ** 2, 16, channels)[1]
    assert plan.slab == slab
    return plan


def _receivers(rng, layout: str) -> np.ndarray:
    if layout == "unsorted":
        return rng.integers(0, N, E).astype(np.int32)
    # sorted; block 0 stays inside nodes 0..39 (nodes 7 and 21 receive
    # nothing), block 1 runs from node 100 over two window boundaries to 290
    # and ends in 40 padded slots at the dummy node N - 1
    first = np.sort(rng.choice(np.setdiff1d(np.arange(40), [7, 21]), 128))
    second = np.sort(rng.integers(100, 291, 88))
    return np.concatenate([first, second, np.full(40, N - 1)]).astype(np.int32)


def _operands(plan: ftp.Plan, layout: str, seed: int = 0):
    rng = np.random.default_rng(seed)
    rcv = _receivers(rng, layout)
    real = (np.arange(E) < E - 40).astype(np.float32)  # padded slots: K = R = 0
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    hs = f32(rng.normal(size=(E, plan.m_in * C)))
    kt = f32(rng.normal(size=(plan.n_k, E)) * real)
    rt = f32(rng.normal(size=(plan.n_paths * C, E)) * real)
    g = f32(rng.normal(size=(N, plan.slab * C)))
    return jnp.asarray(rcv), hs, kt, rt, g


def _close(got, want, tol=2e-6):
    scale = float(jnp.abs(want).max())
    assert scale > 0
    assert float(jnp.abs(got - want).max()) <= tol * scale


@pytest.mark.parametrize("layout", ["sorted", "unsorted"])
@pytest.mark.parametrize("slab", [16, 40])
def test_four_kernels_match_the_xla_path(slab, layout):
    """tp_out against the slab-building reference, tp_dhs / tp_dk / tp_dr
    against its VJP: padded slots, a block over three windows, empty nodes;
    then any edge order at all."""
    plan = _plan(slab)
    rcv, hs, kt, rt, g = _operands(plan, layout)
    static = (plan, N, True)
    want, vjp = jax.vjp(lambda *a: ftp.reference_tensor_product(plan, rcv, *a, N), hs, kt, rt)
    _close(ftp.tp_out(static, rcv, hs, kt, rt), want)
    if layout == "sorted":
        assert not np.asarray(want)[[7, 21]].any() and np.asarray(want)[:40].any()
    d_hs, d_kt, d_rt = vjp(g)
    _close(ftp.tp_dhs(static, rcv, g, kt, rt), d_hs)
    _close(ftp.tp_dk(static, rcv, g, hs, rt), d_kt)
    _close(ftp.tp_dr(static, rcv, g, hs, kt), d_rt)


@pytest.mark.parametrize("slab", [16, 40])
def test_closed_under_grad_of_grad(slab):
    """The gradient of a function of the first derivatives: every rule's
    backward runs (each calls the other three kernels)."""
    plan = _plan(slab)
    rcv, hs, kt, rt, g = _operands(plan, "sorted", seed=1)
    rcv, hs, kt, rt = rcv[:128], hs[:128], kt[:, :128], rt[:, :128]  # one block

    def second(fn):
        def inner(hs, kt, rt):
            out, vjp = jax.vjp(fn, hs, kt, rt)
            return sum(jnp.sum(jnp.sin(d)) for d in vjp(jnp.tanh(out) + g)) + jnp.sum(out ** 2)
        return jax.jit(jax.grad(inner, argnums=(0, 1, 2)))

    fused = lambda *a: ftp.fused_tensor_product(plan, rcv, *a, N, interpret=True)
    reference = lambda *a: ftp.reference_tensor_product(plan, rcv, *a, N)
    for got, want in zip(second(fused)(hs, kt, rt), second(reference)(hs, kt, rt)):
        _close(got, want, tol=1e-5)


def test_route_is_static():
    plan = _plan(40, channels=128)
    assert ftp.tensor_product_route(plan, 3584, 56, jnp.float32, interpret=False) is None
    assert ftp.tensor_product_route(_plan(40), 256, 56, jnp.float32, interpret=True) is None
    assert "channels" in ftp.tensor_product_route(_plan(40), 256, 56, jnp.float32, interpret=False)
    assert "edge slots" in ftp.tensor_product_route(plan, 250, 56, jnp.float32)
    assert "bfloat16" in ftp.tensor_product_route(plan, 256, 56, jnp.bfloat16)
    assert "VMEM" in ftp.tensor_product_route(plan, 256, 8192, jnp.float32)
    with routing.xla_only("mesh step"):
        assert ftp.tensor_product_route(plan, 256, 56, jnp.float32) == "mesh step"


# -- the model ------------------------------------------------------------------------------


@pytest.fixture(scope="module")
def case():
    spec = importlib.util.spec_from_file_location(
        "mace_reference_case", os.path.join(ROOT, "tests", "test_mace_reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    case = mod.Case()
    from hydragnn_tpu.graphs.batching import PadSpec, collate

    samples = mod.program.to_samples(case.graphs, 1.0)
    case.batch = jax.tree.map(jnp.asarray, collate(
        samples, PadSpec(n_node=case.real_n + 5, n_edge=256, n_graph=3)))
    case.flatten = mod.weights.flat_dict
    return case


def test_two_layer_mace_with_the_kernels_on_and_off(case, monkeypatch):
    """Energies, forces (first derivatives) and the force loss's parameter
    gradient (grad of grad) of the two-layer model, flag on against flag off;
    off the TPU the default is off, and an ``xla_only`` program stays XLA."""
    energies = lambda params, batch: case.model.apply({"params": params}, batch, train=False)[0]

    def under_mesh(params, batch):
        with routing.xla_only("mesh step"):
            return energies(params, batch)

    # a fresh function a count: a trace is cached by function, not by flag
    calls = lambda fn: str(jax.make_jaxpr(lambda p, b: fn(p, b))(
        case.params, case.batch)).count("pallas_call")
    assert calls(energies) == 0
    want = jax.device_get(jax.jit(case._program)(case.params, case.batch))
    monkeypatch.setenv("HYDRAGNN_FUSED_TENSOR_PRODUCT", "1")
    assert calls(energies) == 2 and calls(under_mesh) == 0  # one a layer
    got = jax.device_get(jax.jit(case._program)(case.params, case.batch))  # a fresh trace
    _close(got[0], want[0], tol=1e-5)
    _close(got[1], want[1], tol=1e-5)
    got_g, want_g = case.flatten(got[2]), case.flatten(want[2])
    live = 0
    for name, w in want_g.items():
        if np.abs(w).max() > 0:
            live += 1
            _close(got_g[name], w, tol=2e-5)
    assert live >= len(want_g) - 1
