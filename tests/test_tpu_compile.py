"""Compile-only pre-flight for the TPU-default code path.

Nothing else in this suite touches a TPU: the tests run on 8 virtual CPU
devices and the kernels in the Pallas interpreter, which is MORE permissive
than Mosaic (no (8, 128) tiling rule, no alignment proofs, bf16 compares, rank-3
masks...). This file closes that gap without a chip: libtpu is installed, and
``get_topology_desc("v5e:2x2", "tpu")`` hands out compile-only v5e devices, so
every default-on kernel is lowered with ``interpret=False`` and run through
Mosaic's real compiler — expecting exactly what its static route
(``ops/routing.py``) says: it compiles into a Mosaic custom call, or it is
routed to XLA and the program holds none. Numerics stay with the
interpret-mode parity tests and, on the device, ``chip_smoke.py``.

libtpu allows one process at a time (``/tmp/libtpu_lockfile``); the tests
skip, with that reason, only when another process holds the lock.
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
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from hydragnn_tpu.ops import fused_cell_list as fcl
from hydragnn_tpu.ops import fused_scatter as fs
from hydragnn_tpu.ops import fused_softmax as fsm
from hydragnn_tpu.ops import fused_tensor_product as ftp
from hydragnn_tpu.ops import routing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DTYPES = [jnp.float32, jnp.bfloat16]
# the qm9.json batch the issue's pre-flight table was taken at
N, E = 1864, 19200


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    except jax.errors.JaxRuntimeError as exc:
        if "libtpu" in str(exc) and "lockfile" in str(exc):
            pytest.skip(f"libtpu is held by another process: {exc}")
        raise
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


def _compile(fn, args, sharding):
    specs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), args
    )
    return jax.jit(fn).lower(*specs).compile()


def _mosaic_calls(compiled) -> int:
    return len(re.findall(r'custom_call_target="tpu_custom_call"', compiled.as_text()))


def _expect(route, fn, args, v5e):
    """The program compiles for the v5e and holds a Mosaic call exactly when
    the static route says the kernel runs."""
    compiled = _compile(fn, args, SingleDeviceSharding(v5e[0]))
    assert (_mosaic_calls(compiled) > 0) == (route is None), routing.describe(route)


def _grad(fn):
    return jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum())


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("channels", [64, 256])
def test_gather_scatter_compiles(v5e, dtype, channels):
    h = jnp.zeros((N, channels), dtype)
    ids = jnp.zeros((E,), jnp.int32)
    w = jnp.zeros((E,), dtype)
    route = fs.scatter_route(h, E, N, fs.GS_CERT_WINDOW)
    assert route is None
    for fits in (True, None):  # certified, and the in-program cond fallback
        fn = lambda h, s, r, w, fits=fits: fs.fused_gather_scatter(
            h, s, r, N, w, fits=fits, interpret=False)
        _expect(route, fn, (h, ids, ids, w), v5e)
    fn = lambda h, s, r, w: fs.fused_gather_scatter(
        h, s, r, N, w, fits=True, interpret=False)
    _expect(route, _grad(fn), (h, ids, ids, w), v5e)  # the transposed kernel


def test_gather_scatter_compiles_with_schnets_filter_rows(v5e):
    """SchNet's form at the benchmark cell's worst-case bucket (batch 20, 256
    filters: 4,504 node and 225,024 edge slots, which the resident rule
    admits): a per-channel ``[E, 256]`` weight, and the step's three orders of
    differentiation in ``h`` and the weight (the second derivative's kernels
    are the same kernel with the endpoints swapped; the weight's cotangent is
    XLA's)."""
    n, e, c = 4504, 225024, 256
    h, w = jnp.zeros((n, c), jnp.float32), jnp.zeros((e, c), jnp.float32)
    ids = jnp.zeros((e,), jnp.int32)
    route = fs.scatter_route(h, e, n, fs.GS_CERT_WINDOW)
    assert route is None
    fn = lambda h, s, r, w: fs.fused_gather_scatter(h, s, r, n, w, fits=True, interpret=False)
    _expect(route, fn, (h, ids, ids, w), v5e)
    # a square after the sum, so that each order depends on h and the weight
    first = jax.grad(lambda h, s, r, w: (fn(h, s, r, w) ** 2).sum(), argnums=(0, 3))
    _expect(route, first, (h, ids, ids, w), v5e)
    second = jax.grad(lambda h, s, r, w: sum(g.sum() for g in first(h, s, r, w)), argnums=(0, 3))
    _expect(route, second, (h, ids, ids, w), v5e)
    # 2 x N x 256 lanes x 4 B <= 10 MiB: 5,120 node slots are the most
    assert fs.scatter_route(jnp.zeros((5120, c), jnp.float32), e, 5120, fs.GS_CERT_WINDOW) is None
    assert "resident blocks" in fs.scatter_route(
        jnp.zeros((5128, c), jnp.float32), e, 5128, fs.GS_CERT_WINDOW)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
# 3 and 384: PaiNN's vector message, a rank-2 [E, 3F] slab, at F = 1 and 128
@pytest.mark.parametrize("channels", [64, 3, 384])
def test_segment_sum_compiles(v5e, dtype, channels, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # interpret off
    data = jnp.zeros((E, channels), dtype)
    ids = jnp.zeros((E,), jnp.int32)
    fn = lambda d, i: fs.fused_segment_sum(d, i, N, fits=True)
    _expect(fs.scatter_route(data, E, N, 128), fn, (data, ids), v5e)


# painn_mlip_md17.fill's one padded shape: 1,024 molecules x 21 atoms, 318 edges
PAINN_N, PAINN_E = 21512, 325760


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("channels", [384, 128])
def test_tiled_segment_sum_compiles_at_the_painn_shape(v5e, dtype, channels, monkeypatch):
    """Past the resident budget (63 MiB at C 384) ``fused_segment_sum`` is the
    tiled form: a Mosaic call whose VMEM need does not grow with N."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # interpret off
    data = jnp.zeros((PAINN_E, channels), dtype)
    ids = jnp.zeros((PAINN_E,), jnp.int32)
    assert "VMEM" in fs.scatter_route(data, PAINN_E, PAINN_N, 128)
    route = fs.scatter_route(data, PAINN_E, PAINN_N, 128, tiled=True)
    assert route is None
    fn = lambda d, i: fs.fused_segment_sum(d, i, PAINN_N)
    with jax.default_matmul_precision("highest"):  # the cell's; the bf16 passes keep theirs
        _expect(route, fn, (data, ids), v5e)


# the cells' buckets that the RESIDENT rule admits and whose certificate fails:
# (N, E, C) of SchNet's worst-case and typical buckets (batch 20, 256 filters),
# DimeNet++'s small bucket (the block exchange's [E, K I] rows) and EGNN's worst case
UNCERTIFIED = {"schnet_worst": (4504, 225024, 256), "schnet_typical": (1656, 82560, 256),
               "dimenet_small": (208, 10112, 3200), "egnn_worst": (7112, 227456, 128)}


@pytest.mark.parametrize("shape", list(UNCERTIFIED))
def test_uncertified_segment_sum_is_the_tiled_form_at_the_cells_shapes(v5e, shape, monkeypatch):
    """``fits=False`` at a shape the resident rule admits: one Mosaic call (the
    tiled form), no ``lax.cond`` and no XLA scatter, forward and transposed."""
    from hydragnn_tpu.graphs import segment

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # interpret off
    n, e, c = UNCERTIFIED[shape]
    data, x = jnp.zeros((e, c), jnp.float32), jnp.zeros((n, c), jnp.float32)
    ids = jnp.zeros((e,), jnp.int32)
    assert fs.scatter_route(data, e, n, 128) is None
    assert fs.scatter_route(data, e, n, 128, tiled=True) is None
    with jax.default_matmul_precision("highest"):
        for fn, args in ((lambda d, i: fs.fused_segment_sum(d, i, n, fits=False), (data, ids)),
                         (_grad(lambda x, i: segment.gather(x, i, fits=False)), (x, ids))):
            compiled = _compile(fn, args, SingleDeviceSharding(v5e[0]))
            text = compiled.as_text()
            assert _mosaic_calls(compiled) == 1
            assert " conditional(" not in text and not re.search(r" scatter\(", text)


def test_schnets_uncertified_aggregate_compiles_through_grad_of_grad(v5e, monkeypatch):
    """``gather_scatter_sum`` at SchNet's worst-case bucket with ``gs_fits``
    False: the pair's sums are the tiled kernel in all four passes of a force
    loss, and no XLA scatter onto ``[N, 256]`` rows is left."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, e, c = UNCERTIFIED["schnet_worst"]
    x, w = jnp.zeros((n, c)), jnp.zeros((e, c))
    ids = jnp.zeros((e,), jnp.int32)
    assert fs.gather_scatter_route(x, e, n, False) is not None
    assert fs.gather_scatter_route(x, e, n, True) is not None  # a certified batch too

    def force_loss(x, w, snd, rcv):
        energy = lambda x, w: jnp.sum(jnp.tanh(fs.gather_scatter_sum(x, snd, rcv, n, w)))
        return sum(jnp.sum(g ** 2) for g in jax.grad(energy, argnums=(0, 1))(x, w))

    with jax.default_matmul_precision("highest"):
        compiled = _compile(jax.grad(force_loss, argnums=(0, 1)), (x, w, ids, ids),
                            SingleDeviceSharding(v5e[0]))
    assert _mosaic_calls(compiled) >= 4
    assert not re.search(rf"f32\[{n},{c}\]\S* scatter\(", compiled.as_text())


def test_gather_sum_pair_compiles_through_grad_of_grad(v5e, monkeypatch):
    """A force loss through one ``segment.gather`` / ``segment.segment_sum``
    pair at the PaiNN shape: every transposed gather is the kernel again."""
    from hydragnn_tpu.graphs import segment

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, e, c = PAINN_N, PAINN_E, 384
    x, w = jnp.zeros((n, c)), jnp.zeros((e, c))
    ids = jnp.zeros((e,), jnp.int32)

    def force_loss(x, w, rcv, snd):
        energy = lambda x: jnp.sum(jnp.tanh(
            segment.segment_sum(segment.gather(x, rcv) * w, snd, n)))
        return jnp.sum(jax.grad(energy)(x) ** 2)

    compiled = _compile(jax.grad(force_loss, argnums=(0, 1)), (x, w, ids, ids),
                        SingleDeviceSharding(v5e[0]))
    assert _mosaic_calls(compiled) >= 3
    assert not re.search(rf"f32\[{n},{c}\]\S* scatter\(", compiled.as_text())


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
def test_segment_softmax_compiles(v5e, dtype):
    logits = jnp.zeros((E + fsm.self_loop_pad(E) + N, 6), dtype)  # GAT: 6 heads
    ids = jnp.zeros((logits.shape[0],), jnp.int32)
    route = fsm.segment_softmax_route(logits, N)
    assert route is None
    fn = lambda x, i: fsm.fused_segment_softmax(x, i, N, fits=True, interpret=False)
    _expect(route, fn, (logits, ids), v5e)
    _expect(route, _grad(fn), (logits, ids), v5e)


def _pallas_grids(jaxpr) -> list:
    """The grid of every ``pallas_call`` in a jaxpr, sub-jaxprs included."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    grids += _pallas_grids(sub)
    return grids


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("shape, mask_shape", [
    ((65, 4, 29, 29), (65, 1, 1, 29)),  # GPS blocks of the qm9 batch
    # gps_egnn_mlip_oc20.fill's call (batch 8 and the pad graph, 16 heads,
    # N_max 232): until PR 47 a grid of 4,176 eight-row steps
    ((9, 16, 232, 232), (9, 1, 1, 232)),
    ((3, 16, 232, 232), (3, 16, 232, 232)),  # a mask block beside the logits
], ids=["qm9", "gps_cell", "full_mask"])
def test_masked_softmax_compiles(v5e, dtype, shape, mask_shape):
    logits = jnp.zeros(shape, dtype)
    mask = jnp.zeros(mask_shape, bool)
    route = fsm.masked_softmax_route(logits)
    assert route is None
    fn = lambda x, m: fsm.fused_masked_softmax(x, m, interpret=False)
    _expect(route, fn, (logits, mask), v5e)
    _expect(route, _grad(fn), (logits, mask), v5e)
    # a grid step moves a block VMEM holds, not eight rows
    (grid,) = _pallas_grids(jax.make_jaxpr(fn)(logits, mask).jaxpr)
    assert np.prod(grid) < 100, grid


@pytest.mark.parametrize("n, box", [
    (512, 16.0),  # 3^3 cells, 64-row windows
    # chip_smoke.py's MD geometry (9^3 cells, 24-row windows): ~10 s of XLA
    pytest.param(4096, 49.9, marks=pytest.mark.slow),
])
def test_cell_list_compiles(v5e, n, box):
    from hydragnn_tpu import md

    cutoff = 5.0
    cell = np.eye(3, dtype=np.float32) * box
    grid, cap = md.plan_cell_grid(cell, cutoff, n)
    route = fcl.cell_list_route(n, int(np.prod(grid)), fcl.cell_window(cap))
    assert route is None
    fn = lambda pos: fcl.fused_binned_radius_graph(
        pos, cutoff, 64 * n, jnp.asarray(cell), jnp.ones(3, bool), grid, cap,
        interpret=False)
    _expect(route, fn, (jnp.zeros((n, 3), jnp.float32),), v5e)


# mace_mlip_mptrj.fill's three pad buckets (nodes, edge slots), C = 128
MACE_BUCKETS = [(80, 4992), (192, 12032), (896, 56960)]


def _mace_plan(l_in: int, channels: int = 128):
    from hydragnn_tpu.models import mace
    from hydragnn_tpu.models.harmonics import coupling_paths

    paths = tuple(coupling_paths(l_in, 3, 3))
    return mace.couplings(paths, (l_in + 1) ** 2, 16, channels)[1]


@pytest.mark.parametrize("kind", ["out", "dhs", "dk", "dr"])
@pytest.mark.parametrize("l_in", [0, 1], ids=["S16", "S40"])
# + the most nodes the VMEM rule admits at S 40 (96 of its 100 MiB)
@pytest.mark.parametrize("n, e", MACE_BUCKETS + [(2176, 131072)])
def test_tensor_product_compiles(v5e, n, e, l_in, kind):
    """Each of the four kernels, both layers' path sets, at the cell's
    buckets and at the edge of the route's VMEM budget — under the harness's
    ``highest`` default matmul precision, which the kernels' bf16 passes must
    not inherit."""
    plan = _mace_plan(l_in)
    assert (plan.slab, plan.m_in, plan.n_paths) == ((16, 1, 4), (40, 4, 10))[l_in]
    route = ftp.tensor_product_route(plan, e, n, jnp.float32, interpret=False)
    assert route is None
    static = (plan, n, False)
    rcv = jnp.zeros((e,), jnp.int32)
    hs, g = jnp.zeros((e, plan.m_in * 128)), jnp.zeros((n, plan.slab * 128))
    kt, rt = jnp.zeros((plan.n_k, e)), jnp.zeros((plan.n_paths * 128, e))
    fn, args = {"out": (ftp.tp_out, (rcv, hs, kt, rt)), "dhs": (ftp.tp_dhs, (rcv, g, kt, rt)),
                "dk": (ftp.tp_dk, (rcv, g, hs, rt)), "dr": (ftp.tp_dr, (rcv, g, hs, kt))}[kind]
    with jax.default_matmul_precision("highest"):
        _expect(route, lambda *a: fn(static, *a), args, v5e)


def test_tensor_product_static_routes(v5e):
    plan = _mace_plan(1)
    assert "bfloat16" in ftp.tensor_product_route(plan, 3584, 56, jnp.bfloat16, interpret=False)
    assert "channels" in ftp.tensor_product_route(_mace_plan(1, 64), 3584, 56, jnp.float32,
                                                   interpret=False)
    assert "edge slots" in ftp.tensor_product_route(plan, 3600, 56, jnp.float32, interpret=False)
    assert "VMEM" in ftp.tensor_product_route(plan, 3584, 2304, jnp.float32, interpret=False)
    with routing.xla_only("mesh step"):
        assert ftp.tensor_product_route(plan, 3584, 56, jnp.float32, interpret=False) == "mesh step"



def test_static_routes_leave_no_mosaic_call(v5e):
    """What the rule routes to XLA really is XLA in the compiled program —
    by dtype, by shape, and under a mesh-step context."""
    ids = jnp.zeros((E,), jnp.int32)
    gs = lambda h, s, w: fs.fused_gather_scatter(
        h, s, s, h.shape[0], w, fits=True, interpret=False)

    h16 = jnp.zeros((N, 64), jnp.float16)
    route = fs.scatter_route(h16, E, N, fs.GS_CERT_WINDOW)
    assert route == "dtype float16"
    _expect(route, gs, (h16, ids, jnp.zeros((E,), jnp.float16)), v5e)

    small = jnp.zeros((136, 64), jnp.float32)  # fewer rows than one window
    route = fs.scatter_route(small, E, 136, fs.GS_CERT_WINDOW)
    assert route is not None and "window" in route
    _expect(route, gs, (small, ids, jnp.zeros((E,), jnp.float32)), v5e)

    wide = jnp.zeros((16384, 128), jnp.float32)  # past the resident budget
    route = fs.scatter_route(wide, E, 16384, fs.GS_CERT_WINDOW)
    assert route is not None and "VMEM" in route
    _expect(route, gs, (wide, ids, jnp.zeros((E,), jnp.float32)), v5e)

    h = jnp.zeros((N, 64), jnp.float32)

    def under_mesh(h, s, w):
        with routing.xla_only("mesh step"):
            assert fs.scatter_route(h, E, N, fs.GS_CERT_WINDOW) == "mesh step"
            return gs(h, s, w)

    _expect("mesh step", under_mesh, (h, ids, jnp.zeros((E,), jnp.float32)), v5e)


# -- whole step programs, as routed on a TPU (slow: model init + XLA compile) ----


def _load_example(relpath, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _build(config, samples):
    """What run_training builds before its first step, on the CPU."""
    from hydragnn_tpu.config import load_config, update_config
    from hydragnn_tpu.models.create import create_model_config
    from hydragnn_tpu.preprocess.load_data import dataset_loading_and_splitting
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.step import create_train_state, resolve_training_precision

    config = load_config(config)
    loaders = dataset_loading_and_splitting(config, samples=samples)
    config = update_config(config, *(l.samples for l in loaders))
    model = create_model_config(config)
    training = config["NeuralNetwork"]["Training"]
    optimizer = select_optimizer(training["Optimizer"])
    state = create_train_state(model, optimizer, next(iter(loaders[0])))
    return model, optimizer, state, loaders[0], resolve_training_precision(training)


def _qm9():
    with open(os.path.join(ROOT, "examples/qm9/qm9.json")) as f:
        config = json.load(f)
    example = _load_example("examples/qm9/qm9.py", "qm9_example")
    return _build(config, example.synthetic_molecules(640, seed=0))


@pytest.mark.slow
def test_qm9_train_step_compiles_as_shipped(v5e, monkeypatch):
    from hydragnn_tpu.train.step import make_train_step

    model, optimizer, state, loader, precision = _qm9()
    assert precision == jnp.bfloat16
    batch = jax.tree.map(jnp.asarray, next(iter(loader)))
    assert batch.meta.gs_fits
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = make_train_step(model, optimizer, precision)
    compiled = _compile(step, (state, batch), SingleDeviceSharding(v5e[0]))
    # 4 GIN layers forward + 3 backward (the raw features take no gradient)
    assert _mosaic_calls(compiled) == 7


@pytest.mark.slow
@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_lj_mlip_step_compiles(v5e, monkeypatch, precision):
    from hydragnn_tpu.datasets import lennard_jones_data
    from hydragnn_tpu.models.mlip import make_mlip_train_step

    config = copy.deepcopy(
        _load_example("examples/LennardJones/LennardJones.py", "lj_example").CONFIG
    )
    config["NeuralNetwork"]["Training"]["precision"] = precision
    samples = lennard_jones_data(number_configurations=64, cells_per_dim=2)
    model, optimizer, state, loader, dtype = _build(config, samples)
    batch = jax.tree.map(jnp.asarray, next(iter(loader)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = make_mlip_train_step(model, optimizer, dtype)
    compiled = _compile(step, (state, batch), SingleDeviceSharding(v5e[0]))
    assert _mosaic_calls(compiled) > 0  # grad-of-grad THROUGH the kernels


@pytest.mark.slow
def test_four_device_mesh_step_compiles(v5e, monkeypatch):
    from hydragnn_tpu.parallel.step import make_parallel_train_step, stack_device_batches

    model, optimizer, state, loader, precision = _qm9()
    loader.set_group(4)
    stacked = stack_device_batches([b for _, b in zip(range(4), loader)])
    mesh = Mesh(np.array(v5e), ("data",))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = make_parallel_train_step(model, optimizer, mesh, precision)
    place = lambda tree, spec: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)), tree)
    compiled = step.lower(place(state, P()), place(stacked, P("data"))).compile()
    text = compiled.as_text()
    assert _mosaic_calls(compiled) == 0  # routed to XLA under the GSPMD mesh
    assert len(re.findall(r"= \S+ all-reduce(?:-start)?\(", text)) >= 1
    assert not re.search(r"all-gather|all-to-all|collective-permute", text)


def test_mace_mlip_step_compiles_without_the_slab(v5e, monkeypatch):
    """The MACE energy-and-force step at the cell's widths and smallest
    bucket: grad-of-grad THROUGH the fused tensor product, and no array with
    S C = 40 x 128 elements an edge anywhere in the compiled program."""
    import optax

    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.graphs.batching import PadSpec, collate
    from hydragnn_tpu.graphs.graph import GraphSample
    from hydragnn_tpu.models.create import create_model_config
    from hydragnn_tpu.models.mlip import make_mlip_train_step
    from hydragnn_tpu.train.step import TrainState

    with open(os.path.join(ROOT, "benchmark/configs/mace_mlip_mptrj.json")) as f:
        bench = json.load(f)
    config = {k: copy.deepcopy(bench[k]) for k in ("Verbosity", "Dataset", "NeuralNetwork")}
    rng = np.random.default_rng(0)
    n_atoms, n, e = 20, *MACE_BUCKETS[0]
    receivers = np.repeat(np.arange(n_atoms), 64)
    sample = GraphSample(
        x=rng.integers(1, 90, (n_atoms, 1)).astype(np.float32),
        pos=rng.normal(size=(n_atoms, 3)).astype(np.float32),
        senders=rng.integers(0, n_atoms, receivers.size).astype(np.int32),
        receivers=receivers.astype(np.int32),
        edge_shifts=np.zeros((receivers.size, 3), np.float32),
        energy_y=np.zeros((1,), np.float32), forces_y=np.zeros((n_atoms, 3), np.float32))
    samples = [sample, sample]
    model = create_model_config(update_config(config, samples))
    batch = collate(samples, PadSpec(n_node=n, n_edge=e, n_graph=3))
    abstract = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    optimizer = optax.adamw(1e-4)

    def init():
        params = model.init(jax.random.PRNGKey(0), batch, train=False)["params"]
        return TrainState(params=params, batch_stats={}, opt_state=optimizer.init(params),
                          step=jnp.zeros((), jnp.int32))

    state = jax.eval_shape(init)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = make_mlip_train_step(model, optimizer)
    with jax.default_matmul_precision("highest"):
        compiled = _compile(step, (state, abstract(batch)), SingleDeviceSharding(v5e[0]))
    text = compiled.as_text()
    # layer 2: tp_out x 4 (the forward, and one a derivative's transpose) and
    # the three derivatives x 4; layer 1's sender features are the species
    # embedding, no function of the positions, so nine of its sixteen fall away
    assert _mosaic_calls(compiled) == 23
    assert not re.search(rf"f32\[{e},(40,128|5120|16,128|2048)\]", text)


def test_gps_mlip_step_compiles_scanned_and_fits_the_compile_cache(v5e, monkeypatch):
    """The GPS energy-and-force step at the cell's widths (ten 384-wide
    layers, 16 heads, dense ``[G, 232]`` attention blocks) and first bucket,
    its stack scanned and its layer rematerialised as the cell's configuration
    asks: grad-of-grad THROUGH the scanned body (the row-sum kernels,
    ``segment.gather``'s closed VJP and the masked softmax's custom VJP inside
    it), four ``while`` loops (forward, forces, and the parameter gradient
    through both) over all ten layers (the last has no coordinate gate and
    runs in the body with zeros for one), the Mosaic calls of ONE layer body,
    and an executable the chip machines' compile cache can hold: under 64 MiB
    as the cache stores it, where the unrolled step is 171-208 MB and is
    compiled anew in every run (PERF.md section 6, "GPS, three attempts")."""
    import optax
    from jax._src import compilation_cache

    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.graphs.batching import PadSpec, collate
    from hydragnn_tpu.graphs.graph import GraphSample
    from hydragnn_tpu.models.create import create_model_config
    from hydragnn_tpu.models.mlip import make_mlip_train_step
    from hydragnn_tpu.preprocess.encodings import attach_lap_pe
    from hydragnn_tpu.train.step import TrainState

    with open(os.path.join(ROOT, "benchmark/configs/gps_egnn_mlip_oc20.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "benchmark/traffic/oc20_gps_fill.json")) as f:
        batch_size = json.load(f)["training"]["batch_size"]
    config = {k: copy.deepcopy(bench[k]) for k in ("Verbosity", "Dataset", "NeuralNetwork")}
    arch = config["NeuralNetwork"]["Architecture"]
    arch["max_graph_nodes"] = 232  # the cell's: its largest structure (225) in whole 8s
    training = config["NeuralNetwork"]["Training"]
    assert training["scan_conv_layers"] is True and training["conv_checkpointing"] is True
    rng = np.random.default_rng(0)
    n_atoms, k = 24, arch["max_neighbours"]
    senders = np.repeat(np.arange(n_atoms), k)
    sample = GraphSample(
        x=rng.integers(1, 90, (n_atoms, 1)).astype(np.float32),
        pos=rng.normal(size=(n_atoms, 3)).astype(np.float32),
        senders=senders.astype(np.int32),
        receivers=((senders + rng.integers(1, n_atoms, senders.size)) % n_atoms).astype(np.int32),
        edge_shifts=np.zeros((senders.size, 3), np.float32),
        energy_y=np.zeros((1,), np.float32), forces_y=np.zeros((n_atoms, 3), np.float32))
    attach_lap_pe(sample, arch["pe_dim"])
    samples = [sample] * batch_size
    model = create_model_config(update_config(config, samples))
    assert model.spec.max_graph_nodes == 232 and model.spec.scan_conv_layers
    # the typical bucket: the batch x ~100 atoms, 12 edges an atom, one padding graph
    n = 100 * batch_size + 8
    batch = collate(samples, PadSpec(n_node=n, n_edge=12 * (n - 8), n_graph=batch_size + 1,
                                     node_cap=225))
    assert batch.meta.max_n_node == 225  # the certificate: dense blocks
    abstract = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    optimizer = optax.adamw(1e-4)

    def init():
        variables = model.init(jax.random.PRNGKey(0), batch, train=False)
        return TrainState(params=variables["params"], batch_stats=variables["batch_stats"],
                          opt_state=optimizer.init(variables["params"]),
                          step=jnp.zeros((), jnp.int32))

    state = jax.eval_shape(init)
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(state.params)) > 23e6
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = make_mlip_train_step(model, optimizer)
    with jax.default_matmul_precision("highest"):
        compiled = _compile(step, (state, abstract(batch)), SingleDeviceSharding(v5e[0]))
    text = compiled.as_text()
    loops = re.findall(r"= \(([^\n]*?)\) while\(", text)
    assert len(loops) == 4
    # every loop carries arrays stacked over the ten layers, among them their own kernels
    assert all(re.search(r"f32\[10,384,384\]", carried) for carried in loops)
    assert not re.search(r"f32\[9,384,384\]", text)
    # one layer body's kernels in four passes (a batch of encodings certifies no id layout,
    # so the wide row sums take the tiled kernel and the 3-wide ones XLA's scatter), where
    # the unrolled stack holds 105
    assert _mosaic_calls(compiled) == 13
    stored = compilation_cache.compress_executable(compiled.runtime_executable().serialize())
    assert len(stored) < 64 * 2**20
