"""MACE at ``max_ell`` 3, correlation 3, two layers (C = 4) against the
benchmark's plain reference (``benchmark/reference/mace.py``, which builds its
harmonics, couplings and symmetric bases a second way and imports nothing of
the program): energy, forces and the parameter gradient of the force loss on
a padded two-graph periodic batch; symmetries; the contraction against a
brute-force symmetrised product; padding; the layout guard on the MLIP step.
"""

import copy
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.config import update_config
from hydragnn_tpu.graphs.batching import PadSpec, collate
from hydragnn_tpu.models import create_model_config
from hydragnn_tpu.models import mace as mace_model
from hydragnn_tpu.models.harmonics import symmetric_basis

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
C = 4


def _bench(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location("bench_" + "_".join(parts)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _bench("reference", "mace.py")
ref_mlip = _bench("reference", "mlip.py")
weights = _bench("lib", "weights.py")
crystals = _bench("generators", "crystals.py")
program = _bench("lib", "program.py")

CRYSTALS = {"count": 2, "radius": 6.0, "max_neighbours": 12, "volume_per_atom": 14.0,
            "n_species": 89, "sizes": {"seed": 0, "median": 5, "sigma": 0.3, "min": 3,
                                       "max": 7, "max_at": 0}}


class Case:
    """Model, seeded weights, a padded batch, and one jitted function each for
    program and reference: (node energies, forces, d force-loss / d params)."""

    def __init__(self):
        with open(os.path.join(ROOT, "benchmark", "configs", "mace_mlip_mptrj.json")) as f:
            bench_cfg = json.load(f)
        cfg = {k: copy.deepcopy(bench_cfg[k]) for k in program.PROGRAM_KEYS if k in bench_cfg}
        cfg["NeuralNetwork"]["Architecture"].update(hidden_dim=C, avg_num_neighbors=12.0)
        self.graphs = crystals.generate(CRYSTALS, 2**31 + 5)
        samples = program.to_samples(self.graphs, 1.0)
        self.cfg = update_config(cfg, samples)
        self.model = create_model_config(self.cfg)
        real_n = sum(s.num_nodes for s in samples)
        real_e = sum(s.num_edges for s in samples)
        self.real_n, self.real_e = real_n, real_e
        self.batch = jax.tree.map(jnp.asarray, collate(
            samples, PadSpec(n_node=real_n + 5, n_edge=real_e + 9, n_graph=3)))
        shapes = jax.eval_shape(
            lambda: self.model.init(jax.random.PRNGKey(0), self.batch, train=False))
        self.params = weights.make_weights(shapes["params"], 11, bench_cfg["weights"])
        self.flat = weights.flat_dict(self.params)
        stated = copy.deepcopy(bench_cfg)
        stated["NeuralNetwork"]["Architecture"].update(hidden_dim=C, avg_num_neighbors=12.0)
        self.hp = ref.hyperparameters(stated)
        self.real = {k: jnp.asarray(v) for k, v in ref_mlip.concat(self.graphs, 1.0).items()}
        self.program_fn = jax.jit(self._program)
        self.reference_fn = jax.jit(self._reference)

    def _program(self, params, batch):
        def energies(p, pos):
            return self.model.apply({"params": p}, batch.replace(pos=pos), train=False)[0][:, 0]

        def force_loss(p):
            f = -jax.grad(lambda pos: (energies(p, pos) * batch.node_mask).sum())(batch.pos)
            return (((f - batch.forces_y) ** 2) * batch.node_mask[:, None]).sum()

        forces = -jax.grad(lambda pos: (energies(params, pos) * batch.node_mask).sum())(batch.pos)
        return energies(params, batch.pos), forces, jax.grad(force_loss)(params)

    def _reference(self, flat, b):
        def energies(p, pos):
            return ref.node_energy(p, self.hp, b["x"], pos, b["senders"], b["receivers"], b["shifts"])

        def force_loss(p):
            f = -jax.grad(lambda pos: energies(p, pos).sum())(b["pos"])
            return ((f - b["forces"]) ** 2).sum()

        forces = -jax.grad(lambda pos: energies(flat, pos).sum())(b["pos"])
        return energies(flat, b["pos"]), forces, jax.grad(force_loss)(flat)


@pytest.fixture(scope="module")
def case():
    return Case()


@pytest.fixture(scope="module")
def both(case):
    return (jax.device_get(case.program_fn(case.params, case.batch)),
            jax.device_get(case.reference_fn(case.flat, case.real)))


@pytest.mark.parametrize("what", ["energy", "forces", "force_loss_gradient"])
def test_program_matches_reference(case, both, what):
    got, want = both
    n = case.real_n
    if what == "energy":
        np.testing.assert_allclose(got[0][:n], want[0], rtol=2e-5, atol=2e-6 * np.abs(want[0]).max())
    elif what == "forces":
        assert np.abs(want[1]).max() > 1e-3
        np.testing.assert_allclose(got[1][:n], want[1], rtol=1e-4, atol=2e-5 * np.abs(want[1]).max())
    else:
        flat = weights.flat_dict(got[2])
        assert set(flat) == set(want[2])
        for name, g in want[2].items():
            scale = max(np.abs(g).max(), 1e-12)
            assert np.abs(flat[name] - g).max() <= 2e-4 * scale + 1e-7, name
        live = [k for k, g in want[2].items() if np.abs(g).max() > 0]
        assert len(live) >= len(want[2]) - 1, sorted(set(want[2]) - set(live))  # all but the last readout


def _rotation(seed: int, improper: bool):
    q, _ = np.linalg.qr(np.random.default_rng(seed).normal(size=(3, 3)))
    q = q * np.sign(np.linalg.det(q))
    return jnp.asarray(-q if improper else q, jnp.float32)


@pytest.mark.parametrize("improper", [False, True])
def test_energy_invariant_forces_equivariant(case, both, improper):
    (e0, f0, _), _ = both
    r = _rotation(3, improper)
    moved = case.batch.replace(pos=case.batch.pos @ r.T, edge_shifts=case.batch.edge_shifts @ r.T)
    e1, f1, _ = jax.device_get(case.program_fn(case.params, moved))
    np.testing.assert_allclose(e1, e0, rtol=2e-4, atol=1e-5 * np.abs(e0).max())
    np.testing.assert_allclose(f1, f0 @ np.asarray(r).T, rtol=1e-3, atol=1e-4 * np.abs(f0).max())


@pytest.mark.parametrize("nu,L", [(1, 0), (1, 1), (2, 0), (2, 1), (3, 0), (3, 1)])
def test_eta_is_the_rank_of_the_symmetric_subspace(nu, L):
    assert symmetric_basis(3, nu, L).shape[0] == ref.symmetric_rank(3, nu, L) \
        == {(1, 0): 1, (1, 1): 1, (2, 0): 4, (2, 1): 3, (3, 0): 8, (3, 1): 12}[nu, L]
    # and the two constructions of the basis itself agree entry for entry
    np.testing.assert_allclose(symmetric_basis(3, nu, L), ref.symmetric_couplings(3, nu, L),
                               atol=1e-12)


@pytest.mark.parametrize("nu", [2, 3])
def test_contraction_is_the_symmetrised_product(nu):
    """``Contraction`` (monomials x U, component-major) against the dense
    symmetric tensors of the reference applied to every ordered index tuple."""
    rng = np.random.default_rng(nu)
    n, D = 5, 16
    A = rng.normal(size=(n, D, C)).astype(np.float32)
    z = jnp.asarray(rng.integers(1, 90, size=n), jnp.int32)
    module = mace_model.Contraction(max_ell=3, correlation=3, out_ell=1, channels=C)
    params = module.init(jax.random.PRNGKey(0), jnp.asarray(A.reshape(n, D * C)), z)
    n_w = mace_model.contraction_constants(3, 3, 1)[-1]
    w = np.zeros((mace_model.NUM_ELEMENTS, sum(n_w.values()), C), np.float32)
    want = np.zeros((4, n, C))
    at = 0
    for L in (0, 1):
        for order in (1, 2, 3):
            U = ref.symmetric_couplings(3, order, L)
            if order == nu:
                w[:, at:at + U.shape[0]] = rng.normal(size=(w.shape[0], U.shape[0], C))
                poly = np.einsum("eM" + "ijk"[:nu] + "," + ",".join(f"n{a}c" for a in "ijk"[:nu])
                                 + "->neMc", U, *([A.astype(np.float64)] * nu))
                want[L * L:(L + 1) ** 2] += np.einsum(
                    "nec,neMc->Mnc", w[np.asarray(z), at:at + U.shape[0]], poly)
            at += U.shape[0]
    got = module.apply({"params": {"weights": jnp.asarray(w)}}, jnp.asarray(A.reshape(n, D * C)), z)
    np.testing.assert_allclose(np.asarray(got).reshape(4, n, C), want, rtol=2e-4,
                               atol=2e-5 * np.abs(want).max())
    assert params["params"]["weights"].shape == w.shape


def test_species_matter_and_atom_order_does_not(case, both):
    (e0, _, _), _ = both
    n = case.real_n
    z = np.asarray(case.batch.z)
    i, j = 0, int(np.flatnonzero(z[:n] != z[0])[0])
    swapped = z.copy()
    swapped[[i, j]] = z[[j, i]]
    e_swapped = case.program_fn(case.params, case.batch.replace(z=jnp.asarray(swapped)))[0]
    assert np.abs(np.asarray(e_swapped)[:n] - e0[:n]).max() > 1e-3 * np.abs(e0[:n]).max()
    # relabel the atoms of the first graph: the same energies, relabelled
    n0 = int(case.batch.n_node[0])
    perm = np.arange(case.batch.num_nodes)
    perm[:n0] = np.random.default_rng(0).permutation(n0)
    inverse = np.argsort(perm)
    b = case.batch
    relabelled = b.replace(
        z=b.z[perm], x=b.x[perm], pos=b.pos[perm], forces_y=b.forces_y[perm],
        senders=jnp.asarray(inverse)[b.senders], receivers=jnp.asarray(inverse)[b.receivers])
    e_perm = np.asarray(case.program_fn(case.params, relabelled)[0])
    np.testing.assert_allclose(e_perm[:n], e0[perm][:n], rtol=1e-4, atol=1e-5 * np.abs(e0).max())


def test_padding_adds_exactly_zero(case, both):
    """Whatever the padded rows hold, the real atoms' energies and forces are
    bit-identical: a padded edge's radial weights are exactly zero."""
    (e0, f0, _), _ = both
    n, e = case.real_n, case.real_e
    b, rng = case.batch, np.random.default_rng(1)
    junk = b.replace(
        pos=b.pos.at[n:].set(jnp.asarray(rng.normal(size=(b.num_nodes - n, 3)), jnp.float32)),
        z=b.z.at[n:].set(7),
        edge_shifts=b.edge_shifts.at[e:].set(
            jnp.asarray(rng.normal(size=(b.num_edges - e, 3)), jnp.float32)))
    e1, f1, _ = jax.device_get(case.program_fn(case.params, junk))
    assert np.array_equal(e1[:n], e0[:n]) and np.array_equal(f1[:n], f0[:n])


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_no_rank3_gather_or_scatter_in_the_mlip_step(case):
    """PR 25's finding, held before the first chip run: every gather and
    scatter of the energy-and-force step moves rank-2 rows."""
    import optax

    from hydragnn_tpu.models.mlip import make_mlip_train_step
    from hydragnn_tpu.train.step import TrainState

    optimizer = optax.adamw(1e-4)
    state = TrainState(params=case.params, batch_stats={}, opt_state=optimizer.init(case.params),
                       step=jnp.zeros((), jnp.int32))
    step = make_mlip_train_step(case.model, optimizer)
    sized = {case.batch.num_nodes, case.batch.num_edges}
    seen = 0
    for eqn in _equations(jax.make_jaxpr(step)(state, case.batch).jaxpr):
        if eqn.primitive.name.startswith(("gather", "scatter")):
            seen += 1
            for var in list(eqn.invars) + list(eqn.outvars):
                shape = getattr(var.aval, "shape", ())
                assert not (len(shape) >= 3 and shape[0] in sized), (eqn.primitive.name, shape)
    assert seen > 20
