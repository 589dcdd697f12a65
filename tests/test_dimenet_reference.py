"""DimeNet++ (hidden 16, 7 x 6 basis, 3 layers, 3 output layers) against the
benchmark's plain reference (``benchmark/reference/dimenet.py``, which
enumerates its triplets a second way, inside jit, evaluates the spherical
Bessel functions by closed forms and imports nothing of the program): energy,
forces and the parameter gradient of the force loss on a padded two-graph
periodic batch that holds a 2-atom cell whose neighbours are mostly its own
images; the host's triplet enumeration against a brute-force one and against
the reference's table; the periodic rule against the old one; padding;
symmetries; the radial part on edges against the triplet-level evaluation it
replaced; the seed-independent bucket table; the dense ``[E, K]`` block layout
of the triplet dimension (both row sides) against the flat list: the same
triplets, the same numbers, one program a bucket.
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
from hydragnn_tpu.graphs.batching import PadSpec, collate, flat_triplets
from hydragnn_tpu.graphs.graph import GraphSample
from hydragnn_tpu.graphs.triplets import block_rows, build_triplets, degree_cap
from hydragnn_tpu.models import create_model_config
from hydragnn_tpu.models.spherical import radial_on_edges
from hydragnn_tpu.preprocess.load_data import dataset_loading_and_splitting

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K = 8  # neighbours an atom


def _bench(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location("bench_" + "_".join(parts)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _bench("reference", "dimenet.py")
ref_mlip = _bench("reference", "mlip.py")
weights = _bench("lib", "weights.py")
crystals = _bench("generators", "crystals.py")
program = _bench("lib", "program.py")

# sizes [6, 2]: the second structure is a 2-atom cell 3 A wide under a 6 A cutoff
CRYSTALS = {"count": 2, "radius": 6.0, "max_neighbours": K, "volume_per_atom": 14.0,
            "n_species": 83, "sizes": {"seed": 0, "median": 2, "sigma": 0.01, "min": 2,
                                       "max": 6, "max_at": 0}}
SMALL = {"hidden_dim": 16, "out_emb_size": 16, "int_emb_size": 8, "max_neighbours": K,
         "output_heads": {"node": {"num_headlayers": 2, "dim_headlayers": [16, 16], "type": "mlp"}}}


def bench_config():
    with open(os.path.join(ROOT, "benchmark", "configs", "dimenetpp_mlip_oc20.json")) as f:
        cfg = json.load(f)
    cfg["NeuralNetwork"]["Architecture"].update(copy.deepcopy(SMALL))
    return cfg


def brute_force(senders, receivers, shifts, periodic_rule=True):
    """Every (kj, ji) with receiver(kj) = sender(ji), O(E^2); drops the exact
    reverse (``periodic_rule``) or every k = i (the rule this PR replaced)."""
    pairs = []
    for ji in range(len(senders)):
        for kj in range(len(senders)):
            if receivers[kj] != senders[ji]:
                continue
            back = senders[kj] == receivers[ji]
            if periodic_rule:
                back = back and np.abs(shifts[kj] + shifts[ji]).max() < 1e-3
            if not back:
                pairs.append((kj, ji))
    return sorted(pairs)


def molecule():
    pos = np.random.default_rng(4).normal(size=(7, 3)) * 1.5
    d = np.linalg.norm(pos[:, None] - pos[None], axis=-1)
    s, r = np.nonzero((d < 2.5) & (d > 0))
    return {"senders": s.astype(np.int32), "receivers": r.astype(np.int32),
            "shifts": np.zeros((len(s), 3), np.float32), "z": np.ones(7, np.int32)}


@pytest.fixture(scope="module")
def graphs():
    return crystals.generate(CRYSTALS, 2**31 + 7)


@pytest.mark.parametrize("which", ["crystal", "two_atom_cell", "molecule"])
def test_triplets_match_brute_force(graphs, which):
    g = {"crystal": graphs[0], "two_atom_cell": graphs[1], "molecule": molecule()}[which]
    kj, ji = build_triplets(g["senders"], g["receivers"], g["shifts"])
    assert sorted(zip(kj.tolist(), ji.tolist())) == brute_force(
        g["senders"], g["receivers"], g["shifts"])
    assert np.all(np.diff(ji) >= 0)  # sorted by ji: the sum onto ji reads consecutive rows
    if which == "molecule":  # no shifts: the rule reads k != i, as it always did
        assert sorted(zip(*map(np.ndarray.tolist, build_triplets(g["senders"], g["receivers"])))) \
            == brute_force(g["senders"], g["receivers"], g["shifts"], periodic_rule=False)


def test_old_rule_loses_image_triplets_of_the_two_atom_cell(graphs):
    g = graphs[1]
    assert len(g["z"]) == 2
    new = brute_force(g["senders"], g["receivers"], g["shifts"])
    old = brute_force(g["senders"], g["receivers"], g["shifts"], periodic_rule=False)
    assert set(old) < set(new) and len(new) - len(old) >= len(g["senders"])
    kj, _ = build_triplets(g["senders"], g["receivers"], g["shifts"])
    assert len(kj) == len(new) <= K * len(g["senders"])


@pytest.mark.parametrize("which", [0, 1])
def test_triplets_match_the_reference_table(graphs, which):
    g = graphs[which]
    n = len(g["z"])
    ji, real, over = jax.jit(ref.triplet_block, static_argnums=(3, 4))(
        jnp.asarray(g["senders"]), jnp.asarray(g["receivers"]), jnp.asarray(g["shifts"]), n, K)
    ji, real = np.asarray(ji), np.asarray(real)
    table = sorted((kj, int(ji[kj, slot])) for kj, slot in zip(*np.nonzero(real)))
    kj_host, ji_host = build_triplets(g["senders"], g["receivers"], g["shifts"])
    assert not bool(over)
    assert table == sorted(zip(kj_host.tolist(), ji_host.tolist()))


def test_reference_poisons_a_graph_over_the_cap(graphs):
    g = graphs[0]
    _, _, over = ref.triplet_block(jnp.asarray(g["senders"]), jnp.asarray(g["receivers"]),
                                   jnp.asarray(g["shifts"]), len(g["z"]), K - 1)
    assert bool(over)


def reversed_edges(g):
    """The same structure with every edge turned round: what an atom SENT it now
    RECEIVES, so a send-capped graph becomes a receive-capped one (a radius
    graph's cap) with the same triplets, roles swapped."""
    return dict(g, senders=g["receivers"], receivers=g["senders"], shifts=-g["shifts"])


def block_pairs(batch):
    """The real (kj, ji) pairs of a block batch, from its table and mask."""
    rows = batch.meta.triplet_rows
    table, atom = ((batch.idx_ji, batch.receivers) if rows == "kj"
                   else (batch.idx_kj, batch.senders))
    table, atom = np.asarray(table), np.asarray(atom)
    r, slot = np.nonzero(np.asarray(batch.triplet_mask).reshape(len(atom), -1))
    partner = table[atom[r], slot]
    return set(zip(r.tolist(), partner.tolist()) if rows == "kj"
               else zip(partner.tolist(), r.tolist()))


@pytest.mark.parametrize("which,rows", [
    ("crystal", "kj"), ("two_atom_cell", "kj"), ("molecule", "kj"), ("molecule", "ji"),
    ("crystal", "ji"), ("two_atom_cell", "ji")])
def test_block_layout_holds_the_same_triplets(graphs, which, rows):
    """Send-capped (rows kj: the crystals as generated), receive-capped (rows
    ji: the same with every edge turned round) and a molecule either way: the
    block's real pairs are ``build_triplets``' and ``real_triplets`` is equal."""
    g = {"crystal": graphs[0], "two_atom_cell": graphs[1], "molecule": molecule()}[which]
    if rows == "ji" and which != "molecule":
        g = reversed_edges(g)
    n = len(g["z"])
    sample = GraphSample(x=np.ones((n, 1), np.float32), senders=g["senders"],
                         receivers=g["receivers"], edge_shifts=g["shifts"])
    k = max(np.bincount(g["senders"]).max(), np.bincount(g["receivers"]).max()) \
        if which == "molecule" else K
    # kj wins where both sides fit (the molecule; the 2-atom cell, whose atoms
    # each send AND receive K); the 6-atom crystal is capped on one side only
    assert block_rows([sample], int(k)) == (rows if which == "crystal" else "kj")
    e = len(g["senders"]) + 3
    flat = collate([sample], PadSpec(n + 2, e, 2, n_triplet=int(k) * e))
    block = collate([sample], PadSpec(n + 2, e, 2, n_triplet=int(k) * e, triplet_rows=rows))
    kj, ji = build_triplets(g["senders"], g["receivers"], g["shifts"])
    assert block_pairs(block) == set(zip(kj.tolist(), ji.tolist())) and len(kj) > 0
    assert int(block.triplet_mask.sum()) == int(flat.triplet_mask.sum()) == len(kj)
    # no index of the triplet dimension's length is shipped
    row_field, table = ((block.idx_kj, block.idx_ji) if rows == "kj"
                        else (block.idx_ji, block.idx_kj))
    assert row_field.shape == (0,) and table.shape == (n + 2, int(k))
    assert block.triplet_mask.shape == flat.triplet_mask.shape == (int(k) * e,)
    assert flat.meta.triplet_rows is None and block.meta.triplet_rows == rows


def test_no_side_capped_for_the_whole_corpus_keeps_the_flat_list(graphs):
    """``degree_cap`` is borne out sample by sample, by either side; the block
    needs ONE side for every sample."""
    one = program.to_samples([graphs[0]], 1.0)
    other = program.to_samples([reversed_edges(graphs[0])], 1.0)
    assert degree_cap(one + other) == K
    assert (block_rows(one, K), block_rows(other, K), block_rows(one + other, K)) == (
        "kj", "ji", None)
    assert block_rows(one, 0) is None


class Case:
    """Model, seeded weights, a padded batch, and one jitted function each for
    program and reference: (node energies, forces, d force-loss / d params)."""

    def __init__(self, graphs):
        bench_cfg = bench_config()
        cfg = {k: copy.deepcopy(bench_cfg[k]) for k in program.PROGRAM_KEYS if k in bench_cfg}
        self.graphs = graphs
        self.samples = program.to_samples(graphs, bench_cfg["input_scale"])
        self.cfg = update_config(cfg, self.samples)
        self.model = create_model_config(self.cfg)
        self.real_n = sum(s.num_nodes for s in self.samples)
        self.real_e = sum(s.num_edges for s in self.samples)
        self.batch = self.collated(5, 9, 17)
        self.real_t = int(self.batch.triplet_mask.sum())
        shapes = jax.eval_shape(
            lambda: self.model.init(jax.random.PRNGKey(0), self.batch, train=False))
        self.params = weights.make_weights(shapes["params"], 11, bench_cfg["weights"])
        self.flat = weights.flat_dict(self.params)
        self.hp = ref.hyperparameters(bench_cfg)
        self.real = {k: jnp.asarray(v) for k, v in ref_mlip.concat(
            graphs, bench_cfg["input_scale"]).items()}
        self.program_fn = jax.jit(self._program)
        self.reference_fn = jax.jit(self._reference)

    def collated(self, more_nodes, more_edges, more_triplets, samples=None, rows=None):
        """``rows``: the block layout with that row side, K x n_edge slots
        (``more_triplets`` then has no meaning: the edges size the block)."""
        triplets = sum(len(build_triplets(s.senders, s.receivers, s.edge_shifts)[0])
                       for s in self.samples)
        n_edge = self.real_e + more_edges
        return jax.tree.map(jnp.asarray, collate(samples or self.samples, PadSpec(
            n_node=self.real_n + more_nodes, n_edge=n_edge, n_graph=3,
            n_triplet=K * n_edge if rows else triplets + more_triplets, triplet_rows=rows)))

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
def case(graphs):
    return Case(graphs)


@pytest.fixture(scope="module")
def both(case):
    return (jax.device_get(case.program_fn(case.params, case.batch)),
            jax.device_get(case.reference_fn(case.flat, case.real)))


@pytest.mark.parametrize("what", ["energy", "forces", "force_loss_gradient"])
def test_program_matches_reference(case, both, what):
    """fp32 tolerances: 2e-5 of the largest value for energies, 1e-4 for
    forces and for every leaf of the grad-of-grad (measured 1e-6..2e-5: the
    two sides sum 8 neighbours and ~60 triplets an edge in different orders
    and evaluate j_l by different formulas)."""
    got, want = both
    n = case.real_n
    if what == "energy":
        assert np.abs(want[0]).max() > 1e-3
        np.testing.assert_allclose(got[0][:n], want[0], rtol=2e-5, atol=2e-5 * np.abs(want[0]).max())
    elif what == "forces":
        assert np.abs(want[1]).max() > 1e-3
        np.testing.assert_allclose(got[1][:n], want[1], rtol=1e-4, atol=1e-4 * np.abs(want[1]).max())
    else:
        flat = weights.flat_dict(got[2])
        assert set(flat) == set(want[2])
        for name, g in want[2].items():
            # every weight reaches the forces but the last bias, an energy offset
            assert np.abs(g).max() > 0 or name == "head0_branch-0/dense_2/bias", name
            assert np.abs(flat[name] - g).max() <= 1e-4 * np.abs(g).max() + 1e-8, name


def test_angle_blind_reference_is_another_model(case, both):
    """The control of the benchmark's limits: P_l = 1 moves the energies by
    percents of the largest (3.4% here, the two sides agree to 2e-5), not by
    rounding."""
    _, (e, f, _) = both
    blind = dict(case.hp, angle_blind=1)
    b = case.real
    e_blind = ref.node_energy(case.flat, blind, b["x"], b["pos"], b["senders"], b["receivers"],
                              b["shifts"])
    assert np.abs(np.asarray(e_blind) - e).max() > 0.02 * np.abs(e).max()


@pytest.mark.parametrize("layout", ["flat", "block"])
@pytest.mark.parametrize("what", ["nodes", "edges", "triplets"])
def test_padding_adds_nothing(case, both, what, layout):
    """``block``: the same paddings in the dense layout (rows kj; more edges
    are more blocks of K slots), held against the FLAT batch's numbers."""
    (e0, f0, g0), _ = both
    more = {"nodes": (40, 9, 17), "edges": (5, 300, 17), "triplets": (5, 9, 2000)}[what]
    e1, f1, g1 = jax.device_get(case.program_fn(
        case.params, case.collated(*more, rows="kj" if layout == "block" else None)))
    n = case.real_n
    scale = np.abs(f0).max()
    np.testing.assert_allclose(e1[:n], e0[:n], rtol=1e-5, atol=1e-6 * np.abs(e0).max())
    np.testing.assert_allclose(f1[:n], f0[:n], rtol=1e-5, atol=2e-6 * scale)
    assert np.all(f1[n:] == 0.0)  # a padded atom feels nothing, exactly
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5 * max(np.abs(b).max(), 1e-12))


@pytest.mark.parametrize("what", ["energy", "forces", "force_loss_gradient"])
@pytest.mark.parametrize("rows", ["kj", "ji"])
def test_block_layout_gives_the_flat_lists_numbers(case, both, rows, what):
    """Flat and block batches of the same samples, both row sides (rows ji on
    the structures with every edge turned round, against their own flat batch):
    1e-5 of the largest value, on the CPU. Only the order of a sum differs."""
    if rows == "kj":
        flat = both[0]
        block = case.program_fn(case.params, case.collated(5, 9, 17, rows="kj"))
    else:
        samples = program.to_samples([reversed_edges(g) for g in case.graphs],
                                     bench_config()["input_scale"])
        flat = case.program_fn(case.params, case.collated(5, 9, 17, samples))
        block = case.program_fn(case.params, case.collated(5, 9, 17, samples, rows="ji"))
    i = ["energy", "forces", "force_loss_gradient"].index(what)
    for got, want in zip(jax.tree.leaves(jax.device_get(block[i])),
                         jax.tree.leaves(jax.device_get(flat[i]))):
        assert np.abs(want).max() > 0 or what == "force_loss_gradient"
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * max(np.abs(want).max(), 1e-12))


def _host_block(case, rows):
    """The case's structures as one host block batch with that row side."""
    samples = case.samples if rows == "kj" else program.to_samples(
        [reversed_edges(g) for g in case.graphs], bench_config()["input_scale"])
    n_edge = case.real_e + 9
    return samples, collate(samples, PadSpec(
        n_node=case.real_n + 5, n_edge=n_edge, n_graph=3, n_triplet=K * n_edge,
        triplet_rows=rows))


@pytest.mark.parametrize("rows", ["kj", "ji"])
def test_flat_triplets_reads_a_block_as_the_list_of_its_slots(case, rows):
    """What a placement that drops the meta hands on: T-length lists over the
    block's own slots, the same real pairs, the layout gone from the meta; a
    flat batch comes back untouched."""
    samples, block = _host_block(case, rows)
    flat = flat_triplets(block)
    assert flat.meta.triplet_rows is None and flat.meta == block.meta._replace(triplet_rows=None)
    assert flat.idx_kj.shape == flat.idx_ji.shape == flat.triplet_mask.shape == (
        K * block.senders.shape[0],)
    assert flat.idx_kj.dtype == flat.idx_ji.dtype == np.int32
    real = np.asarray(flat.triplet_mask) > 0
    assert set(zip(flat.idx_kj[real].tolist(), flat.idx_ji[real].tolist())) == block_pairs(block)
    assert int(real.sum()) == len(block_pairs(block)) > 0
    assert flat_triplets(flat) is flat
    # the two layouts of one bucket's sizes are not one bucket
    sizes = (case.real_n + 5, case.real_e + 9, 3, K * (case.real_e + 9))
    assert PadSpec(*sizes, triplet_rows=rows) != PadSpec(*sizes)
    assert len({PadSpec(*sizes, triplet_rows=rows), PadSpec(*sizes), PadSpec(*sizes)}) == 2


@pytest.mark.parametrize("rows", ["kj", "ji"])
def test_edge_sharded_placement_gives_the_block_batchs_energies(case, rows):
    """``put_large_batch`` drops the meta the block is read through, so it
    flattens first: the edge-sharded forward over 8 devices equals the block
    forward on one."""
    from hydragnn_tpu.parallel import make_mesh
    from hydragnn_tpu.parallel.large_graph import make_edge_sharded_apply, put_large_batch

    _, block = _host_block(case, rows)
    mesh = make_mesh(n_data=8, n_branch=1)
    placed = put_large_batch(block, mesh)
    assert placed.meta is None and placed.idx_kj.shape == placed.triplet_mask.shape
    variables = {"params": case.params}
    want = case.model.apply(variables, jax.tree.map(jnp.asarray, block), train=False)
    got = make_edge_sharded_apply(case.model, mesh)(variables, placed)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(a).max() > 0
        np.testing.assert_allclose(b, a, rtol=0, atol=1e-5 * np.abs(a).max())


def test_a_two_bucket_loader_lowers_one_program_a_bucket_on_the_block_layout():
    """The row side rides every bucket's ``PadSpec`` into the batches' static
    meta and is the loader's, so the step program of a two-bucket loader is
    traced and lowered once a bucket over a whole epoch."""
    from hydragnn_tpu.analysis import sentinel
    from hydragnn_tpu.models.mlip import make_mlip_train_step
    from hydragnn_tpu.train import create_train_state, select_optimizer

    bench_cfg = bench_config()
    # sizes whose train batches reach both buckets: 128 edge slots hold every
    # batch without the 24-atom structure, and the table says so
    params = dict(CRYSTALS, count=16, sizes={"seed": 0, "median": 6, "sigma": 0.6, "min": 2,
                                             "max": 24, "max_at": 3})
    cfg = {k: copy.deepcopy(bench_cfg[k]) for k in program.PROGRAM_KEYS if k in bench_cfg}
    cfg["NeuralNetwork"]["Training"].update(batch_size=2, perc_train=0.8, pad_buckets=2)
    samples = program.to_samples(crystals.generate(params, 3), bench_cfg["input_scale"])
    train, _, _ = dataset_loading_and_splitting(cfg, samples=samples)
    assert len(train.buckets) == 2 and {b.triplet_rows for b in train.buckets} == {"kj"}
    assert all(b.triplet_block == K and b.n_triplet == K * b.n_edge for b in train.buckets)
    model = create_model_config(update_config(cfg, samples))
    opt = select_optimizer({"type": "AdamW", "learning_rate": 1e-4})
    batches = [jax.tree.map(jnp.asarray, b) for b in train]
    shapes = {b.senders.shape for b in batches}
    assert len(shapes) == 2 and all(b.meta.triplet_rows == "kj" for b in batches)
    assert all(b.idx_kj.shape == (0,) and b.idx_ji.shape == (b.num_nodes, K) for b in batches)
    state = create_train_state(model, opt, batches[0])
    step = make_mlip_train_step(model, opt)
    before = sentinel.compile_counts()
    for b in batches:
        state, out = step(state, b)
        assert all(np.all(np.isfinite(v)) for v in jax.tree.leaves(jax.device_get(out)))
    after = sentinel.compile_counts()
    assert after["lowerings"] - before["lowerings"] == 2


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
    np.testing.assert_allclose(e1, e0, rtol=1e-4, atol=1e-5 * np.abs(e0).max())
    np.testing.assert_allclose(f1, f0 @ np.asarray(r).T, rtol=1e-3, atol=1e-4 * np.abs(f0).max())


def test_atom_permutation(case, both):
    """Renumber the atoms of the first structure: energies and forces move
    with them (the triplets are enumerated anew from the renumbered edges)."""
    (e0, f0, _), _ = both
    g = case.graphs[0]
    n = len(g["z"])
    perm = np.random.default_rng(5).permutation(n)  # new index of old atom a is perm[a]
    inv = np.argsort(perm)
    moved = dict(g, z=g["z"][inv], pos=g["pos"][inv], forces=g["forces"][inv],
                 senders=perm[g["senders"]].astype(np.int32),
                 receivers=perm[g["receivers"]].astype(np.int32))
    samples = program.to_samples([moved, case.graphs[1]], bench_config()["input_scale"])
    e1, f1, _ = jax.device_get(case.program_fn(case.params, case.collated(5, 9, 17, samples)))
    np.testing.assert_allclose(e1[:n][perm], e0[:n], rtol=1e-4, atol=1e-5 * np.abs(e0).max())
    np.testing.assert_allclose(f1[:n][perm], f0[:n], rtol=1e-3, atol=1e-4 * np.abs(f0).max())


def test_radial_part_on_edges_equals_the_triplet_level_evaluation(case):
    """What (3) of the issue replaced: sbf's radial factor evaluated on the
    gathered ``[T]`` distances. Same function of the same numbers, so the
    gathered ``[E, 42]`` rows equal it to rounding."""
    b = case.batch
    vec = b.pos[b.receivers] - b.pos[b.senders] + b.edge_shifts
    x = jnp.where(b.edge_mask > 0, jnp.linalg.norm(vec, axis=-1) / 6.0, 1.0)
    on_edges = radial_on_edges(x, 7, 6, 5)
    on_triplets = radial_on_edges(x[b.idx_kj], 7, 6, 5)
    assert on_edges.shape == (b.num_edges, 42) and on_triplets.shape[0] == b.idx_kj.shape[0]
    scale = float(jnp.abs(on_edges).max())
    np.testing.assert_allclose(on_edges[b.idx_kj], on_triplets, rtol=1e-6, atol=1e-6 * scale)
    assert np.all(np.asarray(on_edges)[np.asarray(b.edge_mask) == 0] == 0.0)


def test_bucket_table_is_the_same_at_every_seed():
    """One traffic file, three seeds: the sizes are the file's, the cap is the
    configuration's, so the (nodes, edges, graphs, triplets) table is one."""
    bench_cfg = bench_config()
    params = dict(CRYSTALS, count=24, sizes={"seed": 0, "median": 6, "sigma": 0.5, "min": 2,
                                             "max": 20, "max_at": 3})
    tables = []
    for seed in (1, 2, 2**31 + 3):
        cfg = {k: copy.deepcopy(bench_cfg[k]) for k in program.PROGRAM_KEYS if k in bench_cfg}
        cfg["NeuralNetwork"]["Training"].update(batch_size=4, perc_train=0.8, pad_buckets=3)
        samples = program.to_samples(crystals.generate(params, seed), bench_cfg["input_scale"])
        assert degree_cap(samples) == K
        train, _, _ = dataset_loading_and_splitting(cfg, samples=samples)
        assert all("idx_kj" not in s.extras for s in train.samples)  # collate enumerates
        tables.append([b.as_tuple() for b in train.buckets])
        batch = next(iter(train))
        assert 0 < batch.triplet_mask.sum() <= K * batch.edge_mask.sum()
    assert tables[0] == tables[1] == tables[2] and len(tables[0]) == 3
    assert all(t == K * e for _, e, _, t in tables[0])


def test_rbf_frequencies_are_one_trainable_leaf(case):
    """PyG's and upstream's one shared ``BesselBasisLayer``: the layer that
    makes the bases holds the frequencies, the layers after it none; the
    program's own init starts them at n pi."""
    assert [k for k in case.flat if "freq" in k] == ["graph_convs_0/rbf/freq"]
    init = case.model.init(jax.random.PRNGKey(0), case.batch, train=False)
    np.testing.assert_allclose(init["params"]["graph_convs_0"]["rbf"]["freq"],
                               np.arange(1, 7) * np.pi, rtol=1e-6)


@pytest.mark.parametrize("order", ["energy", "forces"])
def test_basis_parts_stand_behind_barriers(case, order):
    """The two parts of sbf are programs of their own in every pass (what
    ended the NaN forces on the chip, PERF.md section 6): four barriers in
    the forward pass, and their cotangents' in the force pass."""
    def energy(pos):
        return case.model.apply({"params": case.params}, case.batch.replace(pos=pos),
                                train=False)[0].sum()

    fn = energy if order == "energy" else jax.grad(energy)
    text = str(jax.make_jaxpr(fn)(case.batch.pos))
    assert text.count("optimization_barrier") >= (4 if order == "energy" else 8)
