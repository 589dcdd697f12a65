"""Halo-exchange graph partitioning (parallel/halo): static plan invariants,
ppermute ring correctness, config/flag routing, and (slow) fp32 parity of the
node-resident partitioned steps vs single-device on the 8-device mesh."""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import hydragnn_tpu
from hydragnn_tpu.config import update_config
from hydragnn_tpu.graphs.batching import collate, compute_pad_spec
from hydragnn_tpu.graphs.graph import GraphSample
from hydragnn_tpu.graphs.radius import radius_graph
from hydragnn_tpu.models import create_model_config, init_model
from hydragnn_tpu.parallel import make_mesh, shard_state
from hydragnn_tpu.parallel.halo import (
    HaloBatch,
    HaloConfig,
    HaloPlan,
    _refresh_fn,
    gather_node_predictions,
    halo_boundary_bytes,
    halo_config,
    halo_enabled,
    make_halo_apply,
    make_halo_eval_step,
    make_halo_train_step,
    partition_graph_batch,
    put_halo_batch,
    replicated_allreduce_bytes,
    validate_halo_support,
)
from hydragnn_tpu.parallel.mesh import DATA_AXIS
from hydragnn_tpu.preprocess import apply_variables_of_interest
from hydragnn_tpu.train import (
    create_train_state,
    make_eval_step,
    make_train_step,
    select_optimizer,
)

from test_config import CI_CONFIG


def giant_sample(n=300, seed=7, box=11.0):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, box, size=(n, 3))
    s, r, sh = radius_graph(pos, radius=2.5, max_neighbours=10)
    x = np.concatenate(
        [rng.integers(0, 3, (n, 1)), rng.normal(size=(n, 3))], axis=1
    ).astype(np.float32)
    return GraphSample(
        x=x, pos=pos, senders=s, receivers=r, edge_shifts=sh,
        graph_y=rng.normal(size=(1,)), node_y=rng.normal(size=(n, 1)),
    )


def build(n=300, node_head=False, n_samples=1):
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Architecture"]["radius"] = 2.5
    if node_head:
        cfg["NeuralNetwork"]["Architecture"]["output_heads"] = {
            "node": {"num_headlayers": 2, "dim_headlayers": [8, 8], "type": "mlp"}
        }
        cfg["NeuralNetwork"]["Variables_of_interest"] = {
            "input_node_features": [0],
            "output_index": [0],
            "type": ["node"],
            "output_dim": [1],
            "denormalize_output": False,
        }
    samples = [giant_sample(n, seed=7 + i) for i in range(n_samples)]
    samples = apply_variables_of_interest(samples, cfg)
    cfg = update_config(cfg, samples)
    model = create_model_config(cfg)
    batch = collate(samples[:1], compute_pad_spec(samples, 1))
    return model, batch, cfg


# -- static plan / local views ------------------------------------------------

def test_partition_graph_batch_invariants():
    _, batch, _ = build()
    cfg = HaloConfig()
    hb = partition_graph_batch(batch, 8, cfg=cfg, cutoff=2.5)
    D = 8
    b = hb.batch
    n_real = int(np.round(np.asarray(batch.node_mask).sum()))
    e_real = int(np.round(np.asarray(batch.edge_mask).sum()))
    G = np.asarray(batch.graph_y).shape[0]
    n_owned = np.asarray(hb.n_owned)
    node_global = np.asarray(hb.node_global)

    assert b.x.shape[0] == D and b.x.shape[1] % cfg.node_multiple == 0
    assert b.senders.shape[1] % cfg.edge_multiple == 0
    # owned slots partition the real nodes exactly (disjoint union)
    owned_ids = np.concatenate(
        [node_global[d, : n_owned[d]] for d in range(D)]
    )
    assert n_owned.sum() == n_real
    np.testing.assert_array_equal(np.sort(owned_ids), np.arange(n_real))
    # owned edges partition the real edges by receiver owner
    assert int(np.round(np.asarray(b.edge_mask).sum())) == e_real
    for d in range(D):
        n_loc = b.x.shape[1]
        # node mask covers exactly the owned region; batch ids put halo +
        # pad rows in the dummy graph
        assert int(np.round(np.asarray(b.node_mask[d]).sum())) == n_owned[d]
        np.testing.assert_array_equal(
            np.asarray(b.batch[d, : n_owned[d]]), np.zeros(n_owned[d])
        )
        assert (np.asarray(b.batch[d, n_owned[d]:]) == G - 1).all()
        assert int(b.n_node[d, 0]) == n_owned[d]
        # receiver-owner invariant: every real edge's receiver is an OWNED
        # local row — local aggregation needs no cross-device reduction
        e_here = int(np.round(np.asarray(b.edge_mask[d]).sum()))
        rcv = np.asarray(b.receivers[d, :e_here])
        assert (rcv < n_owned[d]).all()
        # senders point at valid (owned or halo) rows carrying real ids
        snd = np.asarray(b.senders[d, :e_here])
        assert (node_global[d, snd] >= 0).all()
        # local node features equal the global rows they mirror
        k = int((node_global[d] >= 0).sum())
        np.testing.assert_array_equal(
            np.asarray(b.x[d, :k]), np.asarray(batch.x)[node_global[d, :k]]
        )

    # plan shape/width discipline: per-shift buckets, send rows owned,
    # recv slots in the halo region (or the trash slot)
    assert len(hb.plan.send_idx) == D - 1
    for snd, rcv in zip(hb.plan.send_idx, hb.plan.recv_slot):
        assert snd.shape == rcv.shape
        assert snd.shape[1] % cfg.slot_multiple == 0 or snd.shape[1] == 0
        for d in range(D):
            assert (np.asarray(snd[d]) < n_owned[d]).all()
        n_loc = b.x.shape[1]
        r = np.asarray(rcv)
        trash = r == n_loc - 1
        assert ((r >= np.asarray(n_owned)[:, None]) | trash).all()


def test_partition_graph_batch_deterministic():
    _, batch, _ = build()
    h1 = partition_graph_batch(batch, 4, cutoff=2.5)
    h2 = partition_graph_batch(batch, 4, cutoff=2.5)
    for a, b in zip(jax.tree.leaves(h1), jax.tree.leaves(h2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_partition_graph_batch_errors():
    _, batch, _ = build()
    with pytest.raises(ValueError, match=">= 2 partitions"):
        partition_graph_batch(batch, 1)
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Architecture"]["radius"] = 2.5
    samples = apply_variables_of_interest(
        [giant_sample(60, seed=1), giant_sample(60, seed=2)], cfg
    )
    multi = collate(samples, compute_pad_spec(samples, 2))
    with pytest.raises(ValueError, match="exactly 1 real graph"):
        partition_graph_batch(multi, 4)


def test_put_halo_batch_partition_count_pinned():
    _, batch, _ = build()
    mesh = make_mesh(n_data=8, n_branch=1)
    with pytest.raises(ValueError, match="halo.partitions"):
        put_halo_batch(batch, mesh, cfg=HaloConfig(partitions=4))


def test_halo_refresh_ring_two_devices():
    """The ppermute ring delivers every boundary row into the matching halo
    slot: overwrite halo rows with a sentinel, refresh, and every live halo
    slot again equals the owner's (global) feature row."""
    _, batch, _ = build(n=120)
    mesh = make_mesh(n_data=2, n_branch=1, devices=jax.devices()[:2])
    hb = put_halo_batch(batch, mesh, cutoff=2.5)
    n_halo = [
        int((np.asarray(hb.node_global)[d] >= 0).sum() - np.asarray(hb.n_owned)[d])
        for d in range(2)
    ]
    assert max(n_halo) > 0, "fixture has no boundary atoms — test is vacuous"

    def dev_fn(hb: HaloBatch):
        x = hb.batch.x[0]
        n_own = hb.n_owned[0]
        plan_local = [
            (s[0], r[0]) for s, r in zip(hb.plan.send_idx, hb.plan.recv_slot)
        ]
        row = jnp.arange(x.shape[0])
        stale = jnp.where((row >= n_own)[:, None], -7.0, x)
        refreshed, _ = _refresh_fn(plan_local, 2)(stale, None)
        return refreshed[None]

    out = jax.jit(
        shard_map(
            dev_fn, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS),
            check_vma=False,
        )
    )(hb)
    out = np.asarray(out)
    x_global = np.asarray(batch.x)
    node_global = np.asarray(hb.node_global)
    n_owned = np.asarray(hb.n_owned)
    n_loc = out.shape[1]
    for d in range(2):
        for slot in range(n_owned[d], n_loc - 1):  # trash slot excluded
            gid = node_global[d, slot]
            if gid >= 0:
                np.testing.assert_array_equal(out[d, slot], x_global[gid])


# -- config / flags / routing -------------------------------------------------

def test_halo_config_defaults_and_validate():
    cfg = halo_config(None)
    assert cfg == HaloConfig()
    assert not cfg.enabled and cfg.partitions == 0 and cfg.fallback == "error"
    with pytest.raises(ValueError, match="fallback"):
        HaloConfig(fallback="warn").validate()
    with pytest.raises(ValueError, match="partitions"):
        HaloConfig(partitions=-1).validate()
    with pytest.raises(ValueError, match="slot_multiple"):
        HaloConfig(slot_multiple=0).validate()


def test_config_block_unknown_key_rejected():
    from hydragnn_tpu.datasets import deterministic_graph_data

    samples = deterministic_graph_data(number_configurations=4, seed=3)
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Architecture"]["halo"] = {"enabled": True, "bogus": 1}
    with pytest.raises(ValueError, match="Unknown Architecture.halo"):
        update_config(cfg, samples)
    # valid keys pass and defaults are backfilled into the augmented dict
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Architecture"]["halo"] = {"enabled": True}
    aug = update_config(cfg, samples)
    halo = aug["NeuralNetwork"]["Architecture"]["halo"]
    assert halo["enabled"] is True
    assert halo["slot_multiple"] == HaloConfig().slot_multiple
    assert halo["fallback"] == "error"


def test_halo_flag_precedence(monkeypatch):
    monkeypatch.delenv("HYDRAGNN_HALO", raising=False)
    assert halo_enabled({}) is False
    assert halo_enabled({"halo": {"enabled": True}}) is True
    # env wins over config, both directions
    monkeypatch.setenv("HYDRAGNN_HALO", "1")
    assert halo_enabled({}) is True
    monkeypatch.setenv("HYDRAGNN_HALO", "0")
    assert halo_enabled({"halo": {"enabled": True}}) is False
    # empty-but-set counts as unset
    monkeypatch.setenv("HYDRAGNN_HALO", "")
    assert halo_enabled({"halo": {"enabled": True}}) is True


def test_plan_remesh_halo_restart_fallback():
    from hydragnn_tpu.resilience import ElasticController, Fault

    devs = jax.devices()
    ctl = ElasticController(devices=devs[:4])
    ctl.apply(Fault(kind="device_loss", device=3))
    mesh4 = make_mesh(devices=devs[:4])
    _, mode, reason = ctl.plan_remesh(
        mesh4, {"Architecture": {"halo": {"enabled": True}}}
    )
    assert mode == "restart_fallback" and "halo" in reason


def test_validate_halo_support_rejections():
    model, _, _ = build()
    spec = model.spec
    validate_halo_support(spec)  # baseline passes
    cases = [
        (dict(mpnn_type="DimeNet"), "mpnn_type"),
        (dict(equivariance=True), "equivariance"),
        (dict(global_attn_engine="GPS"), "global attention"),
        (dict(sync_batch_norm=True), "SyncBatchNorm"),
        (dict(enable_interatomic_potential=True), "interatomic"),
    ]
    for repl, needle in cases:
        with pytest.raises(ValueError, match=needle):
            validate_halo_support(dataclasses.replace(spec, **repl))
    node_model, _, _ = build(node_head=True)
    bad = dataclasses.replace(
        node_model.spec,
        node_heads=tuple(
            dataclasses.replace(h, node_type="conv")
            for h in node_model.spec.node_heads
        ),
    )
    with pytest.raises(ValueError, match="node heads"):
        validate_halo_support(bad)


def test_analytic_bytes_helpers():
    plan = HaloPlan(
        send_idx=(np.zeros((4, 8), np.int32), np.zeros((4, 0), np.int32)),
        recv_slot=(np.zeros((4, 8), np.int32), np.zeros((4, 0), np.int32)),
    )
    assert halo_boundary_bytes(plan, feat_dim=16) == 4 * 8 * 16 * 4
    assert replicated_allreduce_bytes(100, 16, 8) == 2 * 7 * 100 * 16 * 4
    # the whole point: thin boundaries beat whole-accumulator all-reduces
    assert halo_boundary_bytes(plan, 16) < replicated_allreduce_bytes(100, 16, 8)


def test_gather_node_predictions_roundtrip():
    node_global = np.array([[0, 2, 4, -1], [1, 3, 0, -1]], np.int32)
    n_owned = np.array([3, 2], np.int32)
    stacked = np.arange(2 * 4 * 1).reshape(2, 4, 1).astype(np.float32)
    hb = HaloBatch(
        batch=None, plan=None, node_global=node_global, n_owned=n_owned
    )
    out = gather_node_predictions(stacked, hb)
    # device 0 owns global 0, 2, 4; device 1 owns 1, 3 (its slot 2 is halo)
    np.testing.assert_array_equal(out[:, 0], [0.0, 4.0, 1.0, 5.0, 2.0])


# -- parity gates (slow: full 8-device jit compiles) --------------------------

@pytest.mark.slow
def test_halo_forward_matches_single_device():
    model, host_batch, _ = build(n=400)
    mesh = make_mesh(n_data=8, n_branch=1)
    dev_batch = jax.tree.map(jnp.asarray, host_batch)
    variables = init_model(model, dev_batch)
    single = model.apply(variables, dev_batch, train=False)
    hb = put_halo_batch(host_batch, mesh, cutoff=2.5)
    sharded = make_halo_apply(model, mesh)(variables, hb)
    for a, b in zip(jax.tree.leaves(single), jax.tree.leaves(sharded)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5
        )


@pytest.mark.slow
def test_halo_node_head_forward_matches_single_device():
    model, host_batch, _ = build(n=400, node_head=True)
    mesh = make_mesh(n_data=8, n_branch=1)
    dev_batch = jax.tree.map(jnp.asarray, host_batch)
    variables = init_model(model, dev_batch)
    single = model.apply(variables, dev_batch, train=False)
    hb = put_halo_batch(host_batch, mesh, cutoff=2.5)
    sharded = make_halo_apply(model, mesh)(variables, hb)
    n_real = int(np.round(np.asarray(host_batch.node_mask).sum()))
    got = gather_node_predictions(np.asarray(sharded[0]), hb)
    np.testing.assert_allclose(
        got, np.asarray(single[0])[:n_real], rtol=5e-4, atol=5e-5
    )


@pytest.mark.slow
def test_halo_train_step_matches_single_device():
    model, host_batch, _ = build(n=400)
    mesh = make_mesh(n_data=8, n_branch=1)
    # SGD: parameter deltas stay proportional to gradients, so cross-device
    # reduction-order noise can't flip near-zero Adam updates
    opt = select_optimizer({"type": "SGD", "learning_rate": 0.01})
    dev_batch = jax.tree.map(jnp.asarray, host_batch)

    s1, m1 = make_train_step(model, opt)(
        create_train_state(model, opt, dev_batch), dev_batch
    )
    state = shard_state(create_train_state(model, opt, dev_batch), mesh)
    hb = put_halo_batch(host_batch, mesh, cutoff=2.5)
    s2, m2 = make_halo_train_step(model, opt, mesh)(state, hb)

    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-4)
    assert int(m1["num_graphs"]) == int(m2["num_graphs"]) == 1
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5
        )


@pytest.mark.slow
def test_halo_eval_step_matches_single_device():
    model, host_batch, _ = build(n=400)
    mesh = make_mesh(n_data=8, n_branch=1)
    opt = select_optimizer({"type": "SGD", "learning_rate": 0.01})
    dev_batch = jax.tree.map(jnp.asarray, host_batch)
    state = create_train_state(model, opt, dev_batch)
    m1 = make_eval_step(model)(state, dev_batch)
    hb = put_halo_batch(host_batch, mesh, cutoff=2.5)
    m2 = make_halo_eval_step(model, mesh)(shard_state(state, mesh), hb)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-4)
    np.testing.assert_allclose(
        np.asarray(m1["head_sse"]), np.asarray(m2["head_sse"]), rtol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(m1["head_count"]), np.asarray(m2["head_count"]), rtol=1e-6
    )


@pytest.mark.slow
def test_halo_reachable_from_config(monkeypatch):
    """Architecture.halo.enabled routes run_training through the partitioned
    steps end-to-end on the 8-device mesh (batch_size=1 giant-graph regime)."""
    monkeypatch.setenv("HYDRAGNN_AUTO_PARALLEL", "1")
    monkeypatch.delenv("HYDRAGNN_HALO", raising=False)
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Architecture"]["radius"] = 2.5
    cfg["NeuralNetwork"]["Architecture"]["halo"] = {"enabled": True}
    cfg["NeuralNetwork"]["Training"]["batch_size"] = 1
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 2
    samples = [giant_sample(160, seed=31 + i) for i in range(6)]
    state, model, aug = hydragnn_tpu.run_training(cfg, samples=samples)
    assert int(np.asarray(state.step)) > 0
    for leaf in jax.tree.leaves(state.params):
        assert np.isfinite(np.asarray(leaf)).all()


@pytest.mark.slow
def test_halo_edge_sharding_mutually_exclusive():
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Architecture"]["halo"] = {"enabled": True}
    cfg["NeuralNetwork"]["Architecture"]["edge_sharding"] = True
    cfg["NeuralNetwork"]["Training"]["batch_size"] = 1
    samples = [giant_sample(120, seed=3) for _ in range(4)]
    with pytest.raises(ValueError, match="mutually exclusive"):
        hydragnn_tpu.run_training(cfg, samples=samples)
