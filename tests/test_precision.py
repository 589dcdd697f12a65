"""Precision policy plumbing (reference ``tests/test_precision_control.py`` +
``train_validate_test.py:43-71`` PRECISION_MAP): fp32 master params with
cast-to-compute, every alias resolving, fp64 opt-in.

PR 12 (ISSUE 12) widened this into the bf16 fast-path gate: schema-validated
precision values, ``HYDRAGNN_PRECISION`` env precedence (including the
non-finite guard's auto-arming off the RESOLVED dtype), fp16 + static loss
scaling, and the fp32-master-weight invariant proven through population
vmap and checkpoint/resume (master weights fp32 ON DISK, resume bit-exact).
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.train.step import (
    KNOWN_PRECISIONS,
    PRECISION_MAP,
    _cast_floats,
    create_train_state,
    make_train_step,
    resolve_precision,
    resolve_training_precision,
)


def test_precision_aliases_resolve():
    # reference PRECISION_MAP aliases (train_validate_test.py:43-58)
    for name in ("fp32", "float32", "fp64", "float64", "bf16", "bfloat16"):
        assert resolve_precision(name) is not None
    assert resolve_precision("bf16") == resolve_precision("bfloat16")
    assert resolve_precision("fp32") == resolve_precision("float32")


def test_unknown_precision_raises():
    with pytest.raises(ValueError, match="fp32"):
        resolve_precision("fp16_but_wrong")


def test_cast_floats_only_touches_floats():
    tree = {
        "w": jnp.ones((2, 2), jnp.float32),
        "ids": jnp.arange(3, dtype=jnp.int32),
        "flag": np.bool_(True),
    }
    out = _cast_floats(tree, jnp.bfloat16)
    assert out["w"].dtype == jnp.bfloat16
    assert out["ids"].dtype == jnp.int32


import functools


@functools.lru_cache(maxsize=None)
def _tiny_setup():
    """Built once per process (read-only for tests): model/optimizer/batch.
    States are created per test."""
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.datasets import deterministic_graph_data
    from hydragnn_tpu.graphs.batching import GraphLoader
    from hydragnn_tpu.models import create_model_config
    from hydragnn_tpu.train import select_optimizer
    from test_config import CI_CONFIG

    cfg = copy.deepcopy(CI_CONFIG)
    samples = deterministic_graph_data(number_configurations=16, seed=0)
    cfg = update_config(cfg, samples)
    model = create_model_config(cfg)
    opt = select_optimizer(cfg["NeuralNetwork"]["Training"]["Optimizer"])
    batch = next(iter(GraphLoader(samples, 8)))
    batch = jax.tree.map(jnp.asarray, batch)
    return model, opt, batch


@functools.lru_cache(maxsize=None)
def _shared_step(dtype_name):
    """ONE jitted step per compute dtype, shared across tests so its
    compiled program is paid for once (CPU never donates; sharing is safe)."""
    model, opt, _ = _tiny_setup()
    return make_train_step(model, opt, compute_dtype=PRECISION_MAP[dtype_name])


def test_bf16_compute_keeps_fp32_master_params():
    model, opt, batch = _tiny_setup()
    state = create_train_state(model, opt, batch)
    step = _shared_step("bf16")
    state2, metrics = step(state, batch)
    # master params and gradients-applied params stay fp32
    for leaf in jax.tree.leaves(state2.params):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.float32
    # loss is finite and fp32
    assert metrics["loss"].dtype == jnp.float32
    assert np.isfinite(float(metrics["loss"]))


def test_bf16_and_fp32_losses_agree_roughly():
    model, opt, batch = _tiny_setup()
    state = create_train_state(model, opt, batch)
    l32 = float(_shared_step("fp32")(state, batch)[1]["loss"])
    l16 = float(_shared_step("bf16")(state, batch)[1]["loss"])
    assert l16 == pytest.approx(l32, rel=0.05)


# ---------------------------------------------------------------------------
# PR 12: schema validation, env precedence, loss scaling, e2e invariants
# ---------------------------------------------------------------------------


def test_schema_rejects_unknown_precision():
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.datasets import deterministic_graph_data
    from test_config import CI_CONFIG

    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"].setdefault("Training", {})["precision"] = "bf17"
    samples = deterministic_graph_data(number_configurations=4, seed=0)
    with pytest.raises(ValueError, match="Training.precision"):
        update_config(cfg, samples)
    # every documented value (incl. the backend-resolved fast path) passes
    for name in sorted(KNOWN_PRECISIONS):
        ok = copy.deepcopy(CI_CONFIG)
        ok["NeuralNetwork"].setdefault("Training", {})["precision"] = name
        update_config(ok, samples)


def test_schema_validates_loss_scale():
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.datasets import deterministic_graph_data
    from test_config import CI_CONFIG

    samples = deterministic_graph_data(number_configurations=4, seed=0)
    bad = copy.deepcopy(CI_CONFIG)
    bad["NeuralNetwork"].setdefault("Training", {})["loss_scale"] = -2
    with pytest.raises(ValueError, match="loss_scale"):
        update_config(bad, samples)
    bad["NeuralNetwork"]["Training"]["loss_scale"] = "big"
    with pytest.raises(ValueError, match="loss_scale"):
        update_config(bad, samples)
    # json.loads admits NaN/Infinity literals — they must fail at load,
    # not NaN every gradient at step time
    for nonfinite in (float("nan"), float("inf")):
        bad["NeuralNetwork"]["Training"]["loss_scale"] = nonfinite
        with pytest.raises(ValueError, match="loss_scale"):
            update_config(bad, samples)
    ok = copy.deepcopy(CI_CONFIG)
    ok["NeuralNetwork"].setdefault("Training", {})["loss_scale"] = 1024
    aug = update_config(ok, samples)
    assert aug["NeuralNetwork"]["Training"]["loss_scale"] == 1024


def test_precision_env_flag_overrides_config(monkeypatch):
    monkeypatch.delenv("HYDRAGNN_PRECISION", raising=False)
    assert resolve_training_precision({"precision": "fp32"}) == jnp.float32
    monkeypatch.setenv("HYDRAGNN_PRECISION", "bf16")
    assert resolve_training_precision({"precision": "fp32"}) == jnp.bfloat16
    # empty-but-set counts as unset (the registry convention)
    monkeypatch.setenv("HYDRAGNN_PRECISION", "")
    assert resolve_training_precision({"precision": "fp16"}) == jnp.float16
    # "auto" resolves per backend: fp32 on this CPU host
    monkeypatch.setenv("HYDRAGNN_PRECISION", "auto")
    assert resolve_training_precision({"precision": "fp32"}) == (
        jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    )


def test_env_precision_arms_nonfinite_guard(monkeypatch):
    """The guard's 'auto' policy keys off the RESOLVED dtype: forcing bf16
    via the env must arm it exactly as the config edit would — otherwise
    the flag would silently drop the divergence protection the bf16 path
    documents."""
    from hydragnn_tpu.resilience import Resilience

    monkeypatch.delenv("HYDRAGNN_PRECISION", raising=False)
    monkeypatch.delenv("HYDRAGNN_NONFINITE_GUARD", raising=False)
    assert Resilience.from_config({"precision": "fp32"}).guard_enabled is False
    assert Resilience.from_config({"precision": "bf16"}).guard_enabled is True
    monkeypatch.setenv("HYDRAGNN_PRECISION", "bf16")
    assert Resilience.from_config({"precision": "fp32"}).guard_enabled is True
    monkeypatch.setenv("HYDRAGNN_PRECISION", "fp16")
    assert Resilience.from_config({"precision": "fp32"}).guard_enabled is True
    # an explicit guard switch still wins over the auto policy
    monkeypatch.setenv("HYDRAGNN_NONFINITE_GUARD", "0")
    assert Resilience.from_config({"precision": "fp32"}).guard_enabled is False


def test_loss_scale_matches_unscaled_exactly():
    """Static loss scaling is numerically transparent in fp32 for 2^k
    scales: grad(S·f)/S == grad(f) exactly (multiply/divide by a power of
    two is exact on normal floats), and the reported loss is the UNSCALED
    one carried through aux."""
    model, opt, batch = _tiny_setup()
    state = create_train_state(model, opt, batch)
    plain = _shared_step("fp32")
    scaled = make_train_step(model, opt, jnp.float32, loss_scale=1024.0)
    s_plain, m_plain = plain(state, batch)
    s_scaled, m_scaled = scaled(state, batch)
    assert float(m_plain["loss"]) == float(m_scaled["loss"])
    for a, b in zip(jax.tree.leaves(s_plain.params),
                    jax.tree.leaves(s_scaled.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # loss_scale=1 short-circuits to the historical program
    one = make_train_step(model, opt, jnp.float32, loss_scale=1.0)
    s_one, _ = one(state, batch)
    for a, b in zip(jax.tree.leaves(s_plain.params), jax.tree.leaves(s_one.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fp16_with_loss_scale_trains_finite():
    model, opt, batch = _tiny_setup()
    state = create_train_state(model, opt, batch)
    step = make_train_step(model, opt, jnp.float16, loss_scale=256.0)
    state2, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    for leaf in jax.tree.leaves(state2.params):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.float32  # master weights stay fp32


def _master_fp32(tree):
    return all(
        np.asarray(x).dtype == np.float32
        for x in jax.tree.leaves(tree)
        if np.issubdtype(np.asarray(x).dtype, np.floating)
    )


@pytest.mark.slow
def test_bf16_population_parity_and_master_weights():
    """ISSUE 12 gate: a vmapped bf16 population reproduces sequential bf16
    members (allclose — vmap batching may reassociate reductions) and every
    float leaf of the stacked params AND optimizer state stays fp32.
    Slow-marked up front (~6 s: the vmapped program's compile) per the
    tier-1 budget rule; the fp32-master invariant also has non-slow
    coverage via the single-state and checkpoint gates."""
    from hydragnn_tpu.train import (
        create_population_state,
        make_population_step,
        member_state,
    )

    model, opt, batch = _tiny_setup()
    step = _shared_step("bf16")
    pop_step = make_population_step(step)
    n = 2
    pstate = create_population_state(model, opt, batch, n, seeds=[0, 1])
    assert _master_fp32(pstate.state.params)
    assert _master_fp32(pstate.state.opt_state)
    # sequential refs from the SAME per-member initial states
    refs = []
    for i in range(n):
        s = member_state(pstate, i)
        for _ in range(2):
            s, _ = step(s, batch)
        refs.append(s)
    p = pstate
    for _ in range(2):
        p, _ = pop_step(p, batch)
    assert _master_fp32(p.state.params)
    assert _master_fp32(p.state.opt_state)
    for i, ref in enumerate(refs):
        got = member_state(p, i)
        for a, b in zip(jax.tree.leaves(ref.params), jax.tree.leaves(got.params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-2, atol=2e-2)


def test_bf16_checkpoint_fp32_on_disk_and_bitexact_resume(tmp_path):
    """ISSUE 12 gate: after bf16 training steps the checkpoint payload is
    the fp32 master state — fp32 dtypes on disk — and a restore + continue
    bit-matches the uninterrupted run (the resume contract reduced
    precision must not weaken: the per-step cast is derived state, nothing
    lossy is persisted)."""
    from hydragnn_tpu.train.checkpoint import load_checkpoint, save_checkpoint

    model, opt, batch = _tiny_setup()
    step = _shared_step("bf16")
    state = create_train_state(model, opt, batch)
    for _ in range(2):
        state, _ = step(state, batch)
    save_checkpoint(state, "bf16_ckpt", epoch=0, path=str(tmp_path))

    template = create_train_state(model, opt, batch)
    restored, meta = load_checkpoint(template, "bf16_ckpt", path=str(tmp_path))
    assert _master_fp32(restored.params)
    assert _master_fp32(restored.opt_state)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # continue one step from the restore vs the uninterrupted state:
    # bit-identical params and metrics
    cont, m_cont = step(restored, batch)
    base, m_base = step(state, batch)
    assert float(m_cont["loss"]) == float(m_base["loss"])
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(cont)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- every placement ends in the ONE update tail (train/step.py) ----------------


def _lj_mlip(n_samples, per_batch):
    """Smallest program with the scaled grad-of-grad path: one conv layer,
    narrow widths, ``per_batch``-graph batches (tier-1 time budget)."""
    from test_forces import MLIP_CONFIG

    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.datasets.lennard_jones import lennard_jones_data
    from hydragnn_tpu.graphs.batching import collate, compute_pad_spec
    from hydragnn_tpu.models import create_model_config
    from hydragnn_tpu.preprocess import apply_variables_of_interest
    from hydragnn_tpu.train import select_optimizer

    cfg = copy.deepcopy(MLIP_CONFIG)
    arch = cfg["NeuralNetwork"]["Architecture"]
    arch["num_conv_layers"] = 1
    arch["hidden_dim"] = 8
    arch["output_heads"]["node"]["dim_headlayers"] = [8, 8]
    samples = lennard_jones_data(
        number_configurations=n_samples, cells_per_dim=2, seed=3
    )
    samples = apply_variables_of_interest(samples, cfg)
    cfg = update_config(cfg, samples)
    model = create_model_config(cfg)
    pad = compute_pad_spec(samples, per_batch)
    batches = [
        collate(samples[i : i + per_batch], pad)
        for i in range(0, n_samples, per_batch)
    ]
    opt = select_optimizer(cfg["NeuralNetwork"]["Training"]["Optimizer"])
    return model, opt, batches


def _placement(name):
    """``(make(loss_scale) -> step, state factory, placed batch)`` of one
    placement, each at the smallest size its own test file builds."""
    import optax

    from hydragnn_tpu.parallel import (
        make_mesh,
        make_parallel_train_step,
        put_batch,
        shard_state,
        stack_device_batches,
    )

    if name == "single":
        model, opt, batch = _tiny_setup()
        return (lambda s: make_train_step(model, opt, loss_scale=s),
                lambda: create_train_state(model, opt, batch), batch)
    if name == "mlip":
        from hydragnn_tpu.models.mlip import make_mlip_train_step

        model, opt, batches = _lj_mlip(4, 2)
        batch = jax.tree.map(jnp.asarray, batches[0])
        return (lambda s: make_mlip_train_step(model, opt, loss_scale=s),
                lambda: create_train_state(model, opt, batch), batch)
    if name in ("mesh", "mesh_mlip"):
        if name == "mesh":
            from test_parallel import setup_model

            model, opt, batches = setup_model()
        else:
            model, opt, batches = _lj_mlip(16, 2)
        mesh = make_mesh()
        return (lambda s: make_parallel_train_step(model, opt, mesh, loss_scale=s),
                lambda: shard_state(create_train_state(model, opt, batches[0]), mesh),
                put_batch(stack_device_batches(batches[:8]), mesh))
    if name == "pipeline":
        from test_pipeline import setup as pipeline_setup

        from hydragnn_tpu.parallel.pipeline import (
            make_pipeline_mesh,
            make_pipelined_train_step,
            put_microbatches,
        )

        model, batches = pipeline_setup(num_conv_layers=5, n_micro=4)
        mesh = make_pipeline_mesh(4)
        opt = optax.adamw(5e-3)
        return (lambda s: make_pipelined_train_step(
                    model, opt, mesh, n_micro=4, loss_scale=s),
                lambda: create_train_state(model, opt, batches[0]),
                put_microbatches(stack_device_batches(batches), mesh))
    mesh = make_mesh(n_data=8, n_branch=1)
    if name == "edge":
        from test_large_graph import build

        from hydragnn_tpu.parallel.large_graph import (
            make_edge_sharded_train_step,
            put_large_batch,
        )

        model, host_batch, cfg = build("GIN", giant=True)
        make = make_edge_sharded_train_step
        placed = put_large_batch(host_batch, mesh)
    else:
        from test_halo import build

        from hydragnn_tpu.parallel.halo import make_halo_train_step, put_halo_batch

        model, host_batch, cfg = build(n=300)
        make = make_halo_train_step
        placed = put_halo_batch(host_batch, mesh, cutoff=2.5)
    from hydragnn_tpu.train import select_optimizer

    opt = select_optimizer(cfg["NeuralNetwork"]["Training"]["Optimizer"])
    dev_batch = jax.tree.map(jnp.asarray, host_batch)
    return (lambda s: make(model, opt, mesh, loss_scale=s),
            lambda: shard_state(create_train_state(model, opt, dev_batch), mesh),
            placed)


@pytest.mark.parametrize(
    "placement", ["single", "mlip", "mesh", "mesh_mlip", "edge", "pipeline", "halo"])
def test_every_placement_ends_in_the_one_update_tail(placement):
    """Each placement's step goes through ``train.step.apply_gradients`` and
    ``scaled_value_and_grad``: (a) ``loss_scale = 2^k`` is numerically
    transparent in fp32 — grad(S f) / S == grad(f) exactly, so parameters
    and the reported (unscaled, aux-carried) loss are bit-identical to the
    unscaled step's; for the MLIP objective only the OUTER gradient is
    scaled, the forces stay in physical units. The edge-sharded and halo
    steps took no ``loss_scale`` before they shared the tail. (b) The tail's
    ``optimizer`` scope names its operations in the lowered program, which
    is how a profile tells the update from the model."""
    make, fresh_state, batch = _placement(placement)
    plain, scaled = make(None), make(1024.0)
    s_p, m_p = plain(fresh_state(), batch)
    s_s, m_s = scaled(fresh_state(), batch)
    assert np.isfinite(float(m_p["loss"]))
    assert float(m_p["loss"]) == float(m_s["loss"])
    np.testing.assert_array_equal(
        np.asarray(m_p["tasks_loss"]), np.asarray(m_s["tasks_loss"]))
    moved = False
    for a, b, a0 in zip(jax.tree.leaves(s_p.params), jax.tree.leaves(s_s.params),
                        jax.tree.leaves(fresh_state().params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        moved = moved or not np.array_equal(np.asarray(a), np.asarray(a0))
    assert moved and int(s_s.step) == 1
    text = jax.jit(scaled).lower(fresh_state(), batch).as_text(debug_info=True)
    # the scope leads an op's name (train/optimizer.py is in file paths too)
    assert 'loc("optimizer/' in text
