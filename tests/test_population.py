"""Population training tests (ISSUE 8, ``train/population.py``).

The correctness bar is EXACT (the acceptance gate): a vmapped N-member
population must reproduce N sequential single-member runs bit for bit in
fp32 — params, optimizer state, and metrics — including composed with K>1
supersteps and with a member diverging mid-run. The sequential reference
for divergence is the scalar where-select skip (``select_state`` on a
finiteness probe): the population deliberately does NOT reuse the
resilience guard's ``lax.cond`` under vmap, whose batched lowering perturbs
healthy members' numerics (measured ~1e-7 on CPU — an instant parity-gate
failure).

Plus the routing contracts: ``run_hpo(backend="vmap")`` returns the random
backend's (best_config, history) shape, assignments partition into
vmappable groups with per-trial fallback for architecture singletons, HPO
dedup/failed-trial satellites, config/flags plumbing, and compile
stability under the strict sentinel.
"""

import copy
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.config import update_config
from hydragnn_tpu.datasets import deterministic_graph_data
from hydragnn_tpu.graphs.batching import GraphLoader, collate, compute_pad_spec
from hydragnn_tpu.models import create_model_config
from hydragnn_tpu.parallel.step import stack_device_batches
from hydragnn_tpu.preprocess import apply_variables_of_interest
from hydragnn_tpu.resilience.guard import _all_finite
from hydragnn_tpu.train import (
    create_population_state,
    create_train_state,
    make_population_step,
    make_superstep,
    make_train_step,
    make_weighted_train_step,
    member_state,
    select_optimizer,
)
from hydragnn_tpu.train.loop import train_epoch
from hydragnn_tpu.train.optimizer import (
    get_hyperparam,
    set_hyperparam,
    set_learning_rate,
)
from hydragnn_tpu.train.population import (
    MemberTracker,
    accumulate_members,
    fit_population,
    resolve_population_size,
)
from hydragnn_tpu.train.superstep import select_state

from test_config import CI_CONFIG
from test_superstep import PARITY_RTOL, assert_states_close


@functools.lru_cache(maxsize=None)
def setup_model(n_samples=64, batch=4):
    """Cached per (n_samples, batch): dataset/model/optimizer build once per
    process. Tests must treat everything returned as read-only (deepcopy cfg
    before mutating); states are created per test."""
    cfg = copy.deepcopy(CI_CONFIG)
    samples = deterministic_graph_data(number_configurations=n_samples, seed=9)
    samples = apply_variables_of_interest(samples, cfg)
    cfg = update_config(cfg, samples)
    model = create_model_config(cfg)
    opt = select_optimizer(cfg["NeuralNetwork"]["Training"]["Optimizer"])
    pad = compute_pad_spec(samples, batch)
    batches = [
        collate(samples[i * batch : (i + 1) * batch], pad)
        for i in range(len(samples) // batch)
    ]
    batches = [jax.tree.map(jnp.asarray, b) for b in batches]
    return cfg, model, opt, batches, samples


@functools.lru_cache(maxsize=None)
def shared_plain_step():
    """ONE jitted plain step for the default setup — its compiled programs
    cache across every test that reuses it (CPU never donates, so sharing
    the callable is safe)."""
    _, model, opt, _, _ = setup_model()
    return make_train_step(model, opt)


@functools.lru_cache(maxsize=None)
def shared_pop_superstep(k=2):
    """ONE K-superstep-folded N-population program shared by the parity and
    compile-stability tests."""
    return make_superstep(make_population_step(shared_plain_step()), k)


def state_with_lr(model, opt, batches, lr):
    s = create_train_state(model, opt, batches[0])
    return s._replace(opt_state=set_learning_rate(s.opt_state, lr))


def assert_trees_equal(a, b, what=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=what)


def make_scalar_select_ref_step(step):
    """The sequential single-member reference for divergence parity: the
    SAME plain step with the population's where-select skip applied at
    scalar width (``select_state`` is the shared primitive)."""

    @jax.jit
    def ref_step(state, batch):
        new, m = step(state, batch)
        ok = _all_finite(
            (m["loss"], new.params, new.batch_stats, new.opt_state)
        )
        new = select_state(ok, new, state)
        m = select_state(ok, m, jax.tree.map(jnp.zeros_like, m))
        m["skipped"] = jnp.logical_not(ok).astype(jnp.int32)
        return new, m

    return ref_step


# -- fp32 parity gate ---------------------------------------------------------


def test_population_fp32_bitmatch_sequential():
    """ISSUE 8 acceptance: N=3 members with distinct lrs, vmapped into one
    program, match 3 sequential plain-step runs — params, opt state, and
    per-member metrics. The name keeps the issue's word; what holds is what
    the arithmetic keeps: ``vmap`` over members and a loop over them are
    different XLA programs and agree to a few ulp on this jax (one leaf, 7
    of 8 entries, 1.9e-9 absolute, 3.6e-7 relative after six steps), so
    float leaves and losses hold ``rtol`` 1e-6 by the rule of
    ``test_superstep.assert_states_close`` (members 1 and 2: every entry;
    member 0: 101 of 1,512 entries are AdamW walking on gradients of
    rounding noise, up to 4.9e-3 apart at lr 1e-3); the step counters and
    ``num_graphs`` are exact and stay ``==``."""
    _, model, opt, batches, _ = setup_model()
    step = shared_plain_step()
    lrs = [1e-3, 3e-3, 1e-2]

    seq_states, seq_metrics = [], []
    for lr in lrs:
        s = state_with_lr(model, opt, batches, lr)
        ms = []
        for b in batches[:6]:
            s, m = step(s, b)
            ms.append(m)
        seq_states.append(s)
        seq_metrics.append(ms)

    pop_step = make_population_step(step)
    pstate = create_population_state(
        model, opt, batches[0], 3, hyperparams={"learning_rate": lrs}
    )
    # the stacked opt_state carries ONE lr per member
    np.testing.assert_allclose(
        np.asarray(pstate.state.opt_state.hyperparams["learning_rate"]), lrs
    )
    pop_metrics = []
    for b in batches[:6]:
        pstate, m = pop_step(pstate, b)
        pop_metrics.append(m)

    # per-member lr actually differs: distinct trajectories from one init
    p0 = jax.tree.leaves(member_state(pstate, 0).params)
    p2 = jax.tree.leaves(member_state(pstate, 2).params)
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(p0, p2)
    )
    for i in range(3):
        assert_states_close(
            seq_states[i], member_state(pstate, i), drift=6 * lrs[i],
            what=f"member {i} state",
        )
        for t, (m_ref, m_pop) in enumerate(zip(seq_metrics[i], pop_metrics)):
            np.testing.assert_allclose(
                float(m_ref["loss"]), float(m_pop["loss"][i]),
                rtol=PARITY_RTOL, err_msg=str((i, t)),
            )
            assert float(m_ref["num_graphs"]) == float(m_pop["num_graphs"][i])
            np.testing.assert_allclose(
                np.asarray(m_ref["tasks_loss"]),
                np.asarray(m_pop["tasks_loss"])[i],
                rtol=PARITY_RTOL,
            )


def test_population_superstep_diverged_member_parity():
    """The full acceptance composition: K=2 supersteps x N=3 members, one
    member (lr=1e30) diverging after its first update. Every member — the
    diverged one frozen at its last finite state included — matches its
    sequential scalar-select reference, and healthy members additionally
    match PLAIN unguarded sequential runs (the skip machinery is
    numerics-free for members that never skip). scan x ``vmap`` against a
    loop of dispatched steps is parity of different XLA programs (``rtol``
    1e-6 by ``test_superstep.assert_states_close``; this jax reads 2.8e-9
    absolute, 4.2e-7 relative in the first leaf that differs after eight
    steps); the skip STREAMS, which step each member skipped, are integers
    and stay exact, as do the step counters (the frozen member's stops at
    one)."""
    _, model, opt, batches, _ = setup_model()
    step = shared_plain_step()
    ref_step = make_scalar_select_ref_step(step)
    lrs = [1e-3, 1e30, 1e-2]
    K = 2
    n_steps = 8

    seq_states, seq_skips = [], []
    for lr in lrs:
        s = state_with_lr(model, opt, batches, lr)
        skips = []
        for b in batches[:n_steps]:
            s, m = ref_step(s, b)
            skips.append(int(m["skipped"]))
        seq_states.append(s)
        seq_skips.append(skips)
    # the scenario really is a MID-run divergence: step 0 applies, later skip
    assert seq_skips[1][0] == 0 and all(seq_skips[1][1:])
    assert not any(seq_skips[0]) and not any(seq_skips[2])

    plain_states = []
    for lr in (lrs[0], lrs[2]):
        s = state_with_lr(model, opt, batches, lr)
        for b in batches[:n_steps]:
            s, _ = step(s, b)
        plain_states.append(s)

    superstep = shared_pop_superstep(K)
    pstate = create_population_state(
        model, opt, batches[0], 3, hyperparams={"learning_rate": lrs}
    )
    skipped = []
    for i in range(n_steps // K):
        block = jax.tree.map(
            jnp.asarray, stack_device_batches(batches[i * K : (i + 1) * K])
        )
        pstate, m = superstep(pstate, block)
        skipped.append(np.asarray(m["skipped"]))

    skipped = np.concatenate(skipped, axis=0)  # [n_steps, N]
    for i in range(3):
        assert skipped[:, i].tolist() == seq_skips[i], f"member {i} skip stream"
        assert_states_close(
            seq_states[i], member_state(pstate, i),
            # the diverged member's lr of 1e30 bounds nothing: hold its
            # frozen parameters to the relative tolerance alone
            drift=n_steps * lrs[i] if lrs[i] < 1 else 0.0,
            what=f"member {i} state",
        )
    assert_states_close(
        plain_states[0], member_state(pstate, 0), drift=n_steps * lrs[0])
    assert_states_close(
        plain_states[1], member_state(pstate, 2), drift=n_steps * lrs[2])


def test_weighted_step_spec_weights_bitmatch_and_custom_weights_differ():
    """make_weighted_train_step with the spec's own (normalized) weights
    follows the static make_train_step; a different weight vector changes
    the trajectory. Per-member weights thread through the population step
    as a [N, T] stack. Weights as data and weights as constants are
    different XLA programs (the compiler folds the constants), equal to a
    few ulp and not to the bit (this jax: 7.5e-9 absolute, 1.6e-7 relative
    in the first leaf that differs after three steps): ``rtol`` 1e-6 by
    ``test_superstep.assert_states_close``, where the scaled weight vector
    of member 1 moves parameters by ~lr; step counters exact."""
    _, model, opt, batches, _ = setup_model(n_samples=32)
    step = make_train_step(model, opt)  # 32-sample shapes: own program
    wstep = make_weighted_train_step(model, opt)
    w_spec = jnp.asarray(model.spec.task_weights)

    s1 = create_train_state(model, opt, batches[0])
    s2 = create_train_state(model, opt, batches[0])
    for b in batches[:3]:
        s1, m1 = step(s1, b)
        s2, m2 = wstep(s2, b, w_spec)
    lr = float(CI_CONFIG["NeuralNetwork"]["Training"]["Optimizer"]["learning_rate"])
    assert_states_close(s1, s2, drift=3 * lr, what="traced spec weights vs static")
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=PARITY_RTOL)

    # population: member 0 uses the spec weights (parity), member 1 a scaled
    # vector (different gradient scale -> different params)
    tw = [list(model.spec.task_weights), [w * 0.1 for w in model.spec.task_weights]]
    pop_step = make_population_step(wstep, task_weights=tw)
    pstate = create_population_state(model, opt, batches[0], 2)
    for b in batches[:3]:
        pstate, _ = pop_step(pstate, b)
    assert_states_close(
        s1, member_state(pstate, 0), drift=3 * lr, what="member 0 spec weights")
    diffs = [
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree.leaves(member_state(pstate, 1).params),
            jax.tree.leaves(s1.params),
        )
    ]
    assert any(diffs)


def test_population_seeds_give_distinct_inits():
    _, model, opt, batches, _ = setup_model(n_samples=16)
    pstate = create_population_state(model, opt, batches[0], 2, seeds=[0, 1])
    p0 = jax.tree.leaves(member_state(pstate, 0).params)
    p1 = jax.tree.leaves(member_state(pstate, 1).params)
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(p0, p1)
    )


# -- epoch loop / compile stability ------------------------------------------


def test_train_epoch_population_superstep_matches_eager():
    """train_epoch drives the population superstep (block staging, member
    accumulator) to the same final state as the eager per-dispatch loop,
    and returns per-member epoch losses."""
    _, model, opt, all_batches, _ = setup_model()
    batches = all_batches[:8]
    step = shared_plain_step()
    lrs = [1e-3, 1e-2]
    K = 4
    pop_step = make_population_step(step)
    superstep = make_superstep(pop_step, K)

    pstate = create_population_state(
        model, opt, batches[0], 2, hyperparams={"learning_rate": lrs}
    )
    out, loss, tasks = train_epoch(
        superstep, pstate, list(batches), steps_per_dispatch=K,
        accumulate=functools.partial(accumulate_members, n_members=2),
    )
    assert loss.shape == (2,) and np.all(np.isfinite(loss))
    assert tasks.shape[0] == 2

    ref = create_population_state(
        model, opt, batches[0], 2, hyperparams={"learning_rate": lrs}
    )
    metrics = []
    for b in batches:
        ref, m = pop_step(ref, b)
        metrics.append(m)
    assert_trees_equal(ref, out, "epoch loop vs eager population")
    ref_loss, _, _ = accumulate_members(metrics, n_members=2)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-12)


def test_population_epoch_is_one_program(compile_sentinel):
    """ISSUE 8: vmap x scan composition stays compile-stable — after the
    warm-up dispatch, an entire further population epoch (4 superstep
    blocks) compiles NOTHING new under the strict sentinel."""
    _, model, opt, batches, _ = setup_model()
    K = 2
    superstep = shared_pop_superstep(K)
    pstate = create_population_state(
        model, opt, batches[0], 3,
        hyperparams={"learning_rate": [1e-3, 3e-3, 1e-2]},
    )

    def block(i):
        return jax.tree.map(
            jnp.asarray, stack_device_batches(batches[i * K : (i + 1) * K])
        )

    pstate, _ = superstep(pstate, block(0))  # warm-up dispatch compiles all
    with compile_sentinel(max_compiles=0, what="population epoch"):
        for i in range(4):
            pstate, _ = superstep(pstate, block(i))


def test_member_tracker_streaks_and_statuses():
    t = MemberTracker(n_members=3, max_consecutive=3, lag=0)
    # member 1 skips 3 in a row -> diverged; member 2's skips never streak
    t.push(np.array([0, 1, 0]))
    t.push(np.array([[0, 1, 1], [0, 1, 0]]))  # a [K, N] superstep block
    t.finish()
    assert t.statuses() == ["ok", "diverged", "ok"]
    assert t.total.tolist() == [0, 3, 1]
    # never raises, unlike the resilience SkipTracker — by design


def test_fit_population_reports_diverged_member():
    """End-to-end divergence routing: a member with an absurd lr freezes and
    reports status 'diverged' with objective inf; healthy members finish
    with finite objectives; the ensemble stats cover survivors only."""
    cfg, model, opt, _, samples = setup_model(n_samples=48)
    nn = copy.deepcopy(cfg["NeuralNetwork"])
    nn["Training"]["num_epoch"] = 2
    nn["Training"]["resilience"] = {"max_consecutive_skips": 3}
    train_loader = GraphLoader(samples[:32], 4, shuffle=False)
    val_loader = GraphLoader(samples[32:], 4)
    pstate, summary = fit_population(
        model, opt, train_loader, val_loader, nn,
        n_members=3, learning_rates=[1e-3, 1e30, 1e-2],
    )
    statuses = [m["status"] for m in summary["members"]]
    assert statuses == ["ok", "diverged", "ok"]
    assert summary["members"][1]["objective"] == float("inf")
    assert all(np.isfinite(summary["members"][i]["objective"]) for i in (0, 2))
    assert summary["members"][1]["skipped_steps"] > 0
    assert summary["ensemble"]["n_finite"] == 2
    assert summary["ensemble"]["variance"] is not None


def test_population_summary_honors_path_argument(tmp_path, monkeypatch):
    """Regression (ISSUE 15 satellite): ``population.json`` used to hardcode
    ``"./logs"`` and ignore the configurable ``path=`` checkpoint.py threads
    everywhere — a relocated log tree silently dropped its summary into the
    CWD. ``train_population(path=...)`` must write the summary (and the
    rolling per-epoch population checkpoints) under that path."""
    from hydragnn_tpu.train.population import train_population

    monkeypatch.chdir(tmp_path)  # a ./logs write would be visible here
    monkeypatch.setenv("HYDRAGNN_VALTEST", "0")
    cfg, model, opt, _, samples = setup_model(n_samples=48)
    nn = copy.deepcopy(cfg["NeuralNetwork"])
    nn["Training"]["num_epoch"] = 1
    nn["Training"]["population"] = {"size": 2}
    nn["Training"]["resilience"] = {"checkpoint_every_epoch": True}
    loaders = (
        GraphLoader(samples[:32], 4, shuffle=False),
        GraphLoader(samples[32:40], 4),
        GraphLoader(samples[40:], 4),
    )
    dest = tmp_path / "relocated"
    _, summary = train_population(
        model, opt, *loaders, nn, "pop_path_run", path=str(dest)
    )
    summary_path = dest / "pop_path_run" / "population.json"
    assert summary_path.exists()
    assert json.load(open(summary_path))["n_members"] == 2
    # the rolling per-epoch checkpoint landed under the same root
    assert (dest / "pop_path_run" / "checkpoints").exists()
    # and NOTHING leaked into the hardcoded default
    assert not (tmp_path / "logs" / "pop_path_run").exists()


# -- config / flags / run_training routing -----------------------------------


def test_run_training_population_e2e(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    import hydragnn_tpu
    from hydragnn_tpu.train.population import PopulationState

    samples = deterministic_graph_data(number_configurations=40, seed=7)
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 1
    cfg["NeuralNetwork"]["Training"]["steps_per_dispatch"] = 2
    cfg["NeuralNetwork"]["Training"]["population"] = {
        "size": 3,
        "learning_rates": [1e-3, 3e-3, 1e-2],
    }
    pstate, model, aug = hydragnn_tpu.run_training(cfg, samples=list(samples))
    assert isinstance(pstate, PopulationState) and pstate.n_members == 3
    summaries = list((tmp_path / "logs").glob("*/population.json"))
    assert len(summaries) == 1
    summary = json.loads(summaries[0].read_text())
    assert [m["status"] for m in summary["members"]] == ["ok"] * 3
    assert summary["ensemble"]["n_finite"] == 3
    # default seeds = range(size): a deep ensemble gets distinct inits
    assert [m["seed"] for m in summary["members"]] == [0, 1, 2]


def test_population_rejects_mesh_modes():
    import hydragnn_tpu

    samples = deterministic_graph_data(number_configurations=20, seed=3)
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Training"]["population"] = {"size": 2}
    cfg["NeuralNetwork"]["Architecture"]["parallelism"] = "pipeline"
    with pytest.raises(ValueError, match="population"):
        hydragnn_tpu.run_training(cfg, samples=list(samples))


def test_population_flag_overrides_config(monkeypatch):
    assert resolve_population_size({"population": {"size": 4}}) == 4
    assert resolve_population_size({}) == 0
    monkeypatch.setenv("HYDRAGNN_POPULATION", "6")
    assert resolve_population_size({"population": {"size": 4}}) == 6
    from hydragnn_tpu.utils import flags

    assert "HYDRAGNN_POPULATION" in flags.describe()


def test_schema_population_block_validation():
    cfg = copy.deepcopy(CI_CONFIG)
    samples = deterministic_graph_data(number_configurations=8, seed=1)
    out = update_config(cfg, samples)
    pop = out["NeuralNetwork"]["Training"]["population"]
    assert pop["size"] == 0 and pop["seeds"] is None
    bad = copy.deepcopy(CI_CONFIG)
    bad["NeuralNetwork"]["Training"]["population"] = {
        "size": 3, "learning_rates": [1e-3, 1e-2],
    }
    with pytest.raises(ValueError, match="learning_rates"):
        update_config(bad, samples)


def test_weight_decay_injection_is_explicit_only():
    """Back-compat contract: implicit decay stays a baked constant (the
    historical opt_state pytree, so pre-existing checkpoints restore); an
    EXPLICIT Training.Optimizer.weight_decay injects it as a runtime
    hyperparameter for per-member decays."""
    _, model, opt, batches, _ = setup_model(n_samples=8)
    s = create_train_state(model, opt, batches[0])
    assert "weight_decay" not in s.opt_state.hyperparams  # default AdamW
    with pytest.raises(KeyError, match="nope"):
        set_hyperparam(s.opt_state, "nope", 1.0)
    wd_opt = select_optimizer(
        {"type": "AdamW", "learning_rate": 1e-3, "weight_decay": 3e-4}
    )
    wd_state = wd_opt.init({"w": jnp.zeros(3)})
    assert get_hyperparam(wd_state, "weight_decay") == pytest.approx(3e-4)
    sgd = select_optimizer({"type": "SGD", "learning_rate": 1e-3})
    with pytest.raises(KeyError, match="weight_decay"):
        set_hyperparam(sgd.init({"w": jnp.zeros(3)}), "weight_decay", 1e-4)


def test_schema_autofills_weight_decay_for_population_decays():
    """Training.population.weight_decays auto-fills an explicit
    Optimizer.weight_decay (the optax default) so the decay gets injected;
    non-decoupled optimizers reject per-member decays loudly."""
    samples = deterministic_graph_data(number_configurations=8, seed=1)
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Training"]["population"] = {
        "size": 2, "weight_decays": [1e-4, 1e-3],
    }
    out = update_config(cfg, samples)
    assert out["NeuralNetwork"]["Training"]["Optimizer"]["weight_decay"] == \
        pytest.approx(1e-4)  # optax.adamw's signature default
    bad = copy.deepcopy(cfg)
    bad["NeuralNetwork"]["Training"]["Optimizer"] = {
        "type": "SGD", "learning_rate": 1e-3,
    }
    with pytest.raises(ValueError, match="decoupled-decay"):
        update_config(bad, samples)


def test_population_per_member_weight_decays_train():
    """Per-member decays end-to-end: explicit Optimizer.weight_decay →
    injected leaf → [N] stack → members with very different decays diverge
    in params."""
    _, model, _, batches, _ = setup_model(n_samples=8)
    opt = select_optimizer(
        {"type": "AdamW", "learning_rate": 1e-3, "weight_decay": 1e-4}
    )
    pstate = create_population_state(
        model, opt, batches[0], 2,
        hyperparams={"weight_decay": [0.0, 0.5]},
    )
    np.testing.assert_allclose(
        np.asarray(pstate.state.opt_state.hyperparams["weight_decay"]), [0.0, 0.5]
    )
    pop_step = make_population_step(make_train_step(model, opt))
    for b in batches[:2]:
        pstate, m = pop_step(pstate, b)
    assert not np.asarray(m["skipped"]).any()
    p0 = jax.tree.leaves(member_state(pstate, 0).params)
    p1 = jax.tree.leaves(member_state(pstate, 1).params)
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(p0, p1)
    )


# -- run_hpo backend="vmap" ---------------------------------------------------


def _fake_population_objective(calls=None):
    """Deterministic stand-in: objective = the member's lr (lower is
    better), no training. Records (config, members) calls."""

    def pop_obj(cfg_static, members):
        if calls is not None:
            calls.append((cfg_static, members))
        return [
            (float(m["NeuralNetwork.Training.Optimizer.learning_rate"]), "ok")
            for m in members
        ]

    return pop_obj


def test_run_hpo_vmap_scalar_space_contract():
    """Acceptance: backend="vmap" on a scalar-only space returns the random
    backend's (best_config, best_value, history) contract — best excludes
    non-ok trials, history entries carry assignment/value/status."""
    from hydragnn_tpu.utils.hpo import run_hpo

    base = copy.deepcopy(CI_CONFIG)
    space = {"NeuralNetwork.Training.Optimizer.learning_rate": ("log_float", 1e-5, 1e-1)}

    def never(cfg):
        raise AssertionError("scalar-only space must not use the fallback objective")

    calls = []
    best_cfg, best_val, hist = run_hpo(
        base, space, never, n_trials=5, seed=0, backend="vmap",
        population_objective=_fake_population_objective(calls),
    )
    assert len(calls) == 1 and len(calls[0][1]) == 5  # ONE population, 5 members
    assert len(hist) == 5
    assert all(h["mode"] == "vmap" and h["status"] == "ok" for h in hist)
    assert best_val == min(h["value"] for h in hist)
    assert (
        best_cfg["NeuralNetwork"]["Training"]["Optimizer"]["learning_rate"]
        == best_val  # fake objective = lr
    )


def test_run_hpo_vmap_partitions_and_falls_back():
    """Mixed space: assignments group by their architecture key; multi-member
    groups train as one population, singleton groups fall back to the
    per-trial objective (the subprocess path)."""
    from hydragnn_tpu.utils.hpo import run_hpo, sample_unique_assignments

    base = copy.deepcopy(CI_CONFIG)
    space = {
        "NeuralNetwork.Architecture.hidden_dim": [4, 8, 16, 32],
        "NeuralNetwork.Training.Optimizer.learning_rate": ("log_float", 1e-4, 1e-1),
    }
    # pin a seed whose sample contains BOTH a singleton and a multi-member
    # hidden_dim group
    seed = next(
        s for s in range(50)
        if (lambda counts: 1 in counts.values() and max(counts.values()) > 1)(
            __import__("collections").Counter(
                a["NeuralNetwork.Architecture.hidden_dim"]
                for a in sample_unique_assignments(
                    space, np.random.default_rng(s), 5
                )
            )
        )
    )
    fallback_calls = []

    def objective(cfg):
        fallback_calls.append(cfg["NeuralNetwork"]["Architecture"]["hidden_dim"])
        return 1000.0 + cfg["NeuralNetwork"]["Architecture"]["hidden_dim"]

    pop_calls = []
    best_cfg, best_val, hist = run_hpo(
        base, space, objective, n_trials=5, seed=seed, backend="vmap",
        population_objective=_fake_population_objective(pop_calls),
    )
    from collections import Counter

    modes = Counter(h["mode"] for h in hist)
    assert modes["fallback"] == len(fallback_calls) >= 1
    assert modes["vmap"] >= 2
    # every vmapped group shares one architecture config and only scalar
    # keys vary within it
    for cfg_static, members in pop_calls:
        assert all(
            set(m) == {"NeuralNetwork.Training.Optimizer.learning_rate"}
            for m in members
        )
    assert np.isfinite(best_val)


def test_run_hpo_vmap_diverged_members_excluded_from_best():
    from hydragnn_tpu.utils.hpo import run_hpo

    base = {"NeuralNetwork": {"Training": {"Optimizer": {"learning_rate": 1e-3}}}}
    space = {"NeuralNetwork.Training.Optimizer.learning_rate": ("log_float", 1e-5, 1e-1)}

    def pop_obj(cfg_static, members):
        out = []
        for i, m in enumerate(members):
            lr = float(m["NeuralNetwork.Training.Optimizer.learning_rate"])
            out.append(
                (float("inf"), "diverged") if i == 0 else (lr, "ok")
            )
        return out

    _, best_val, hist = run_hpo(
        base, space, lambda c: 0.0, n_trials=4, seed=2, backend="vmap",
        population_objective=pop_obj,
    )
    assert sum(h["status"] == "diverged" for h in hist) == 1
    assert np.isfinite(best_val)
    assert best_val == min(h["value"] for h in hist if h["status"] == "ok")


# -- HPO satellites -----------------------------------------------------------


def test_hpo_dedups_small_categorical_space():
    """10 trials over a 3-point space used to re-train duplicates; now every
    distinct point evaluates exactly once."""
    from hydragnn_tpu.utils.hpo import run_hpo

    calls = []

    def objective(cfg):
        calls.append(cfg["x"])
        return float(cfg["x"])

    best_cfg, best_val, hist = run_hpo(
        {"x": 0}, {"x": [1, 2, 3]}, objective, n_trials=10, seed=0
    )
    assert sorted(calls) == [1, 2, 3]  # each once, duplicates re-drawn
    assert len(hist) == 3
    assert best_val == 1.0 and best_cfg["x"] == 1


def test_hpo_failed_trial_recorded_not_fatal():
    """A non-TrainingDivergedError exception is a trial RESULT (status
    'failed', objective inf), not a sweep-killer — in the random branch and
    therefore in the optuna objective that shares ``evaluate``."""
    from hydragnn_tpu.utils.hpo import run_hpo

    def objective(cfg):
        if cfg["x"] == 2:
            raise ValueError("worker blew up")
        return float(cfg["x"])

    best_cfg, best_val, hist = run_hpo(
        {"x": 0}, {"x": [1, 2, 3]}, objective, n_trials=9, seed=0
    )
    by_status = {h["status"] for h in hist}
    assert "failed" in by_status and "ok" in by_status
    failed = [h for h in hist if h["status"] == "failed"]
    assert all(h["value"] == float("inf") for h in failed)
    # the exception text survives into the record — a systematic setup bug
    # must be diagnosable, not N anonymous infs
    assert all("worker blew up" in h["error"] for h in failed)
    assert best_val == 1.0

    # ... and when EVERY trial fails the sweep still dies loudly, naming
    # the last underlying error
    with pytest.raises(RuntimeError, match="boom"):
        run_hpo(
            {"x": 0}, {"x": [1, 2]},
            lambda cfg: (_ for _ in ()).throw(ValueError("boom")),
            n_trials=4, seed=0,
        )


def test_subprocess_objective_records_assignment(tmp_path):
    """keep_dir trial records carry the sampled assignment (self-describing
    post-hoc records), threaded from run_hpo through the objective's
    optional kwarg."""
    from hydragnn_tpu.utils.hpo import run_hpo, subprocess_objective

    worker = tmp_path / "ok.py"
    worker.write_text(
        "import json, sys\n"
        "cfg = json.load(open(sys.argv[1]))\n"
        "json.dump({'objective': float(cfg['x'])}, open(sys.argv[2], 'w'))\n"
    )
    keep = tmp_path / "keep"
    obj = subprocess_objective(str(worker), timeout=60, keep_dir=str(keep))
    best_cfg, best_val, hist = run_hpo(
        {"x": 0}, {"x": [1, 2, 3]}, obj, n_trials=3, seed=1
    )
    recs = [json.loads(p.read_text()) for p in sorted(keep.glob("trial_*.json"))]
    assert len(recs) == len(hist) == 3
    rec_assignments = {json.dumps(r["assignment"], sort_keys=True) for r in recs}
    hist_assignments = {json.dumps(h["assignment"], sort_keys=True) for h in hist}
    assert rec_assignments == hist_assignments
    # direct calls without an assignment still work (back-compat)
    assert obj({"x": 5}) == 5.0


def test_accumulate_members_weighted_mean_and_all_skipped_nan():
    metrics = [
        {
            "loss": np.array([1.0, 5.0]),
            "tasks_loss": np.array([[1.0], [5.0]]),
            "num_graphs": np.array([2.0, 0.0]),  # member 1 skipped
        },
        {
            "loss": np.array([2.0, 7.0]),
            "tasks_loss": np.array([[2.0], [7.0]]),
            "num_graphs": np.array([2.0, 0.0]),
        },
    ]
    loss, tasks, _ = accumulate_members(metrics, n_members=2)
    assert loss[0] == pytest.approx(1.5)
    assert np.isnan(loss[1])  # nothing trained: NaN, never a fake 0.0
    assert tasks.shape == (2, 1) and np.isnan(tasks[1, 0])
