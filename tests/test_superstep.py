"""Device-resident superstep tests (ISSUE 4, ``train/superstep.py``).

The correctness bar is what the arithmetic keeps: a ``lax.scan`` of K steps
and K dispatched steps are DIFFERENT XLA programs of the same mathematics,
and on this jax they agree to a few ulp a step (measured: 1.0e-7 of a leaf's
largest entry after four steps) — so fp32 parity holds ``rtol`` 1e-6, where a
skipped step, a swapped batch or a mis-selected fill batch moves a parameter
by ~lr = 2e-2, over four orders more (``assert_states_close`` has the one
exception: entries whose gradient is rounding noise). What IS exact stays
exact: integer
leaves (the step counter), ``num_graphs``, a fill batch's select against
the carry, and ``train_epoch`` against the same superstep program fed by
hand. bf16 is allclose; with and without a mesh. Plus the scheduling
contracts: bucket-major blocks stay single-bucket, masked fill
batches leave the state untouched, HYDRAGNN_MAX_NUM_BATCH keeps counting raw
loader batches, and a 2-epoch bucketed run stays compile-stable.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.config import update_config
from hydragnn_tpu.datasets import deterministic_graph_data
from hydragnn_tpu.graphs.batching import GraphLoader, PrefetchLoader, collate, compute_pad_spec
from hydragnn_tpu.models import create_model_config
from hydragnn_tpu.parallel import (
    make_mesh,
    make_parallel_train_step,
    put_batch,
    put_block,
    shard_state,
    stack_device_batches,
)
from hydragnn_tpu.preprocess import apply_variables_of_interest
from hydragnn_tpu.train import (
    create_train_state,
    make_superstep,
    make_train_step,
    select_optimizer,
)
from hydragnn_tpu.train.loop import _accumulate, _empty_like, train_epoch, train_validate_test

from test_config import CI_CONFIG

LR = float(CI_CONFIG["NeuralNetwork"]["Training"]["Optimizer"]["learning_rate"])


def setup_model(n_samples=64, batch=4):
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
    return cfg, model, opt, batches, samples


def _state_leaves(state):
    return [np.asarray(x) for x in jax.tree.leaves(state)]


def assert_states_equal(a, b):
    """Bit-identity: for two runs of ONE compiled program."""
    la, lb = _state_leaves(a), _state_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(x, y), "state leaf diverged"


PARITY_RTOL = 1e-6  # a few fp32 ulp a step; see the module docstring
NOISE_SHARE = 0.10  # of the float entries; sound runs read 0-6.7%, faults 50-55%


def assert_states_close(a, b, drift, what="", rtol=PARITY_RTOL):
    """Parity of two DIFFERENT XLA programs of the same training steps (a
    scan against dispatches, a ``vmap`` against a loop, traced weights
    against constants). What the arithmetic keeps, and no more:

    * integer leaves (the step counter) bit for bit: a skipped or an extra
      update shows there first;
    * float entries to ``rtol`` of the entry or of the leaf's largest entry
      (an entry near zero carries the rounding of the leaf-sized terms
      summed into it) — wherever the gradient is more than rounding noise.
      Where it IS noise (a bias in front of a feature norm has a true
      gradient of zero and reads ~1e-8 beside gradients of ~1; so do the
      weights of dead units: half of this CI model) AdamW's update is
      scale-free, lr x sign(noise), and two programs may walk such an entry
      apart by up to an lr a step each. So at most ``NOISE_SHARE`` of the
      float entries may lie outside ``rtol`` (measured on this jax: 0 in
      most runs, 101 of 1,512 for one population member after six steps;
      a skipped step, a swapped or wrong batch or another member's lr puts
      759-826 of 1,512 outside), and no parameter further than ``2 x
      drift`` apart, ``drift`` = steps x lr."""
    la, lb = _state_leaves(a), _state_leaves(b)
    assert len(la) == len(lb), what
    entries = outside = 0
    for x, y in zip(la, lb):
        if not np.issubdtype(x.dtype, np.floating):
            np.testing.assert_array_equal(x, y, err_msg=what)
            continue
        scale = float(np.abs(y).max()) if y.size else 0.0
        gap = np.abs(x.astype(np.float64) - y)
        outside += int((gap > rtol * np.maximum(np.abs(y), scale)).sum())
        entries += x.size
    assert outside <= NOISE_SHARE * entries, (
        f"{what}: {outside} of {entries} float entries over rtol {rtol}")
    for x, y in zip(_state_leaves(a.params), _state_leaves(b.params)):
        np.testing.assert_allclose(x, y, rtol=rtol, atol=2 * drift, err_msg=what)


def _stack_k(batches):
    return jax.tree.map(jnp.asarray, stack_device_batches(batches))


def test_superstep_fp32_exact_parity_single_device():
    """K scanned steps == K individual steps (params, opt state, per-step
    metrics). The scan and the K dispatches are different XLA programs and
    agree to a few ulp, not bit for bit (this jax: 7 of 8 entries of one
    leaf differ by 1.9e-9 absolute, 3.6e-7 relative): float leaves and
    losses hold ``rtol`` 1e-6 — a skipped step or a swapped batch moves a
    parameter by ~lr = 2e-2 — and the step counter and ``num_graphs``,
    which are exact, stay ``==``."""
    _, model, opt, batches, _ = setup_model()
    step = make_train_step(model, opt)
    K = 4
    state0 = create_train_state(model, opt, batches[0])

    s_ref = state0
    ref_metrics = []
    for b in batches[:K]:
        s_ref, m = step(s_ref, jax.tree.map(jnp.asarray, b))
        ref_metrics.append(m)

    superstep = make_superstep(step, K)
    s_sup, m_sup = superstep(state0, _stack_k(batches[:K]))

    assert_states_close(s_ref, s_sup, drift=K * LR)
    assert m_sup["loss"].shape == (K,)
    assert m_sup["tasks_loss"].shape == (K,) + ref_metrics[0]["tasks_loss"].shape
    for i in range(K):
        np.testing.assert_allclose(
            float(ref_metrics[i]["loss"]), float(m_sup["loss"][i]), rtol=PARITY_RTOL
        )
        assert float(ref_metrics[i]["num_graphs"]) == float(m_sup["num_graphs"][i])
        np.testing.assert_allclose(
            np.asarray(ref_metrics[i]["tasks_loss"]),
            np.asarray(m_sup["tasks_loss"][i]),
            rtol=PARITY_RTOL,
        )


def test_superstep_bf16_allclose_single_device():
    _, model, opt, batches, _ = setup_model()
    step = make_train_step(model, opt, compute_dtype=jnp.bfloat16)
    K = 3
    state0 = create_train_state(model, opt, batches[0])
    s_ref = state0
    for b in batches[:K]:
        s_ref, m_ref = step(s_ref, jax.tree.map(jnp.asarray, b))
    superstep = make_superstep(step, K)
    s_sup, m_sup = superstep(state0, _stack_k(batches[:K]))
    # fp32 master params, bf16 compute: tiny cross-program fusion jitter only
    for x, y in zip(_state_leaves(s_ref), _state_leaves(s_sup)):
        if np.issubdtype(np.asarray(x).dtype, np.floating):
            np.testing.assert_allclose(x, y, rtol=2e-2, atol=2e-2)
        else:
            np.testing.assert_array_equal(x, y)
    np.testing.assert_allclose(
        float(m_ref["loss"]), float(m_sup["loss"][-1]), rtol=2e-2
    )


def test_superstep_mesh_parity_8dev():
    """Same contract on the virtual 8-device CPU mesh: a [K, D, ...] block
    through one scanned SPMD dispatch == K grouped SPMD steps, to the few
    ulp two different SPMD programs keep (``rtol`` 1e-6; measured 1.1e-7);
    the step counter bit for bit."""
    _, model, opt, batches, _ = setup_model()
    mesh = make_mesh()
    assert mesh.shape["data"] == 8
    K = 2
    par = make_parallel_train_step(model, opt, mesh)
    state0 = create_train_state(model, opt, batches[0])

    s_ref = shard_state(state0, mesh)
    ref_losses = []
    for i in range(K):
        sb = put_batch(stack_device_batches(batches[i * 8 : (i + 1) * 8]), mesh)
        s_ref, m = par(s_ref, sb)
        ref_losses.append(float(m["loss"]))

    superstep = make_superstep(par, K)
    steps = [
        stack_device_batches(batches[i * 8 : (i + 1) * 8]) for i in range(K)
    ]
    block = put_block(stack_device_batches(steps), mesh)
    s_sup, m_sup = superstep(shard_state(state0, mesh), block)

    assert_states_close(s_ref, s_sup, drift=K * LR)
    np.testing.assert_allclose(ref_losses, np.asarray(m_sup["loss"]), rtol=PARITY_RTOL)


def test_trailing_fill_is_bit_identical_to_real_only():
    """ISSUE 4 satellite: a trailing partial block (real + _empty_like
    masked batches) must yield the state of training on only the real
    batches — the scan body select-skips the optimizer update when a step
    saw zero real graphs (AdamW decay on a zero gradient is not a no-op).
    Against three DISPATCHED steps that is parity of two programs (``rtol``
    1e-6; measured 6.6e-8, where an applied fill step moves a parameter by
    up to 2.0 and the step counter by one). The select itself is exact and
    is held exactly: a block of nothing but fill batches hands back the
    carry bit for bit, step counter included."""
    _, model, opt, batches, _ = setup_model()
    step = make_train_step(model, opt)
    K = 4
    n_real = 3
    state0 = create_train_state(model, opt, batches[0])

    s_ref = state0
    for b in batches[:n_real]:
        s_ref, _ = step(s_ref, jax.tree.map(jnp.asarray, b))

    superstep = make_superstep(step, K)
    fill = [_empty_like(batches[0])] * (K - n_real)
    s_sup, m_sup = superstep(state0, _stack_k(batches[:n_real] + fill))

    assert_states_close(s_ref, s_sup, drift=n_real * LR)
    s_same, m_fill = superstep(s_sup, _stack_k([_empty_like(batches[0])] * K))
    assert_states_equal(s_sup, s_same)
    assert np.asarray(m_fill["num_graphs"]).sum() == 0.0
    g = np.asarray(m_sup["num_graphs"])
    assert g[n_real:].sum() == 0.0  # fill steps carry zero metric weight
    # and the loop's weighted accumulate ignores them entirely
    loss_sup, _, _ = _accumulate([m_sup])
    ref_metrics = []
    s = state0
    for b in batches[:n_real]:
        s, m = step(s, jax.tree.map(jnp.asarray, b))
        ref_metrics.append(m)
    loss_ref, _, _ = _accumulate(ref_metrics)
    np.testing.assert_allclose(loss_sup, loss_ref, rtol=PARITY_RTOL)


def _epoch_by_hand(superstep, state, batches, k):
    """The blocks ``train_epoch`` should stage, staged here: k batches a
    block, the trailing block filled with masked batches."""
    metrics = []
    for i in range(0, len(batches), k):
        block = list(batches[i : i + k])
        block += [_empty_like(block[0])] * (k - len(block))
        state, m = superstep(state, _stack_k(block))
        metrics.append(m)
    return state, metrics


# an epoch's mean loss against the K=1 epoch's: 16 steps read 2.0e-6 apart,
# 10 steps 1.6e-7; two swapped batches move it by 7e-4, a dropped one by 1e-2
EPOCH_LOSS_RTOL = 2e-5


def test_train_epoch_superstep_matches_k1(tmp_path):
    """train_epoch with steps_per_dispatch=K (block staging, double buffer,
    stacked-metric accumulate) reproduces the K=1 epoch.

    Bit for bit where that is defined: against the SAME superstep program
    fed the same blocks by hand, state and epoch loss are identical, so the
    staging drops, reorders and mis-fills nothing. Against 16 DISPATCHED
    steps it is another program: after a few AdamW updates (lr 2e-2) an
    entry whose gradient is noise takes a different sign in the two and the
    states part by ~lr (measured 3e-2 by step 8), so the K=1 epoch holds the
    step counter exactly and the epoch losses to ``EPOCH_LOSS_RTOL``; the
    state's parity over one block is the single-device test's."""
    _, model, opt, batches, _ = setup_model()
    step = make_train_step(model, opt)
    state0 = create_train_state(model, opt, batches[0])

    s1, loss1, tasks1 = train_epoch(step, state0, list(batches))
    K = 4
    superstep = make_superstep(step, K)
    s2, loss2, tasks2 = train_epoch(
        superstep, state0, list(batches), steps_per_dispatch=K
    )
    s_hand, m_hand = _epoch_by_hand(superstep, state0, batches, K)
    assert_states_equal(s_hand, s2)
    loss_hand, tasks_hand, _ = _accumulate(m_hand)
    assert loss2 == loss_hand
    np.testing.assert_array_equal(tasks2, tasks_hand)

    assert int(s1.step) == int(s2.step) == len(batches)
    np.testing.assert_allclose(loss1, loss2, rtol=EPOCH_LOSS_RTOL)
    np.testing.assert_allclose(tasks1, tasks2, rtol=EPOCH_LOSS_RTOL)


def test_train_epoch_superstep_partial_tail_matches_k1():
    """10 batches, K=4: two full blocks + one 2-real/2-fill block must match
    10 individual steps (fill steps are select-skipped): bit for bit against
    the same superstep program fed by hand, and against the ten dispatched
    steps the step counter exactly (ten, not twelve) and the epoch loss to
    ``EPOCH_LOSS_RTOL`` (see ``test_train_epoch_superstep_matches_k1``)."""
    _, model, opt, batches, _ = setup_model()
    step = make_train_step(model, opt)
    state0 = create_train_state(model, opt, batches[0])
    ten = list(batches[:10])
    s1, loss1, _ = train_epoch(step, state0, ten)
    superstep = make_superstep(step, 4)
    s2, loss2, _ = train_epoch(superstep, state0, ten, steps_per_dispatch=4)
    s_hand, m_hand = _epoch_by_hand(superstep, state0, ten, 4)
    assert_states_equal(s_hand, s2)
    assert loss2 == _accumulate(m_hand)[0]

    assert int(s1.step) == int(s2.step) == 10
    np.testing.assert_allclose(loss1, loss2, rtol=EPOCH_LOSS_RTOL)


def _counting(step_fn):
    calls = []

    def wrapped(state, batch):
        calls.append(1)
        return step_fn(state, batch)

    return wrapped, calls


def test_max_num_batch_counts_raw_batches_under_supersteps(monkeypatch):
    """HYDRAGNN_MAX_NUM_BATCH caps RAW loader batches, not dispatches: cap=5
    with K=2 runs ceil(5/2)=3 superstep dispatches (= 6 raw batches trained)
    — if the cap counted blocks it would run 5 dispatches (10 raw)."""
    _, model, opt, batches, _ = setup_model()
    step = make_train_step(model, opt)
    state0 = create_train_state(model, opt, batches[0])
    monkeypatch.setenv("HYDRAGNN_MAX_NUM_BATCH", "5")

    sup, sup_calls = _counting(make_superstep(step, 2))
    train_epoch(sup, state0, list(batches), steps_per_dispatch=2)  # 16 avail
    assert len(sup_calls) == 3  # ceil(5 raw / 2 per dispatch), not 5 blocks

    one, one_calls = _counting(step)
    train_epoch(one, state0, list(batches))
    assert len(one_calls) == 5  # same cap in raw units at K=1


def test_two_epoch_bucketed_superstep_compile_stable(monkeypatch, tmp_path):
    """ISSUE 4 acceptance: pad_buckets + supersteps compile nothing new after
    epoch 0 — HYDRAGNN_COMPILE_SENTINEL=strict must stay green for 2 epochs
    (bucket-major blocks keep the program count bounded by the bucket
    table)."""
    monkeypatch.setenv("HYDRAGNN_COMPILE_SENTINEL", "strict")
    monkeypatch.chdir(tmp_path)
    cfg, model, opt, _, samples = setup_model(n_samples=80)
    nn = copy.deepcopy(cfg["NeuralNetwork"])
    nn["Training"]["num_epoch"] = 2
    nn["Training"]["steps_per_dispatch"] = 3

    train_loader = GraphLoader(samples[:64], 4, shuffle=False, buckets=3)
    assert len(train_loader.buckets) >= 2  # the test must exercise >1 bucket
    val_loader = GraphLoader(samples[64:72], 4)
    test_loader = GraphLoader(samples[72:], 4)
    state = create_train_state(model, opt, next(iter(train_loader)))
    # strict sentinel raises RecompileError on any post-warmup compile
    train_validate_test(
        model, opt, state, train_loader, val_loader, test_loader,
        nn, "superstep_sentinel", verbosity=0,
    )


def test_mesh_superstep_carry_sharding_stays_compile_stable(compile_sentinel):
    """K folding a SMALL epoch into one dispatch must not push a second
    compile past the warm-up: without the carry-sharding pin, GSPMD may
    re-shard the scanned carry's outputs on dispatch 1, and dispatch 2 (=
    epoch 1) compiles against the new input layout."""
    from hydragnn_tpu.train.superstep import state_shardings

    _, model, opt, batches, _ = setup_model()
    mesh = make_mesh()
    par = make_parallel_train_step(model, opt, mesh)
    state = shard_state(create_train_state(model, opt, batches[0]), mesh)
    K = 2
    superstep = make_superstep(par, K, carry_shardings=state_shardings(state))

    def block(i):
        steps = [
            stack_device_batches(batches[j * 8 : (j + 1) * 8])
            for j in range(i * K, i * K + K)
        ]
        return put_block(stack_device_batches(steps), mesh)

    b0, b1 = block(0), block(0)  # build inputs OUTSIDE the guarded region
    state, _ = superstep(state, b0)  # warm-up dispatch (epoch 0)
    with compile_sentinel(max_compiles=0, what="superstep dispatch 2"):
        state, _ = superstep(state, b1)


def test_bucket_major_plan_blocks_are_single_bucket():
    """Every K x group block in the reordered plan draws from ONE bucket, and
    the epoch still covers every sample exactly once."""
    _, _, _, _, samples = setup_model(n_samples=80)
    loader = GraphLoader(samples, 4, shuffle=True, buckets=3)
    assert len(loader.buckets) >= 2
    K = 3
    loader.set_superstep(K)
    for epoch in (0, 1):
        loader.set_epoch(epoch)
        plan = loader.batch_plan()
        pads = [p.as_tuple() for _, p in plan]
        blocks = [pads[i : i + K] for i in range(0, len(pads), K)]
        assert all(len(set(b)) == 1 for b in blocks)
        covered = sorted(int(i) for chunk, _ in plan for i in chunk)
        assert covered == list(range(len(samples)))


def test_bucket_major_plan_with_device_groups():
    """group=2 (mesh stacking) composes with block=2: blocks of group*K
    consecutive batches stay single-bucket and group alignment is preserved
    (a partial device group, if any, is the plan suffix)."""
    _, _, _, _, samples = setup_model(n_samples=80)
    loader = GraphLoader(samples, 4, shuffle=True, buckets=3)
    loader.set_group(2)
    loader.set_superstep(2)
    plan = loader.batch_plan()
    pads = [p.as_tuple() for _, p in plan]
    step = 2 * 2  # group * K
    for i in range(0, (len(pads) // step) * step, step):
        assert len(set(pads[i : i + step])) == 1
    covered = sorted(int(i) for chunk, _ in plan for i in chunk)
    assert covered == list(range(len(samples)))


def test_bucket_major_leftover_tail_uses_top_bucket():
    """The leftover tail re-pads to the TOP bucket — a per-epoch max would
    give the tail a permutation-dependent shape (a fresh compile whenever
    the leftover mix changes)."""
    _, _, _, _, samples = setup_model(n_samples=80)
    loader = GraphLoader(samples, 4, shuffle=True, buckets=3)
    loader.set_superstep(3)
    table = {b.as_tuple() for b in loader.buckets}
    top = loader.buckets[-1].as_tuple()
    for epoch in (0, 1, 2):
        loader.set_epoch(epoch)
        plan = loader.batch_plan()
        pads = [p.as_tuple() for _, p in plan]
        # every block shape comes from the table (nothing epoch-synthesized)
        assert set(pads) <= table
        # non-top buckets appear ONLY as full K-blocks; their leftovers were
        # re-padded to top, so the tail's shape is epoch-independent
        for t in set(pads) - {top}:
            assert pads.count(t) % 3 == 0
        assert pads[-1] == top  # the fill suffix always lands on top


def test_train_epoch_rejects_k_gt_1_with_placement_overrides():
    """Pipeline's group_put (and edge-sharded's put_fn) expect per-batch
    placement — K>1 must fail loudly, not hand them a [K, ...] block."""
    _, model, opt, batches, _ = setup_model()
    step = make_train_step(model, opt)
    state = create_train_state(model, opt, batches[0])
    with pytest.raises(ValueError, match="pin K=1"):
        train_epoch(step, state, list(batches), steps_per_dispatch=2,
                    put_fn=lambda b: b)
    with pytest.raises(ValueError, match="pin K=1"):
        train_epoch(step, state, list(batches), steps_per_dispatch=2,
                    mesh=make_mesh(), group_n=2, group_put=lambda b, m: b)


def test_prefetch_loader_delegates_superstep_and_widens_buffer():
    _, _, _, _, samples = setup_model(n_samples=80)
    inner = GraphLoader(samples, 4, shuffle=False, buckets=3)
    pf = PrefetchLoader(inner, depth=2, device_put=False)
    pf.set_group(2)
    pf.set_superstep(4)
    assert inner.block == 4 and inner.group == 2
    assert pf._effective_depth() >= 4 * 2 + 1  # holds a full block ahead
    # iteration yields the bucket-major order and survives the wider buffer
    batches = list(pf)
    assert len(batches) == len(inner)


def test_double_buffer_preserves_order_and_propagates_errors():
    from hydragnn_tpu.train.superstep import double_buffer

    assert list(double_buffer(iter(range(20)))) == list(range(20))

    def boom():
        yield 1
        raise RuntimeError("staging failed")

    it = double_buffer(boom())
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="staging failed"):
        list(it)


def test_make_superstep_k1_is_identity():
    def fake(state, batch):
        return state, {}

    assert make_superstep(fake, 1) is fake
