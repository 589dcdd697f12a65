"""Fused segment-softmax Pallas kernel (ops/fused_softmax.py): parity vs the
XLA max→exp→sum→divide chain, forward and VJP, plus the GAT/GPS routing.

Runs in interpret mode on the CPU test platform (tests/conftest.py forces
JAX_PLATFORMS=cpu); the same kernel compiles natively on TPU.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.graphs import segment
from hydragnn_tpu.ops.fused_softmax import (
    SM_CERT_BLOCK,
    SM_CERT_WINDOW,
    fused_masked_softmax,
    fused_segment_softmax,
    reference_segment_softmax,
    self_loop_pad,
)


def make_sorted_ids(rng, n_segments, n_rows, reserve_dummy=True):
    """Sorted segment ids over [0, n_segments-1), reserving the last segment
    as the collate dummy (the pad convention every production batch obeys)."""
    hi = n_segments - 1 if reserve_dummy else n_segments
    return np.sort(rng.integers(0, hi, size=n_rows)).astype(np.int32)


def test_forward_parity_dynamic_path():
    rng = np.random.default_rng(0)
    n, e, h = 512, 700, 6  # e not a block multiple: exercises edge padding
    ids = jnp.asarray(make_sorted_ids(rng, n, e))
    logits = jnp.asarray(rng.normal(size=(e, h)), jnp.float32)
    got = fused_segment_softmax(logits, ids, n, interpret=True)
    want = reference_segment_softmax(logits, ids, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_grad_parity():
    rng = np.random.default_rng(1)
    n, e, h = 512, 640, 4
    ids = jnp.asarray(make_sorted_ids(rng, n, e))
    logits = jnp.asarray(rng.normal(size=(e, h)), jnp.float32)

    # (out**2) readout: the VJP's per-segment reduction term matters, so a
    # corrupted Σ s·dy cannot hide behind an all-ones cotangent
    def loss_fused(x):
        return (fused_segment_softmax(x, ids, n, interpret=True) ** 2).sum()

    def loss_ref(x):
        return (reference_segment_softmax(x, ids, n) ** 2).sum()

    gf = jax.grad(loss_fused)(logits)
    gr = jax.grad(loss_ref)(logits)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                               rtol=1e-5, atol=1e-6)


def test_unsorted_ids_fall_back_in_program():
    """Blocks spanning the whole segment range exceed the window; the
    in-program lax.cond must route to the reference chain, keeping results
    exact for EVERY entry (no pad-exemption caveat on the fallback path)."""
    rng = np.random.default_rng(2)
    n, e, h = 512, 512, 4
    ids = make_sorted_ids(rng, n, e)
    perm = rng.permutation(e)
    ids = jnp.asarray(ids[perm])
    logits = jnp.asarray(rng.normal(size=(e, h)), jnp.float32)
    got = fused_segment_softmax(logits, ids, n, interpret=True)
    want = reference_segment_softmax(logits, ids, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_fits_false_and_small_n_take_reference_path():
    rng = np.random.default_rng(3)
    n, e, h = 512, 384, 4
    ids = jnp.asarray(make_sorted_ids(rng, n, e))
    logits = jnp.asarray(rng.normal(size=(e, h)), jnp.float32)
    got = fused_segment_softmax(logits, ids, n, fits=False, interpret=True)
    want = reference_segment_softmax(logits, ids, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # n below the 256 window: statically ineligible, identical chain
    small = fused_segment_softmax(logits[:, :2], ids % 64, 64, interpret=True)
    ref = reference_segment_softmax(logits[:, :2], ids % 64, 64)
    np.testing.assert_array_equal(np.asarray(small), np.asarray(ref))


def _collated_batch(n_samples=48, batch=24, seed=6):
    from conftest import random_molecule_samples
    from hydragnn_tpu.graphs.batching import collate, compute_pad_spec

    samples = random_molecule_samples(n_samples, seed=seed)
    pad = compute_pad_spec(samples, batch)
    return collate(samples[:batch], pad)


def test_collate_certifies_attn_layout_and_kernel_matches():
    """The acceptance path: a real collated batch certifies attn_fits for
    the self-loop-extended receiver layout, and the STATIC kernel route
    (fits=True, no cond in the program) matches the reference chain on
    every non-dummy entry."""
    rng = np.random.default_rng(7)
    b = _collated_batch()
    assert b.meta is not None and b.meta.attn_fits is True
    N = b.x.shape[0]
    E = b.senders.shape[0]
    sl_pad = self_loop_pad(E)
    recv = jnp.asarray(np.concatenate([
        b.receivers,
        np.full(sl_pad, N - 1, np.int32),
        np.arange(N, dtype=np.int32),
    ]))
    h = 6
    logits = jnp.asarray(rng.normal(size=(recv.shape[0], h)), jnp.float32)
    got = fused_segment_softmax(logits, recv, N, fits=True, interpret=True)
    want = reference_segment_softmax(logits, recv, N)
    # the dummy segment (N-1) is exempt from the window certificate: its
    # entries are defined only up to the caller's mask (kernel yields 0,
    # reference a finite value) — compare every non-dummy entry exactly
    real = np.asarray(recv) != N - 1
    np.testing.assert_allclose(
        np.asarray(got)[real], np.asarray(want)[real], rtol=1e-6, atol=1e-6
    )
    assert np.all(np.isfinite(np.asarray(got)))


def test_cert_geometry_is_what_collate_checked():
    # the kernel pins its geometry to the certificate's; a drift here would
    # silently void every attn_fits certificate
    assert (SM_CERT_WINDOW, SM_CERT_BLOCK) == (256, 256)
    assert self_loop_pad(896) == 128 and self_loop_pad(1024) == 0


def test_segment_softmax_routes_by_flag(monkeypatch):
    """segment.segment_softmax: flag on (CPU → interpret kernel) must agree
    with flag off (XLA chain); =0 must restore the chain bit-for-bit."""
    rng = np.random.default_rng(8)
    n, e, h = 512, 600, 6
    ids = jnp.asarray(make_sorted_ids(rng, n, e))
    logits = jnp.asarray(rng.normal(size=(e, h)), jnp.float32)
    monkeypatch.setenv("HYDRAGNN_FUSED_SOFTMAX", "0")
    off = segment.segment_softmax(logits, ids, n)
    want = reference_segment_softmax(logits, ids, n)
    np.testing.assert_array_equal(np.asarray(off), np.asarray(want))
    monkeypatch.setenv("HYDRAGNN_FUSED_SOFTMAX", "1")
    on = segment.segment_softmax(logits, ids, n)
    np.testing.assert_allclose(np.asarray(on), np.asarray(off),
                               rtol=1e-6, atol=1e-6)


# -- dense masked row softmax (GPS) ------------------------------------------


def _masked_reference(x, mask):
    return jax.nn.softmax(jnp.where(mask, x, -1e9), axis=-1)


def _key_mask(rng, mask_shape, masked_graphs=()):
    mask = rng.integers(0, 2, size=mask_shape).astype(bool)
    mask[..., 0] = True  # no all-masked real row
    for g in masked_graphs:  # a padding graph: no key is real
        mask[g] = False
    return jnp.asarray(mask)


# (logits' shape, mask's shape, all-masked leading indices, VMEM budget or
# None for the file's own). A [G, 1, 1, m] mask is read by the graph, any
# other is broadcast to the logits; a budget of 256 KiB holds 64 rows of 232
# (256 lanes) a step with the mask by the graph and 40 with a mask block
MASKED_CASES = {
    "qm9_blocks": ((5, 3, 9, 24), (5, 1, 1, 24), (), None),
    "all_masked_row": ((1, 8), (1, 8), (0,), None),
    "m232_one_step_overhangs": ((2, 2, 232, 232), (2, 1, 1, 232), (), None),
    "m232_eight_steps_last_overhangs": ((2, 2, 232, 232), (2, 1, 1, 232), (), 256 << 10),
    "lane_multiple_steps_divide_rows": ((2, 4, 64, 128), (2, 1, 1, 128), (), 128 << 10),
    "all_masked_pad_graph": ((3, 2, 40, 40), (3, 1, 1, 40), (2,), None),
    "full_mask": ((3, 2, 40, 40), (3, 2, 40, 40), (), None),
    "full_mask_steps_overhang": ((2, 2, 72, 232), (2, 2, 72, 232), (1,), 256 << 10),
    "mask_of_keys_alone": ((2, 3, 16, 24), (24,), (), None),
    "mask_by_query": ((2, 3, 16, 24), (2, 1, 16, 24), (), None),
}


@pytest.mark.parametrize("case", MASKED_CASES)
def test_masked_row_softmax_matches_xla_to_the_second_derivative(case, monkeypatch):
    """Value, gradient and the gradient of a function of the gradient (the
    force-training order) against ``softmax(where(mask, x, -1e9))``, over the
    block rule's cases: rows that are and are not whole blocks, one and many
    grid steps a graph, a mask read by the graph and one read beside the
    logits. A row with no real key is uniform and finite and takes no
    gradient."""
    from hydragnn_tpu.ops import fused_softmax

    shape, mask_shape, masked, budget = MASKED_CASES[case]
    if budget is not None:
        monkeypatch.setattr(fused_softmax, "_VMEM_RESIDENT_LIMIT", budget)
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    w = jnp.asarray(rng.normal(size=shape), jnp.float32)
    mask = _key_mask(rng, mask_shape, masked)
    fused = lambda x: fused_masked_softmax(x, mask, interpret=True)
    ref = lambda x: _masked_reference(x, mask)

    # (out**2) readout: the VJP's row-sum term matters (test_grad_parity)
    first = lambda f: jax.grad(lambda x: (f(x) ** 2 * w).sum())
    second = lambda f: jax.grad(lambda x: (first(f)(x) ** 2).sum())
    orders = lambda f: jax.jit(lambda x: (f(x), first(f)(x), second(f)(x)))(x)
    got, want = orders(fused), orders(ref)
    for a, b, rtol, atol in zip(got, want, (1e-6, 1e-5, 1e-4), (1e-7, 1e-6, 1e-6)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol, atol=atol)
    for g in masked:
        np.testing.assert_allclose(np.asarray(got[0])[g], 1.0 / shape[-1], rtol=1e-6)
        assert not np.asarray(got[1])[g].any()


def test_masked_row_softmax_in_a_rematerialised_scan_differentiated_twice():
    """The form GPS's scanned stack runs, and the one that found the jax 0.9.0
    fault ``routing.saved`` works round: the kernel in a ``lax.scan`` body
    under ``jax.checkpoint``, an energy's force differentiated again."""
    rng = np.random.default_rng(10)
    g, n, h, d, layers = 3, 24, 2, 4, 3
    nodes = jnp.asarray(0.5 * rng.normal(size=(g, n, h, d)), jnp.float32)
    scales = jnp.asarray(1 + 0.1 * rng.normal(size=(layers, 2, h, d)), jnp.float32)
    mask = _key_mask(rng, (g, 1, 1, n), masked_graphs=(2,))

    def force_loss_grad(softmax):
        def layer(x, scale):
            scores = jnp.einsum("gnhd,gmhd->ghnm", x * scale[0], x * scale[1]) / d ** 0.5
            return 0.5 * (x + jnp.einsum("ghnm,gmhd->gnhd", softmax(scores, mask), x)), None

        energy = lambda x, s: jnp.sum(jax.lax.scan(jax.checkpoint(layer), x, s)[0] ** 2)

        def loss(s, x):
            e, force = jax.value_and_grad(energy)(x, s)
            return e + jnp.sum(force ** 2)

        return jax.jit(jax.grad(loss))(scales, nodes)

    got = force_loss_grad(lambda x, m: fused_masked_softmax(x, m, interpret=True))
    want = force_loss_grad(_masked_reference)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("shape, mask_shape, groups", [
    ((9, 16, 232, 232), (9, 1, 1, 232), 9),  # what GPS hands over
    ((9, 16, 232, 232), (232,), 1),
    ((9, 16, 232, 232), (16, 1, 232), 0),  # the heads' axis in full, the graphs' not
    ((9, 16, 232, 232), (9, 16, 232, 232), 0),
    ((9, 16, 232, 232), (9, 1, 232, 232), 0),
    ((9, 16, 232, 232), (9, 1, 1, 1), 0),
    ((1, 8), (1, 8), 1),
])
def test_masked_softmax_reads_the_mask_by_group_where_it_can(shape, mask_shape, groups):
    from hydragnn_tpu.ops.fused_softmax import _row_groups

    assert _row_groups(shape, mask_shape) == groups


def test_masked_softmax_block_follows_the_shape_and_the_budget():
    from hydragnn_tpu.ops.fused_softmax import _rows_per_step

    # the GPS cell's call: 3,712 rows a graph in two steps, 18 a call
    assert _rows_per_step(16 * 232, 232, 1, jnp.float32) == 1856
    # a mask block beside the logits takes a third of the budget
    assert _rows_per_step(9 * 16 * 232, 232, 9 * 16 * 232, jnp.float32) == 1672
    # whole sublane tiles of the dtype
    assert _rows_per_step(27, 24, 1, jnp.float32) == 32
    assert _rows_per_step(27, 24, 1, jnp.bfloat16) == 32
    assert _rows_per_step(1, 8, 1, jnp.float32) == 8


# -- model-level A/B ---------------------------------------------------------


def _forward_ab(cfg_mutator, seed, monkeypatch):
    """Model forward with HYDRAGNN_FUSED_SOFTMAX 0 vs 1 on the same batch."""
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.datasets import deterministic_graph_data
    from hydragnn_tpu.graphs.batching import collate, compute_pad_spec
    from hydragnn_tpu.models import create_model_config, init_model
    from hydragnn_tpu.preprocess import apply_variables_of_interest
    from test_config import CI_CONFIG

    cfg = copy.deepcopy(CI_CONFIG)
    cfg_mutator(cfg)
    samples = deterministic_graph_data(number_configurations=8, seed=seed)
    samples = apply_variables_of_interest(samples, cfg)
    pe_dim = cfg["NeuralNetwork"]["Architecture"].get("pe_dim") or 0
    if pe_dim:
        from hydragnn_tpu.preprocess.encodings import attach_lap_pe

        for s in samples:
            attach_lap_pe(s, pe_dim)
    cfg = update_config(cfg, samples)
    model = create_model_config(cfg)
    pad = compute_pad_spec(samples, 8)
    batch = jax.tree.map(jnp.asarray, collate(samples, pad))
    variables = init_model(model, batch)
    outs = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("HYDRAGNN_FUSED_SOFTMAX", flag)
        outs[flag] = model.apply(variables, batch, train=False)
    return outs


def test_gat_forward_parity_with_fused_softmax(monkeypatch):
    """GAT attention routes the self-loop-extended softmax through the
    kernel; real (masked) head outputs must match the XLA route."""
    outs = _forward_ab(
        lambda cfg: cfg["NeuralNetwork"]["Architecture"].update(
            {"mpnn_type": "GAT"}
        ),
        seed=4, monkeypatch=monkeypatch,
    )
    for a, b in zip(jax.tree.leaves(outs["0"]), jax.tree.leaves(outs["1"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_gps_dense_forward_parity_with_fused_softmax(monkeypatch):
    """GPS dense per-graph attention routes its masked softmax through the
    row kernel; outputs must match the XLA route."""
    def mutate(cfg):
        cfg["NeuralNetwork"]["Architecture"].update({
            "mpnn_type": "GIN", "global_attn_engine": "GPS",
            "global_attn_type": "multihead", "global_attn_heads": 2,
            "hidden_dim": 8, "pe_dim": 4,
        })

    outs = _forward_ab(mutate, seed=5, monkeypatch=monkeypatch)
    for a, b in zip(jax.tree.leaves(outs["0"]), jax.tree.leaves(outs["1"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.slow  # ~5 s; the VJP itself is pinned non-slow by
#                    test_grad_parity, the routing by the forward-parity test
def test_gat_train_step_parity_with_fused_softmax(monkeypatch):
    """One GAT train step flag-on vs flag-off: same loss, same updates —
    pins the custom VJP inside the full model backward pass."""
    import optax

    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.datasets import deterministic_graph_data
    from hydragnn_tpu.graphs.batching import collate, compute_pad_spec
    from hydragnn_tpu.models import create_model_config
    from hydragnn_tpu.preprocess import apply_variables_of_interest
    from hydragnn_tpu.train import create_train_state, make_train_step
    from test_config import CI_CONFIG

    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Architecture"]["mpnn_type"] = "GAT"
    samples = deterministic_graph_data(number_configurations=8, seed=0)
    samples = apply_variables_of_interest(samples, cfg)
    cfg = update_config(cfg, samples)
    model = create_model_config(cfg)
    pad = compute_pad_spec(samples, 8)
    batch = jax.tree.map(jnp.asarray, collate(samples, pad))
    opt = optax.adamw(1e-3)

    results = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("HYDRAGNN_FUSED_SOFTMAX", flag)
        state = create_train_state(model, opt, batch)
        step = make_train_step(model, opt)
        new_state, metrics = step(state, batch)
        results[flag] = (float(metrics["loss"]), new_state.params)

    assert np.isfinite(results["1"][0])
    np.testing.assert_allclose(results["0"][0], results["1"][0], rtol=1e-4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4
        ),
        results["0"][1], results["1"][1],
    )
