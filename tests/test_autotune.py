"""Shared kernel-geometry autotuner (ISSUE 12, ``ops/autotune.py``).

The contracts: candidates pass each kernel's static-fit + certificate
filters BEFORE timing; adoption requires a paired-window win beyond the
noise floor (ties keep the hard-coded default); choices persist per
(kernel, backend, shape-signature) next to the XLA compile cache and a
warm lookup costs zero sweeps; wrappers only honor a cached geometry when
the collate certificate provably transfers to it.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.ops import autotune as at
from hydragnn_tpu.ops.fused_scatter import (
    gather_scatter_sum,
    reference_gather_scatter,
    window_fits_host,
)


@pytest.fixture
def tuner_cache(tmp_path, monkeypatch):
    """Isolated on-disk cache per test (next to a throwaway compile-cache
    dir, exactly where production persists it)."""
    cache_dir = tmp_path / "jax_cache"
    monkeypatch.delenv("HYDRAGNN_COMPILE_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache_dir))
    at.reset_cache()
    yield cache_dir
    at.reset_cache()


def _const_timer(ms_by_tag):
    """Fake ``_time_window`` reading a tag the build attached to its fn —
    deterministic sweeps without real wall clock."""

    def timer(fn, args, reps):
        return ms_by_tag[fn._tag]

    return timer


def _tagged_build(tag):
    def build():
        def fn(*a):
            return None

        fn._tag = tag
        return fn, ()

    return build


def test_sweep_adopts_measured_winner(tuner_cache, monkeypatch):
    monkeypatch.setattr(at, "_time_window", _const_timer({"slow": 10.0, "fast": 4.0}))
    rec = at.sweep("fused_scatter", "test_sig",
                   {"slow": _tagged_build("slow"), "fast": _tagged_build("fast")},
                   "slow", reps=1, pairs=4)
    assert rec["geometry"] == "fast"
    assert rec["swept"] is True and rec["cache"] == "miss"
    assert rec["evidence"]["trials"]["fast"]["adopted"] is True


def test_sweep_tie_keeps_default(tuner_cache, monkeypatch):
    # identical timings: overhead 0 is NOT a win beyond the floor — the
    # hard-coded default can only be displaced by a measured improvement
    monkeypatch.setattr(at, "_time_window", _const_timer({"a": 5.0, "b": 5.0}))
    rec = at.sweep("fused_scatter", "tie_sig",
                   {"a": _tagged_build("a"), "b": _tagged_build("b")},
                   "a", reps=1, pairs=4)
    assert rec["geometry"] == "a"
    assert rec["evidence"]["trials"]["b"]["adopted"] is False


def test_warm_cache_is_zero_sweep_cost(tuner_cache, monkeypatch):
    monkeypatch.setattr(at, "_time_window", _const_timer({"a": 5.0, "b": 1.0}))
    at.sweep("fused_scatter", "warm_sig",
             {"a": _tagged_build("a"), "b": _tagged_build("b")},
             "a", reps=1, pairs=4)
    before = at.sweeps_run()
    rec = at.sweep("fused_scatter", "warm_sig",
                   {"a": _tagged_build("a"), "b": _tagged_build("b")},
                   "a", reps=1, pairs=4)
    assert rec["cache"] == "hit" and rec["swept"] is False
    assert rec["sweep_s"] == 0.0
    assert at.sweeps_run() == before  # no timing ran at all
    assert rec["geometry"] == "b"


def test_cache_persists_to_disk_next_to_compile_cache(tuner_cache):
    at.record("fused_scatter", "disk_sig", (512, 256), {"why": "test"})
    path = at.cache_path()
    assert path is not None and path.startswith(str(tuner_cache))
    assert os.path.basename(path) == "ops_autotune.json"
    # a fresh in-memory view reloads the persisted choice
    at.reset_cache()
    rec = at.lookup("fused_scatter", "disk_sig")
    assert rec is not None and rec["geometry"] == [512, 256]
    # the key carries kernel|backend|sig: another backend's timings can
    # never leak into this one's choices
    blob = json.load(open(path))
    key = f"fused_scatter|{jax.default_backend()}|disk_sig"
    assert key in blob["choices"]


def test_version_mismatch_discards_cache(tuner_cache):
    at.record("fused_scatter", "ver_sig", (512, 256))
    path = at.cache_path()
    blob = json.load(open(path))
    blob["version"] = at._SCHEMA_VERSION + 1
    json.dump(blob, open(path, "w"))
    at.reset_cache()
    # stale cert rules must not outlive the proof that admitted them
    assert at.lookup("fused_scatter", "ver_sig") is None


def test_gs_candidates_filtered_by_static_rules():
    # 8-aligned, roomy: the full grid survives
    assert (256, 256) in at.gs_static_candidates(1024, 64)
    assert (512, 256) in at.gs_static_candidates(1024, 64)
    # too few nodes for the wide windows
    small = at.gs_static_candidates(192, 64)
    assert all(w <= 192 for w, _ in small) and small
    # non-8-aligned node count: nothing is admissible
    assert at.gs_static_candidates(1001, 64) == []
    # VMEM filter: resident h+out blow the budget at huge channel counts
    assert at.gs_static_candidates(4096, 4096) == []


def test_gs_cert_compatible_is_same_block_wider_window():
    # same block, window >= the certified 256, array wide enough: transfers
    assert at.gs_cert_compatible(512, 256, 1024)
    assert at.gs_cert_compatible(256, 256, 256)
    # narrower window: the 256-cert says nothing about 128 spans
    assert not at.gs_cert_compatible(128, 256, 1024)
    # different blocking: different block boundaries, cert is void
    assert not at.gs_cert_compatible(256, 512, 1024)
    # clamp argument needs the array at least window wide
    assert not at.gs_cert_compatible(512, 256, 384)


def _sorted_graph(n, e, c, seed=0):
    """Tiny synthetic batch with collate's layout property (near-sorted
    ids, certified at the default geometry): n 8-aligned nodes, e edges."""
    rng = np.random.default_rng(seed)
    ids = np.sort(rng.integers(0, n - 1, size=e))
    snd = jnp.asarray(ids, jnp.int32)
    rcv = jnp.asarray(np.sort(rng.integers(0, n - 1, size=e)), jnp.int32)
    h = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 1.0, size=(e,)), jnp.float32)
    return h, snd, rcv, w


def test_autotune_gather_scatter_real_sweep_records_choice(tuner_cache, monkeypatch):
    """One REAL (interpret-mode) sweep over a two-candidate grid: builds
    compile and run, the choice lands in the cache, and the warm call is a
    pure lookup."""
    monkeypatch.setattr(at, "GS_CANDIDATES", ((256, 256), (128, 256)))
    h, snd, rcv, w = _sorted_graph(256, 512, 8)
    assert window_fits_host(np.asarray(snd), 256, 256, 256, exempt_pad_id=True)
    rec = at.autotune_gather_scatter(h, snd, rcv, 256, w, reps=1, pairs=2)
    assert rec["swept"] is True
    assert tuple(rec["geometry"]) in ((256, 256), (128, 256))
    warm = at.autotune_gather_scatter(h, snd, rcv, 256, w)
    assert warm["cache"] == "hit" and warm["sweep_s"] == 0.0


def test_uncertifiable_default_is_kept_uncontested(tuner_cache):
    # ids spanning the whole array in every block: no geometry certifies
    n, e, c = 1024, 512, 8
    rng = np.random.default_rng(3)
    snd = jnp.asarray(np.concatenate([[0, n - 2] * (e // 2)])[:e], jnp.int32)
    rcv = snd
    h = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
    rec = at.autotune_gather_scatter(h, snd, rcv, n, None, reps=1, pairs=2)
    assert tuple(rec["geometry"]) == (256, 256)
    assert rec["swept"] is False
    assert "not certifiable" in rec["evidence"]["note"]


def test_tuned_geometry_hook_gated_and_cert_checked(tuner_cache, monkeypatch):
    sig = at.gs_signature(1024, 2048, 16, jnp.float32)
    at.record("fused_scatter", sig, (512, 256))
    # flag off (the default): the wrapper never consults the cache
    monkeypatch.delenv("HYDRAGNN_OPS_AUTOTUNE", raising=False)
    assert at.tuned_gather_scatter_geometry(1024, 2048, 16, jnp.float32) is None
    monkeypatch.setenv("HYDRAGNN_OPS_AUTOTUNE", "1")
    assert at.tuned_gather_scatter_geometry(1024, 2048, 16, jnp.float32) == (512, 256)
    # a cached choice whose certificate does NOT transfer is refused
    at.record("fused_scatter", sig, (128, 256))
    assert at.tuned_gather_scatter_geometry(1024, 2048, 16, jnp.float32) is None
    # corrupt geometry: refused, not crashed
    at.record("fused_scatter", sig, "garbage")
    assert at.tuned_gather_scatter_geometry(1024, 2048, 16, jnp.float32) is None


def test_wrapper_parity_with_tuned_geometry(tuner_cache, monkeypatch):
    """gather_scatter_sum under HYDRAGNN_OPS_AUTOTUNE with a cached wider
    window must stay numerically the same op (the certificate-transfer rule
    is exactly what makes this safe)."""
    n, e, c = 512, 1024, 8
    h, snd, rcv, w = _sorted_graph(n, e, c, seed=5)
    assert window_fits_host(np.asarray(snd), n, 256, 256, exempt_pad_id=True)
    at.record("fused_scatter", at.gs_signature(n, e, c, h.dtype), (512, 256))
    monkeypatch.setenv("HYDRAGNN_OPS_AUTOTUNE", "1")
    assert at.tuned_gather_scatter_geometry(n, e, c, h.dtype) == (512, 256)
    out = gather_scatter_sum(h, snd, rcv, n, w, fused=True)
    ref = reference_gather_scatter(h, snd, rcv, n, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_quant_row_block_geometry():
    from hydragnn_tpu.ops.quant_matmul import quant_dense, quantize_weight

    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    w_q, s_w = quantize_weight(jnp.asarray(rng.normal(size=(16, 8)), jnp.float32))
    b = jnp.asarray(rng.normal(size=(8,)), jnp.float32)
    base = quant_dense(x, w_q, s_w, 0.05, b, kernel=True, interpret=True)
    for rb in (16, 32):
        out = quant_dense(x, w_q, s_w, 0.05, b, kernel=True, interpret=True,
                          row_block=rb)
        # dense rows carry no layout contract: every admissible block is
        # the same arithmetic
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="row_block"):
        quant_dense(x, w_q, s_w, 0.05, b, row_block=12)
    # candidate filter mirrors the kernel's own eligibility
    assert at.qm_static_candidates(64, 16, 8) == [8, 16, 32]
    assert at.qm_static_candidates(8, 16, 8) == [8]


def test_quant_tuned_row_block_hook(tuner_cache, monkeypatch):
    """quant_dense CONSUMES the cache: with the flag on and a cached row
    block for its exact shape, the default-geometry call runs the tuned
    block (same arithmetic — asserted bit-identical to an explicit
    row_block call)."""
    from hydragnn_tpu.ops.quant_matmul import quant_dense, quantize_weight

    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    w_q, s_w = quantize_weight(jnp.asarray(rng.normal(size=(16, 8)), jnp.float32))
    at.record("quant_matmul", at.qm_signature(64, 16, 8), 32)
    monkeypatch.delenv("HYDRAGNN_OPS_AUTOTUNE", raising=False)
    assert at.tuned_quant_row_block(64, 16, 8) is None  # flag off
    monkeypatch.setenv("HYDRAGNN_OPS_AUTOTUNE", "1")
    assert at.tuned_quant_row_block(64, 16, 8) == 32
    tuned = quant_dense(x, w_q, s_w, 0.05, kernel=True, interpret=True)
    explicit = quant_dense(x, w_q, s_w, 0.05, kernel=True, interpret=True,
                           row_block=32)
    np.testing.assert_array_equal(np.asarray(tuned), np.asarray(explicit))
    # a cached block the shape's own rules reject is refused
    at.record("quant_matmul", at.qm_signature(64, 16, 8), 128)
    assert at.tuned_quant_row_block(64, 16, 8) is None
    at.record("quant_matmul", at.qm_signature(64, 16, 8), 12)
    assert at.tuned_quant_row_block(64, 16, 8) is None


def _cell_list_setup():
    rng = np.random.default_rng(13)
    pos = jnp.asarray(rng.uniform(0, 9.0, size=(48, 3)), jnp.float32)
    cell = jnp.asarray(np.eye(3) * 9.0, jnp.float32)
    pbc = jnp.asarray(np.ones(3, bool))
    from hydragnn_tpu.md import plan_cell_grid

    grid, cap = plan_cell_grid(np.asarray(cell), 2.5, 48)
    return pos, cell, pbc, grid, int(cap)


def test_cell_list_window_validation_and_hook(tuner_cache, monkeypatch):
    """Window-override validation + the flag-gated hook — pure host logic
    (the ValueError fires before any kernel builds; the slow twin below
    runs the interpret kernel for edge-set parity)."""
    from hydragnn_tpu.ops.fused_cell_list import (
        cell_window,
        fused_binned_radius_graph,
    )

    pos, cell, pbc, grid, cap = _cell_list_setup()
    with pytest.raises(ValueError, match="window"):
        fused_binned_radius_graph(pos, 2.5, 4000, cell, pbc, grid, cap,
                                  interpret=True, window=8)
    # hook: gated on the flag, refuses sub-minimum / non-aligned choices
    gx, gy, gz = (int(g) for g in grid)
    sig_args = (48, gx * gy * gz, cap)
    at.record("fused_cell_list", at.cl_signature(*sig_args),
              cell_window(cap) + 8)
    monkeypatch.delenv("HYDRAGNN_OPS_AUTOTUNE", raising=False)
    assert at.tuned_cell_list_window(*sig_args) is None
    monkeypatch.setenv("HYDRAGNN_OPS_AUTOTUNE", "1")
    assert at.tuned_cell_list_window(*sig_args) == cell_window(cap) + 8
    at.record("fused_cell_list", at.cl_signature(*sig_args), 8)
    assert at.tuned_cell_list_window(*sig_args) is None


@pytest.mark.slow
def test_cell_list_window_slack_preserves_edge_set(tuner_cache):
    """Window slack above the exact-membership minimum cannot change the
    edge SET (two full interpret-mode builds: slow-marked up front per the
    tier-1 budget)."""
    from hydragnn_tpu.ops.fused_cell_list import (
        cell_window,
        fused_binned_radius_graph,
    )

    pos, cell, pbc, grid, cap = _cell_list_setup()
    base = fused_binned_radius_graph(pos, 2.5, 4000, cell, pbc, grid, cap,
                                     interpret=True)
    wide = fused_binned_radius_graph(pos, 2.5, 4000, cell, pbc, grid, cap,
                                     interpret=True,
                                     window=cell_window(cap) + 8)

    def edge_set(out):
        s, r, _, m, _ = [np.asarray(a) for a in out]
        k = int(m.sum())
        return set(zip(s[:k].tolist(), r[:k].tolist()))

    assert edge_set(base) == edge_set(wide)


def test_softmax_axis_is_cert_pinned(tuner_cache):
    rec = at.autotune_softmax(512, 4)
    assert tuple(rec["geometry"]) == (256, 256)
    assert "cert rules" in rec["evidence"]["pinned_by"]
    # and the pin is cached like any other choice
    assert at.autotune_softmax(512, 4)["cache"] == "hit"


def test_disabled_compile_cache_is_memory_only(tmp_path, monkeypatch):
    monkeypatch.setenv("HYDRAGNN_COMPILE_CACHE", "0")
    at.reset_cache()
    assert at.cache_path() is None
    at.record("fused_scatter", "mem_sig", (512, 256))
    assert at.lookup("fused_scatter", "mem_sig")["geometry"] == [512, 256]
    at.reset_cache()  # no disk: the choice is gone with the process view
    assert at.lookup("fused_scatter", "mem_sig") is None
