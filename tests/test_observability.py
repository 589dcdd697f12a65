"""Observability: tracer spans, TensorBoard, visualizer, walltime, HPO."""

import os

import numpy as np
import pytest

from hydragnn_tpu.postprocess.visualizer import Visualizer
from hydragnn_tpu.utils import tracer as tr
from hydragnn_tpu.utils.hpo import run_hpo, sample_config
from hydragnn_tpu.utils.walltime import _parse_slurm_time, make_walltime_check


def test_tracer_spans_and_save(tmp_path):
    tr.reset()
    with tr.span("train"):
        with tr.span("forward"):
            pass
    tr.start("opt_step"); tr.stop("opt_step")
    s = tr.summary()
    # "gc": a collection inside these lines, where an earlier test registered the hook
    assert set(s) - {"gc"} == {"train", "forward", "opt_step"}
    assert s["train"]["count"] == 1
    tr.save(str(tmp_path), prefix="timing")
    assert any(f.startswith("timing.p") for f in os.listdir(tmp_path))
    tr.reset()


def test_visualizer_writes_plots(tmp_path):
    rng = np.random.default_rng(0)
    t = [rng.normal(size=(50, 1))]
    p = [t[0] + 0.1 * rng.normal(size=(50, 1))]
    viz = Visualizer("vizrun", path=str(tmp_path))
    viz.add_history(0, train=1.0, val=1.1)
    viz.add_history(1, train=0.5, val=0.6)
    assert os.path.exists(viz.plot_history())
    assert os.path.exists(viz.create_parity_plot(t, p, names=["energy"]))
    assert os.path.exists(viz.create_error_histogram(t, p))


def test_walltime_parsing_and_check():
    assert _parse_slurm_time("1-02:03:04") == ((26 * 60) + 3) * 60 + 4
    assert _parse_slurm_time("15:30") == 930
    check = make_walltime_check()
    assert check() is False  # not under SLURM here


def test_hpo_random_search_finds_minimum():
    base = {"a": {"x": 0.0}, "b": 1}
    space = {"a.x": ("float", -2.0, 2.0), "b": [1, 2, 3]}
    rng_seen = []

    def objective(cfg):
        rng_seen.append(cfg)
        return (cfg["a"]["x"] - 1.0) ** 2 + cfg["b"]

    best_cfg, best_val, hist = run_hpo(base, space, objective, n_trials=40, seed=1)
    assert len(hist) == 40
    assert best_val < 1.3  # b=1 and x near 1
    assert best_cfg["b"] == 1


def test_hpo_over_training(tmp_path):
    """HPO drives real (tiny) trainings end-to-end."""
    import copy
    import hydragnn_tpu
    from hydragnn_tpu.datasets import deterministic_graph_data
    from test_config import CI_CONFIG

    samples = deterministic_graph_data(number_configurations=30, seed=51)
    base = copy.deepcopy(CI_CONFIG)
    base["NeuralNetwork"]["Training"]["num_epoch"] = 2

    def objective(cfg):
        state, model, aug = hydragnn_tpu.run_training(cfg, samples=list(samples))
        err, *_ = hydragnn_tpu.run_prediction(cfg, state, model, samples=list(samples))
        return err

    space = {"NeuralNetwork.Architecture.hidden_dim": [4, 8]}
    best_cfg, best_val, hist = run_hpo(base, space, objective, n_trials=2, seed=0)
    assert np.isfinite(best_val) and len(hist) == 2


def test_visualizer_extended_plots(tmp_path):
    """Vector parity, density parity, per-node error, size histogram
    (reference visualizer.py:387-519,734)."""
    import numpy as np

    from hydragnn_tpu.postprocess.visualizer import Visualizer

    rng = np.random.default_rng(0)
    viz = Visualizer("viz_ext", path=str(tmp_path))

    t_vec = rng.normal(size=(200, 3))
    p_vec = t_vec + 0.05 * rng.normal(size=(200, 3))
    out = viz.create_parity_plot_vector(t_vec, p_vec, name="forces",
                                        component_names=["fx", "fy", "fz"])
    assert out.endswith("parity_forces.png") and os.path.exists(out)

    t = rng.normal(size=500)
    p = t + 0.1 * rng.normal(size=500)
    assert os.path.exists(viz.create_density_parity_plot(t, p, name="energy"))

    counts = [5, 8, 12, 9, 6]
    tn = rng.normal(size=sum(counts))
    pn = tn + 0.1 * rng.normal(size=sum(counts))
    assert os.path.exists(viz.create_error_histogram_per_node(tn, pn, counts))

    class S:
        def __init__(self, n):
            self.num_nodes = n

    assert os.path.exists(viz.num_nodes_plot([S(n) for n in (4, 9, 9, 16)]))
    # reference-name alias
    assert os.path.exists(viz.create_scatter_plots([t], [p], ["energy"]))

    # global-analysis grid + per-size vector parity (visualizer.py:134,519,722)
    assert os.path.exists(viz.create_plot_global([t], [p], ["energy"]))
    assert os.path.exists(viz.create_plot_global_analysis([t], [p], ["energy"]))
    tv = rng.normal(size=(sum(counts), 3))
    pv = tv + 0.05 * rng.normal(size=(sum(counts), 3))
    assert os.path.exists(
        viz.create_parity_plot_per_node_vector(tv, pv, counts, name="forces")
    )


def test_unscale_features_by_num_nodes():
    """Extensive node targets scaled by 1/num_nodes are unscaled per sample
    (reference postprocess.py:29-54)."""
    import numpy as np

    from hydragnn_tpu.postprocess.postprocess import (
        unscale_features_by_num_nodes,
        unscale_features_by_num_nodes_config,
    )

    nodes = [2, 4]
    true = [[np.ones(2), np.ones(4)]]
    pred = [[np.full(2, 0.5), np.full(4, 0.5)]]
    t2, p2 = unscale_features_by_num_nodes([true, pred], [0], nodes)
    assert np.allclose(t2[0][0], 2.0) and np.allclose(t2[0][1], 4.0)
    assert np.allclose(p2[0][1], 2.0)

    cfg = {
        "NeuralNetwork": {
            "Variables_of_interest": {
                "output_names": ["energy_scaled_num_nodes"],
                "denormalize_output": True,
            }
        }
    }
    true = [[np.ones(2), np.ones(4)]]
    out = unscale_features_by_num_nodes_config(cfg, [true], nodes)
    assert np.allclose(out[0][0][1], 4.0)


def test_run_prediction_dump_testdata(tmp_path, monkeypatch):
    """HYDRAGNN_DUMP_TESTDATA=1 writes per-rank test pickles (reference
    train_validate_test.py:908)."""
    import copy
    import pickle

    import numpy as np

    import hydragnn_tpu
    from hydragnn_tpu.datasets import deterministic_graph_data
    from test_config import CI_CONFIG

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("HYDRAGNN_DUMP_TESTDATA", "1")
    cfg = copy.deepcopy(CI_CONFIG)
    cfg["NeuralNetwork"]["Training"]["num_epoch"] = 1
    samples = deterministic_graph_data(number_configurations=24, seed=3)
    state, model, aug = hydragnn_tpu.run_training(cfg, samples=samples)
    hydragnn_tpu.run_prediction(cfg, state, model, samples=samples)
    with open("testdata_rank0.pickle", "rb") as f:
        dump = pickle.load(f)
    assert len(dump["true"]) == len(dump["pred"]) >= 1
    assert np.asarray(dump["true"][0]).size > 0


def test_compile_cache_placement_rule(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is then set in code; unset,
    the cache is <checkout>/.jax_cache whatever the working directory; the
    HYDRAGNN_COMPILE_CACHE=0 off switch still switches it off."""
    import jax

    import hydragnn_tpu
    import hydragnn_tpu.utils.compile_cache as cc

    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.append((k, v))
    )
    monkeypatch.delenv("HYDRAGNN_COMPILE_CACHE", raising=False)

    # placed from outside: honoured, and the program sets no directory
    outside = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    monkeypatch.setattr(cc, "_enabled", False)
    assert cc.enable_compile_cache() == outside
    assert updates == [] and not os.path.exists(outside)
    assert cc.enable_compile_cache() == outside  # idempotent

    # not placed: the fixed path beside the package, from any cwd
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setattr(cc, "_enabled", False)
    checkout = os.path.dirname(os.path.dirname(hydragnn_tpu.__file__))
    want = os.path.join(checkout, ".jax_cache")
    assert cc.cache_dir() == want
    monkeypatch.chdir(tmp_path)
    assert cc.cache_dir() == want
    assert cc.enable_compile_cache() == want
    assert ("jax_compilation_cache_dir", want) in updates

    # off switch
    monkeypatch.setenv("HYDRAGNN_COMPILE_CACHE", "0")
    monkeypatch.setattr(cc, "_enabled", False)
    assert cc.cache_dir() is None and cc.enable_compile_cache() is None


def test_device_memory_summary_is_robust():
    from hydragnn_tpu.utils.print_utils import device_memory_summary

    s = device_memory_summary()
    assert isinstance(s, str) and s  # CPU backend: explanatory fallback text


def test_hpo_walltime_budget_stops_launching():
    """walltime_budget: once spent, no NEW trials launch; in-flight finish."""
    import time as _time

    calls = []

    def slow_objective(cfg):
        calls.append(1)
        _time.sleep(0.3)
        return float(cfg["x"])

    base = {"x": 0.0}
    space = {"x": ("float", 0.0, 1.0)}
    best, val, hist = run_hpo(
        base, space, slow_objective, n_trials=50, seed=2, walltime_budget=1.0
    )
    assert 1 <= len(calls) < 50
    assert len(hist) == len(calls)
    assert np.isfinite(val)


def test_subprocess_objective_crash_and_timeout_score_inf(tmp_path):
    from hydragnn_tpu.utils.hpo import subprocess_objective

    crash = tmp_path / "crash.py"
    crash.write_text("import sys; sys.exit(3)\n")
    obj = subprocess_objective(str(crash), timeout=30, keep_dir=str(tmp_path / "k"))
    assert obj({"a": 1}) == float("inf")

    slow = tmp_path / "slow.py"
    slow.write_text("import time; time.sleep(60)\n")
    obj2 = subprocess_objective(str(slow), timeout=1)
    assert obj2({"a": 1}) == float("inf")

    ok = tmp_path / "ok.py"
    ok.write_text(
        "import json, sys\n"
        "cfg = json.load(open(sys.argv[1]))\n"
        "json.dump({'objective': cfg['a'] * 2.0}, open(sys.argv[2], 'w'))\n"
    )
    obj3 = subprocess_objective(str(ok), timeout=30, keep_dir=str(tmp_path / "k2"))
    assert obj3({"a": 2}) == 4.0
    assert obj3({"a": 5}) == 10.0
    recs = sorted((tmp_path / "k2").glob("trial_*.json"))
    assert len(recs) == 2  # one record per trial of THIS evaluator

    # a trial that cannot open its accelerator is an error of the whole
    # search (on one chip every later trial would die the same way), not inf
    from hydragnn_tpu.utils.hpo import TrialBackendError, run_hpo

    nochip = tmp_path / "nochip.py"
    nochip.write_text(
        "import sys\n"
        "sys.exit(\"RuntimeError: Unable to initialize backend 'tpu': "
        "the TPU is already in use\")\n"
    )
    obj4 = subprocess_objective(str(nochip), timeout=30)
    with pytest.raises(TrialBackendError, match="Unable to initialize backend"):
        run_hpo({"a": 0}, {"a": [1, 2, 3]}, obj4, n_trials=3, seed=0)


def test_visualizer_scalar_parity_and_contour(tmp_path):
    """Reference create_parity_plot_and_error_histogram_scalar incl. the
    hist2d-contour form (visualizer.py:83-92,281-385)."""
    import os

    rng = np.random.default_rng(0)
    t = rng.normal(size=400)
    p = t + rng.normal(scale=0.1, size=400)
    viz = Visualizer("viz_scalar", path=str(tmp_path))
    out = viz.create_parity_plot_and_error_histogram_scalar("energy", t, p, iepoch=3)
    assert out and os.path.exists(out) and "energy_3" in out
    out2 = viz.create_parity_plot_and_error_histogram_scalar(
        "energy", t, p, contour=True
    )
    assert out2 and os.path.exists(out2)
    assert viz.create_parity_plot_and_error_histogram_scalar(
        "energy", t, p, save_plot=False
    ) is None
