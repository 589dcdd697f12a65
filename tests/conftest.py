"""Test harness: force CPU with 8 virtual devices BEFORE jax backends init.

Mirrors the reference's CI strategy (oversubscribed `mpirun -n 2` ranks on one
machine, `.github/workflows/CI.yml:53-67`) the JAX way: a virtual 8-device CPU
platform lets every sharding/pjit test exercise real multi-device program
partitioning without TPU hardware. The program itself runs on the chip
(``chip_smoke.py``); the one TPU-facing test here is the compile-only
pre-flight ``tests/test_tpu_compile.py``.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

assert not jax._src.xla_bridge._backends, "jax backends initialized before conftest"

# Convergence gates pin the single-device optimization trajectory; grouping 8
# virtual devices per step cuts optimizer updates 8x for the same epochs
# (standard large-batch scaling). Tests opt into auto-parallel explicitly.
os.environ.setdefault("HYDRAGNN_AUTO_PARALLEL", "0")


def random_molecule_samples(n, seed=0, lo=9, hi=30):
    """Canonical random-radius-graph test samples (QM9-ish sizes), shared by
    the kernel/certificate test files."""
    import numpy as _np

    from hydragnn_tpu.graphs.graph import GraphSample
    from hydragnn_tpu.graphs.radius import radius_graph

    rng = _np.random.default_rng(seed)
    out = []
    for _ in range(n):
        na = int(rng.integers(lo, hi))
        pos = rng.uniform(0, 6.0, size=(na, 3))
        s, r, sh = radius_graph(pos, radius=3.0, max_neighbours=20)
        out.append(
            GraphSample(
                x=rng.normal(size=(na, 1)).astype(_np.float32),
                pos=pos, senders=s, receivers=r, edge_shifts=sh,
                graph_y=rng.normal(size=(1,)),
                node_y=rng.normal(size=(na, 1)),
            )
        )
    return out


# Recompile-sentinel fixture (hydragnn_tpu.analysis.sentinel): any test can
# `def test_x(compile_sentinel): ... with compile_sentinel(max_compiles=0): ...`
# to assert jit compile-count stability over a region.
from hydragnn_tpu.analysis.sentinel import compile_sentinel  # noqa: E402,F401

# Lock-order sanitizer fixtures (hydragnn_tpu.analysis.threadsan): `threadsan`
# instruments locks created inside one test and asserts the acquisition graph
# is cycle-free at teardown; `threadsan_module` is the module-scoped variant
# the serve/fleet/elastic suites ride (their servers live in module fixtures).
from hydragnn_tpu.analysis.threadsan import (  # noqa: E402,F401
    threadsan,
    threadsan_module,
)

import pytest  # noqa: E402


@pytest.fixture
def telemetry_isolate():
    """Scoped fresh-instance telemetry plane (telemetry.isolate): the
    process metrics registry, span buffer, tracer timers, cost ledger,
    journal, ambient context, and the enable/trace/propagate overrides are
    swapped for fresh state for the duration of the test and restored on
    exit — absolute-count assertions hold under any suite ordering without
    manual reset calls. Yields the telemetry package."""
    import hydragnn_tpu.telemetry as tel

    with tel.isolate():
        yield tel
