"""hydragnn_tpu — a TPU-native multi-headed graph neural network framework.

A from-scratch JAX/XLA/Pallas rebuild of the capabilities of ORNL/HydraGNN
(multi-headed GNNs on atomistic data, 13 interchangeable message-passing
architectures, GPS global attention, energy-conserving interatomic potentials,
foundation-model multibranch training) designed for TPU hardware: statically
padded graph batches, segment-op message passing, pjit/shard_map SPMD over
device meshes, forces via jax.grad.

Top-level API mirrors the reference (``hydragnn/__init__.py:1-3``):
``run_training``, ``run_prediction`` plus subpackages.
"""

import os as _os


def _maybe_enable_threadsan() -> None:
    """HYDRAGNN_THREADSAN=1: instrument every lock the package creates from
    import time on (analysis/threadsan.py) — whole-process lock-order
    sanitizing for chaos/soak runs; tests use the ``threadsan`` fixture."""
    if _os.environ.get("HYDRAGNN_THREADSAN", "") not in ("", "0"):
        from .analysis import threadsan

        threadsan.maybe_enable_from_env()


_maybe_enable_threadsan()

from . import graphs  # noqa: F401,E402

__version__ = "0.1.0"


# Eager function imports LAST: any later `import hydragnn_tpu.run_training`
# rebinds the package attribute to the submodule, so modules of the same name
# must be imported before the functions shadow them (reference exports the
# same two symbols, hydragnn/__init__.py:1-3).
from . import run_prediction as _run_prediction_module  # noqa: E402
from . import run_training as _run_training_module  # noqa: E402
from .run_prediction import run_prediction  # noqa: E402,F811
from .run_training import run_training  # noqa: E402,F811

__all__ = ["run_training", "run_prediction", "graphs", "__version__"]
