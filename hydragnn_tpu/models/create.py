"""Model factory: config dict -> HydraModel (reference ``models/create.py``).

The reference dispatches on ``mpnn_type`` across 13 stack classes, passing
string signatures of conv inputs for PyG Sequential (``create.py:112-766``).
Here each architecture registers a conv module in ``CONV_REGISTRY`` with one
uniform call contract, and the factory just builds the typed ``ModelSpec`` and
instantiates ``HydraModel`` (plus the MLIP wrapper when
``enable_interatomic_potential`` — reference ``create.py:590-758``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..config.schema import ModelSpec
from ..utils.print_utils import print_distributed
from .base import CONV_REGISTRY, HydraModel

# Importing architecture modules populates CONV_REGISTRY.
from . import gin  # noqa: F401

_IMPORT_ERRORS: dict[str, Exception] = {}
for _mod in (
    "sage", "gat", "mfc", "cgcnn", "pna", "pnaplus", "schnet",
    "dimenet", "egnn", "painn", "pnaeq", "mace",
):
    try:
        __import__(f"{__name__.rsplit('.', 1)[0]}.{_mod}")
    except ImportError as e:  # arch not built yet; factory errors on use
        _IMPORT_ERRORS[_mod] = e


def create_model_config(config: dict) -> HydraModel:
    """Build the model from an *augmented* config dict (after
    ``hydragnn_tpu.config.update_config``)."""
    spec = ModelSpec.from_config(config)
    describe = getattr(CONV_REGISTRY.get(spec.mpnn_type), "describe", None)
    if describe is not None:  # the stack's one-line record of what it builds
        print_distributed(config.get("Verbosity", {}).get("level", 0), describe(spec))
    return create_model(spec)


def create_model(spec: ModelSpec) -> HydraModel:
    if spec.mpnn_type not in CONV_REGISTRY:
        known = sorted(CONV_REGISTRY)
        hint = ""
        failed = _IMPORT_ERRORS.get(spec.mpnn_type.lower())
        if failed is not None:
            hint = (
                f" The '{spec.mpnn_type.lower()}' module exists but failed to "
                f"import: {failed!r}."
            )
        raise ValueError(
            f"Unknown or not-yet-registered mpnn_type '{spec.mpnn_type}'. "
            f"Registered: {known}.{hint}"
        )
    return HydraModel(spec=spec)


def init_model(model: HydraModel, example_batch, rng=None):
    """Initialize parameters + batch stats on an example batch."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    example_batch = jax.tree.map(jnp.asarray, example_batch)
    variables = model.init(rng, example_batch, train=False)
    return variables
