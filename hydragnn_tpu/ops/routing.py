"""Where a fused Pallas kernel may run: the static rule every wrapper in
``ops/`` shares.

A kernel call is placed on Mosaic or on its XLA reference at TRACE time,
from what the code can see — the backend, the dtype and shapes (each
kernel's ``*_route`` function, next to its geometry constants) and the
surrounding program (:func:`xla_only`). A route is ``None`` ("run the
kernel") or the reason the call takes the XLA path; ``chip_smoke.py`` prints
them as the routing table and checks the compiled programs against it.

Nothing here, or anywhere in ``ops/``, wraps a compile in ``try/except``: a
call the rule admits must compile, and ``tests/test_tpu_compile.py`` holds
every default-on kernel to that against a compile-only v5e device.
"""

from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.numpy as jnp

_xla_only: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "hydragnn_ops_xla_only", default=None
)

# a sublane tile is 8 rows of 128 lanes of 32 bits; a narrower minor dim
# still occupies full lanes in VMEM
LANES = 128

# dtypes the kernels are built and compile-tested for
KERNEL_DTYPES = ("float32", "bfloat16")


def default_on(flag) -> bool:
    """A kernel's A/B flag (``HYDRAGNN_FUSED_*``) when set, else on exactly
    when the program is being built for a TPU."""
    from ..utils import flags

    forced = flags.get(flag)
    return forced if forced is not None else jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """Off the TPU a kernel can only run in the Pallas interpreter."""
    return jax.default_backend() != "tpu"


def saved(residuals):
    """What a custom-VJP ``fwd`` rule saves for its ``bwd``: every leaf of
    ``residuals`` copied (``x + 0``, which XLA drops again: the cells'
    optimized programs are the ones they were, PERF.md section 6).

    Why. jax 0.9.0 notices a residual that IS one of ``fwd``'s inputs and
    forwards that input by its index (``custom_derivatives._flatten_fwd``);
    where the call sits in a jaxpr with closed-over constants (a ``lax.scan``
    body under a second differentiation: the kernels' rules here call each
    other, so an energy-and-force step of a scanned stack,
    ``models/layer_scan.py``, takes that path) the index is counted without
    the constants and read with them, and ``bwd`` is handed ANOTHER operand in
    the residual's place: a shape or dtype error where they differ, a silent
    wrong gradient where they do not. A copy is no input, so nothing is
    forwarded. ``tests/test_layer_scan.py`` pins the fault with a pure-jax
    repro and fails the day jax mends it: save the inputs themselves then."""
    return jax.tree.map(
        lambda x: jnp.logical_or(x, False) if x.dtype == jnp.bool_ else x + jnp.zeros((), x.dtype),
        residuals)


@contextlib.contextmanager
def xla_only(reason: str):
    """Trace the enclosed code with every fused kernel on its XLA path.

    For programs a Mosaic call cannot be placed in. The one user today is
    the mesh step (``parallel/step.py``): it ``vmap``s the per-device body
    over the stacked ``[D, ...]`` batch and lets GSPMD split that axis, and
    GSPMD cannot partition a Mosaic custom call ("wrap the call in a
    shard_map"). Enter it INSIDE the jitted function, where tracing
    happens."""
    token = _xla_only.set(reason)
    try:
        yield
    finally:
        _xla_only.reset(token)


def preflight(dtype=None) -> str | None:
    """The part of every kernel's route that does not depend on its
    geometry: the enclosing :func:`xla_only` reason, else a dtype the
    kernels are not built for (``dtype=None``: the kernel has no dtype
    axis)."""
    reason = _xla_only.get()
    if reason is None and dtype is not None:
        name = jnp.dtype(dtype).name
        if name not in KERNEL_DTYPES:
            reason = f"dtype {name}"
    return reason


def lane_padded(width: int) -> int:
    """Minor-dim elements a row really occupies in VMEM."""
    return -(-int(width) // LANES) * LANES


def over_budget(what: str, nbytes: int, limit: int) -> str | None:
    """Route reason when ``nbytes`` of resident VMEM exceed ``limit``."""
    if nbytes <= limit:
        return None
    return f"{what} {nbytes >> 20} MiB > {limit >> 20} MiB VMEM budget"


def describe(route: str | None) -> str:
    """Routing-table cell for a route."""
    return "mosaic" if route is None else f"xla: {route}"
