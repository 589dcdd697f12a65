"""Device-resident supersteps: fold K train steps into ONE host dispatch.

The reference training loop (``hydragnn/train/train_validate_test.py:678-801``)
dispatches one program per batch from Python. On TPU that leaves the chip idle
between steps whenever host collate + dispatch latency exceeds step time —
exactly the regime small per-graph GNN steps live in (the r5 per-arch sweep
measured sub-10ms steps for GIN/SAGE/MFC). The canonical JAX fix: wrap the
per-batch train step in a ``lax.scan`` over a ``[K, ...]``-stacked block of
batches, carrying a donated ``TrainState``, so the host touches the device
once per K batches instead of once per batch.

Contracts (enforced by ``tests/test_superstep.py``):

* **Parity** — K scanned steps reproduce K individual ``train_step`` calls
  on the same batches: params/opt-state/metrics to a few fp32 ulp (``rtol``
  1e-6; the scan and the K dispatches are different XLA programs and are
  not bit-identical on this jax), the step counter and ``num_graphs``
  exactly; bf16 allclose. The scan body inlines the very same step
  function; nothing is reassociated across steps.
* **Fill skip** — an all-masked fill batch (``loop._empty_like``, used to pad
  the trailing partial block) contributes zero loss weight AND zero state
  change (bit for bit: a block of fill batches hands back its carry): the
  scan body select-skips the optimizer update when the step saw zero real
  graphs. Without the skip, AdamW's weight decay + EMA decay would
  drift params on zero-gradient steps and the trailing block would diverge
  from the K=1 path.
* **Compile boundedness** — one program per (bucket shape, K); the loader's
  bucket-major block scheduling (``GraphLoader.set_superstep``) guarantees
  every block is collated to a single pad bucket, so the program count stays
  bounded by the bucket table and ``HYDRAGNN_COMPILE_SENTINEL=strict`` holds.

Edge-sharded, pipeline and halo modes pin K=1 for now
(``StepPlan.stackable``): each places *one batch* with a transfer function
of its own (``put_large_batch`` / ``put_microbatches`` / ``put_halo_batch``)
whose sharding has no stacked ``[K, ...]`` equivalent yet.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from .step import donate_state_argnums


def resolve_steps_per_dispatch(training_cfg: dict) -> int:
    """The single resolver for K (shared by ``run_training``'s staging
    decisions and ``train_validate_test``'s dispatch routing, so the two
    can't drift): ``HYDRAGNN_SUPERSTEP`` overrides
    ``Training.steps_per_dispatch``; unset/0/1 disables. A placement whose
    batches have no stacked form pins K=1 through ``StepPlan.stackable``
    (``train/loop.py::plan_steps``), where the placements are known."""
    from ..utils import flags

    k = flags.get(
        flags.SUPERSTEP,
        default=int(training_cfg.get("steps_per_dispatch", 1) or 1),
    )
    return max(1, int(k))


_NO_CONSTRAINT = object()


def select_state(keep, new_state, old_state):
    """Branchless pytree select: ``new_state`` where the bool ``keep`` holds,
    else ``old_state`` — ONE fused compare+select inside the step program, no
    extra dispatch, no retrace. The shared skip primitive of the superstep's
    fill-batch skip, the resilience layer's non-finite step guard
    (``resilience/guard.py``), and the population layer's per-member
    divergence skip (``train/population.py``); all must revert EVERY leaf
    (params, batch stats, optimizer moments, step counter) or AdamW decay /
    the dropout rng fold drift on skipped steps.

    ``keep`` may be a scalar (whole-state skip) or a ``[N]`` member mask
    (population state, every leaf ``[N, ...]``): a non-scalar ``keep``
    broadcasts against each leaf's LEADING axes, so member ``i`` keeps or
    reverts independently. (A bare ``jnp.where`` would broadcast against the
    TRAILING axes and pair members with feature columns.)"""
    keep = jnp.asarray(keep)

    def sel(n, o):
        k = keep
        if keep.ndim and jnp.ndim(n) > keep.ndim:
            k = keep.reshape(keep.shape + (1,) * (jnp.ndim(n) - keep.ndim))
        return jnp.where(k, n, o)

    return jax.tree.map(sel, new_state, old_state)


def state_shardings(state):
    """Carry-sharding pins for ``make_superstep`` (mesh path): the input
    state's per-leaf ``NamedSharding``s. Without the pin, the partitioner is
    free to re-shard the scanned carry's outputs (e.g. tiny replicated params
    across the data axis) on the FIRST dispatch — the second dispatch then
    sees differently-sharded inputs and compiles a second program. With one
    dispatch per epoch (small epochs, large K) that second compile lands in
    epoch 1 and trips ``HYDRAGNN_COMPILE_SENTINEL=strict``. Non-array leaves
    (and uncommitted host arrays) pass through unconstrained."""
    from jax.sharding import NamedSharding

    def one(x):
        sh = getattr(x, "sharding", None)
        return sh if isinstance(sh, NamedSharding) else _NO_CONSTRAINT

    return jax.tree.map(one, state)


def make_superstep(
    train_step: Callable, k: int, donate_argnums=None, carry_shardings=None
) -> Callable:
    """Wrap a jitted ``(state, batch) -> (state, metrics)`` train step into a
    ``(state, block) -> (state, stacked_metrics)`` superstep that runs ``k``
    steps on-device per dispatch.

    ``block`` is the batch pytree with a leading ``[k, ...]`` axis (built by
    ``loop._blocked``); ``stacked_metrics`` carries a leading ``[k]`` axis and
    drops straight into the epoch loop's ``_accumulate``/backpressure
    machinery as one pytree per dispatch.

    The carry is donated on accelerators (same policy as the per-batch step:
    ``donate_state_argnums``), so K steps reuse one set of state buffers.
    ``carry_shardings`` (see :func:`state_shardings`) pins the carry-out
    layout to the carry-in layout so the jit cache stays single-entry.
    """
    k = int(k)
    if k <= 1:
        return train_step
    donate = donate_state_argnums() if donate_argnums is None else donate_argnums

    def body(carry, batch):
        new_state, metrics = train_step(carry, batch)
        # Fill-batch skip: a step that saw ZERO real graphs (an all-masked
        # _empty_like pad in the trailing partial block) must not touch the
        # state — optimizer decay/weight-decay on a zero gradient is not a
        # no-op, and the step counter drives the dropout rng fold. The
        # select keeps the whole block one static program.
        real = metrics["num_graphs"] > 0
        new_state = select_state(real, new_state, carry)
        return new_state, metrics

    @functools.partial(jax.jit, donate_argnums=donate)
    def superstep(state, block):
        state, metrics = jax.lax.scan(body, state, block, length=k)
        if carry_shardings is not None:
            state = jax.tree.map(
                lambda x, s: x if s is _NO_CONSTRAINT
                else jax.lax.with_sharding_constraint(x, s),
                state, carry_shardings,
            )
        return state, metrics

    return superstep


def double_buffer(iterable, depth: int = 2):
    """Run ``iterable`` (block staging: collate-stack + ``device_put``) in a
    worker thread ``depth`` items ahead of the consumer, so the next block's
    host work overlaps the current superstep's device execution.

    The per-batch path gets this overlap from ``PrefetchLoader``; blocks need
    it again because stacking K batches and placing the ``[K, ...]`` array
    happens *after* the prefetcher. Thin front for the shared
    ``graphs.batching.background_iter`` machinery (exception propagation,
    prompt worker shutdown when the consumer abandons the iterator).

    Each block's staging work (collate-stack + device_put, running in the
    worker thread) is bracketed in a ``stage_block`` tracer span, so the
    telemetry trace timeline shows staging overlapping superstep execution
    — or failing to, which is the bottleneck this buffer exists to hide.
    """
    from ..graphs.batching import background_iter
    from ..utils import tracer as tr

    _END = object()

    def _staged():
        it = iter(iterable)
        while True:
            tr.start("stage_block")
            try:
                block = next(it, _END)
            finally:
                tr.stop("stage_block")
            if block is _END:
                return
            yield block

    return background_iter(_staged(), depth=depth)


__all__ = ["make_superstep", "double_buffer", "select_state"]
