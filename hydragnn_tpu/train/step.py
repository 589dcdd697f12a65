"""Jitted train/eval steps.

The hot loop of reference ``hydragnn/train/train_validate_test.py:629-801``
(forward under autocast -> loss -> backward -> all-reduce -> opt step) becomes
ONE compiled XLA program per step: forward, loss, grad, optimizer update, and
(on a mesh) gradient/metric all-reduce all fuse into a single executable —
there is no separate "backward hook bucket all-reduce" plane like DDP's.

Precision policy (reference ``resolve_precision``/``get_autocast_and_scaler``,
``train_validate_test.py:43-109``): parameters stay fp32 (master copy), compute
runs in the requested dtype (bf16 on TPU's MXU), losses/metrics accumulate in
fp32. No GradScaler — bf16 has fp32's exponent range.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax

from ..graphs.graph import GraphBatch
from ..models.base import HydraModel
from ..models.common import SYNC_BN_AXIS
from ..models.mlip import energy_force_loss, make_graph_energy_fn, validate_mlip_spec

PRECISION_MAP = {
    "fp32": jnp.float32,
    "float32": jnp.float32,
    "fp64": jnp.float64,
    "float64": jnp.float64,
    "bf16": jnp.bfloat16,
    "bfloat16": jnp.bfloat16,
    "fp16": jnp.float16,
    "float16": jnp.float16,
}

# Every value Training.precision may take (config/schema.py validates against
# THIS set at load time, so a typo fails before any compile). "auto" is the
# backend-resolved fast path: bf16 compute (fp32 master weights) on TPU —
# the MXU's native reduced-precision format — and fp32 everywhere else, so
# CPU CI keeps its bit-exact parity gates while TPU runs get the fast path
# without a per-deployment config edit.
KNOWN_PRECISIONS = frozenset(PRECISION_MAP) | {"auto"}


def resolve_precision(name: str):
    if name == "auto":
        return jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    try:
        return PRECISION_MAP[name]
    except KeyError:
        raise ValueError(
            f"Unknown precision '{name}'; one of {sorted(KNOWN_PRECISIONS)}"
        )


def resolve_training_precision(training_cfg: dict):
    """The single env-aware resolver for the training stack's compute dtype:
    ``HYDRAGNN_PRECISION`` > ``Training.precision`` > fp32. Every consumer
    that builds step programs from a Training config (the epoch loop, the
    population engine, the non-finite guard's auto-arming) must resolve
    through HERE, so the env override changes all of them coherently — a
    flag that switched the step to bf16 but left the guard disarmed would
    silently drop the divergence protection the bf16 path is documented to
    carry."""
    from ..utils import flags

    name = flags.get(
        flags.PRECISION,
        default=str(training_cfg.get("precision", "fp32") or "fp32"),
    )
    return resolve_precision(str(name))


def resolve_loss_scale(training_cfg: dict) -> float | None:
    """Static loss scale for fp16-class compute: ``Training.loss_scale``
    (0/1/unset disables). Returns None when disabled so step builders can
    keep the historical (byte-identical) program on the default path."""
    scale = float(training_cfg.get("loss_scale", 0) or 0)
    return scale if scale not in (0.0, 1.0) else None


class TrainState(NamedTuple):
    params: Any
    batch_stats: Any
    opt_state: Any
    step: jax.Array


_FROZEN_PREFIXES = ("graph_convs_", "feature_norm_")


def freeze_conv_grads(grads, spec) -> Any:
    """``freeze_conv_layers``: zero gradients for the conv stack + feature
    norms (the reference's ``requires_grad=False`` over ``graph_convs`` and
    ``feature_layers``, ``Base.py:495-500``); heads keep training."""
    if not getattr(spec, "freeze_conv_layers", False):
        return grads
    return {
        k: (jax.tree.map(jnp.zeros_like, v) if k.startswith(_FROZEN_PREFIXES) else v)
        for k, v in grads.items()
    }


def apply_initial_bias(params, spec):
    """``initial_bias``: fill the last linear layer's bias of every
    graph-type head (reference ``_set_bias``, ``Base.py:502-507`` — UQ
    initialization for ensemble heads)."""
    if getattr(spec, "initial_bias", None) is None:
        return params
    bias = float(spec.initial_bias)
    for ihead, otype in enumerate(spec.output_type):
        if otype != "graph":
            continue
        for key in params:
            if not key.startswith(f"head{ihead}_"):
                continue
            dense_keys = sorted(
                (k for k in params[key] if k.startswith("dense_")),
                key=lambda k: int(k.split("_")[-1]),
            )
            if dense_keys and "bias" in params[key][dense_keys[-1]]:
                leaf = params[key][dense_keys[-1]]["bias"]
                params[key][dense_keys[-1]]["bias"] = jnp.full_like(leaf, bias)
    return params


def create_train_state(model: HydraModel, optimizer, example_batch, rng=None) -> TrainState:
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    example_batch = jax.tree.map(jnp.asarray, example_batch)
    variables = model.init(rng, example_batch, train=False)
    params = apply_initial_bias(variables["params"], model.spec)
    batch_stats = variables.get("batch_stats", {})
    opt_state = optimizer.init(params)
    return TrainState(
        params=params,
        batch_stats=batch_stats,
        opt_state=opt_state,
        step=jnp.zeros((), jnp.int32),
    )


def _cast_floats(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree,
    )


def donate_state_argnums() -> tuple:
    """Donate the incoming TrainState's buffers to the step on accelerators
    (halves peak HBM for params + optimizer state). CPU keeps no-donation so
    tests can inspect pre-step state."""
    try:
        return (0,) if jax.default_backend() == "tpu" else ()
    except Exception:
        return ()


def _static_scale(loss_scale) -> float | None:
    """``loss_scale`` as a builder sees it: None where it is off (unset, 0
    or 1), so the default path traces no scaling operation at all."""
    return None if not loss_scale or float(loss_scale) == 1.0 else float(loss_scale)


def scaled_value_and_grad(loss_fn, loss_scale=None):
    """``jax.value_and_grad`` of ``loss_fn(params, ...) -> (loss, aux)`` with
    static loss scaling: the SCALED loss is differentiated (small gradients
    survive fp16's 5-bit exponent through the backward pass) and the
    unscaled one rides out through aux, so metrics never see the scale.
    Returns ``((loss, aux), grads)``; the gradients still carry the scale,
    which :func:`apply_gradients` divides back out after its fp32 cast.
    An MLIP objective's INNER position gradient is not touched: forces feed
    the loss itself and stay in physical units."""
    scale = _static_scale(loss_scale)

    def scaled(*args):
        loss, aux = loss_fn(*args)
        return (loss if scale is None else loss * scale), (loss, aux)

    grad_fn = jax.value_and_grad(scaled, has_aux=True)

    def run(*args):
        (_, out), grads = grad_fn(*args)
        return out, grads

    return run


def apply_gradients(state: TrainState, grads, new_stats, optimizer, spec,
                    loss_scale=None) -> TrainState:
    """The end of EVERY training step, whatever placed it: gradients -> fp32
    -> un-scale -> ``freeze_conv_grads`` -> optimizer -> new ``TrainState``.
    The un-scale comes AFTER the fp32 cast: the scaled backward kept tiny
    values above fp16's underflow, and fp32 has the range to divide back
    exactly (prefer 2^k scales). The ``optimizer`` scope names this device
    work in a profile, where flax's module scopes do not reach."""
    scale = _static_scale(loss_scale)
    with jax.named_scope("optimizer"):
        grads = _cast_floats(grads, jnp.float32)
        if scale is not None:
            grads = jax.tree.map(lambda g: g / scale, grads)
        grads = freeze_conv_grads(grads, spec)
        updates, new_opt_state = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
    return TrainState(
        params=new_params,
        batch_stats=new_stats,
        opt_state=new_opt_state,
        step=state.step + 1,
    )


def dropout_rng(state: TrainState):
    """The step's dropout key: a fold of the step counter, so a skipped or
    resumed step draws the mask the uninterrupted run drew."""
    return jax.random.fold_in(jax.random.PRNGKey(0), state.step)


# -- the two objectives -------------------------------------------------------
# Each is a plain function of ONE batch,
#   (cast params, batch_stats, cast batch, raw batch, dropout rng
#    [, traced task weights]) -> (total, stacked task losses, new batch_stats),
# that a placement maps over its batches: the forward runs in the compute
# dtype, the losses read the raw (fp32) targets.


def model_objective(model: HydraModel):
    """``model.apply(train=True)`` -> ``model.loss``. ``task_weights=None``
    is the static path (``spec.task_weights`` baked into ``model.loss``); a
    traced ``[n_tasks]`` vector re-weights the SAME per-task losses in the
    SAME accumulation order, which is what the population layer's per-member
    loss weights ride."""

    def objective(c_params, batch_stats, c_batch, batch, rng, task_weights=None):
        outputs, updates = model.apply(
            {"params": c_params, "batch_stats": batch_stats},
            c_batch,
            train=True,
            mutable=["batch_stats"],
            rngs={"dropout": rng},
        )
        pred = _cast_floats(outputs, jnp.float32)
        tot, tasks = model.loss(pred, batch)
        if task_weights is not None:
            # the statically-weighted `tot` above is dead code XLA eliminates
            tot = 0.0
            for ihead, task_loss in enumerate(tasks):
                tot = tot + task_loss * task_weights[ihead]
        return tot, jnp.stack(tasks), updates["batch_stats"]

    return objective


def energy_force_objective(model: HydraModel):
    """Energy, energy per atom and forces (``models/mlip.py``): a train-mode
    forward inside a position gradient, so the parameter gradient of this
    objective is grad-of-grad. The SAME dropout mask serves the energy and
    its position gradient."""
    spec = model.spec
    validate_mlip_spec(spec)
    graph_energy = make_graph_energy_fn(model)

    def objective(c_params, batch_stats, c_batch, batch, rng):
        variables = {"params": c_params, "batch_stats": batch_stats}

        def total_energy(pos):
            graph_e, new_stats = graph_energy(
                variables, pos, c_batch, train=True, rngs={"dropout": rng}
            )
            graph_e = graph_e.astype(jnp.float32)
            return graph_e.sum(), (graph_e, new_stats)

        (_, (graph_e, new_stats)), grad_pos = jax.value_and_grad(
            total_energy, has_aux=True
        )(c_batch.pos)
        forces = (-grad_pos * batch.node_mask[:, None]).astype(jnp.float32)
        with jax.named_scope("mlip_loss"):
            tot, tasks = energy_force_loss(spec, graph_e, forces, batch)
        return tot, jnp.stack(tasks), new_stats

    return objective


def step_objective(model: HydraModel):
    """The objective a configuration trains: energy and forces where the
    spec enables interatomic potentials, the model's own loss otherwise."""
    if model.spec.enable_interatomic_potential:
        return energy_force_objective(model)
    return model_objective(model)


def on_one_device(objective, spec):
    """Bind the SyncBatchNorm axis as a size-1 ``vmap`` round ``objective``:
    a ``pmean`` over it is the identity, so SyncBatchNorm configurations run
    unchanged on one device (the reference's ``convert_sync_batchnorm`` is
    likewise a no-op at world size 1). Other specs get ``objective`` back."""
    if not spec.sync_batch_norm:
        return objective

    def bound(c_params, batch_stats, c_batch, batch, rng, *task_weights):
        def one(cb, b, r):
            return objective(c_params, batch_stats, cb, b, r, *task_weights)

        lead = lambda tree: jax.tree.map(lambda x: x[None], tree)  # noqa: E731
        out = jax.vmap(one, axis_name=SYNC_BN_AXIS)(lead(c_batch), lead(batch), rng[None])
        return jax.tree.map(lambda x: x[0], out)

    return bound


def single_device_step(model: HydraModel, optimizer, objective, compute_dtype, loss_scale):
    """The single-device step body (unjitted) behind :func:`make_train_step`,
    :func:`make_weighted_train_step` and ``models.mlip.make_mlip_train_step``:
    ``(state, batch, *task_weights) -> (state, metrics)``."""
    objective = on_one_device(objective, model.spec)

    def loss_fn(params, batch_stats, batch: GraphBatch, rng, *task_weights):
        c_params = _cast_floats(params, compute_dtype)
        c_batch = _cast_floats(batch, compute_dtype)
        tot, tasks, new_stats = objective(
            c_params, batch_stats, c_batch, batch, rng, *task_weights
        )
        return tot, (tasks, new_stats)

    grad_fn = scaled_value_and_grad(loss_fn, loss_scale)

    def step(state: TrainState, batch: GraphBatch, *task_weights):
        (tot, (tasks, new_stats)), grads = grad_fn(
            state.params, state.batch_stats, batch, dropout_rng(state), *task_weights
        )
        new_state = apply_gradients(
            state, grads, new_stats, optimizer, model.spec, loss_scale
        )
        metrics = {
            "loss": tot,
            "tasks_loss": tasks,
            "num_graphs": batch.graph_mask.sum(),
        }
        return new_state, metrics

    return step


def make_train_step(model: HydraModel, optimizer, compute_dtype=jnp.float32,
                    loss_scale=None):
    """Build the jitted single-device train step:
    (state, batch) -> (state, metrics dict).

    ``loss_scale`` (static, baked at build time; None/0/1 traces no scaling
    at all): fp16-class static scaling, see :func:`scaled_value_and_grad`.
    bf16 shares fp32's exponent range and never needs it; metrics always
    report the UNSCALED loss."""
    step = single_device_step(
        model, optimizer, model_objective(model), compute_dtype, loss_scale
    )

    @functools.partial(jax.jit, donate_argnums=donate_state_argnums())
    def train_step(state: TrainState, batch: GraphBatch):
        return step(state, batch)

    return train_step


def make_weighted_train_step(model: HydraModel, optimizer, compute_dtype=jnp.float32,
                             loss_scale=None):
    """Like :func:`make_train_step` but with TRACED task weights:
    ``(state, batch, task_weights[n_tasks]) -> (state, metrics)``.

    The weights ride the program as data, not constants, so N differently
    weighted trainings share one executable — the population layer vmaps this
    step with a per-member ``[N, n_tasks]`` weight stack (HPO over loss
    weights / heteroscedastic ensembles) without N recompiles. Callers pass
    weights normalized the way ``ModelSpec`` normalizes ``task_weights``
    (w / sum|w|) if they want parity with a statically-weighted run."""
    step = single_device_step(
        model, optimizer, model_objective(model), compute_dtype, loss_scale
    )

    @functools.partial(jax.jit, donate_argnums=donate_state_argnums())
    def train_step(state: TrainState, batch: GraphBatch, task_weights):
        return step(state, batch, task_weights)

    return train_step


def make_eval_step(model: HydraModel, compute_dtype=jnp.float32):
    """(state, batch) -> metrics with per-head RMSE; no stat updates."""

    @jax.jit
    def eval_step(state: TrainState, batch: GraphBatch):
        c_params = _cast_floats(state.params, compute_dtype)
        c_batch = _cast_floats(batch, compute_dtype)
        outputs = model.apply(
            {"params": c_params, "batch_stats": state.batch_stats},
            c_batch,
            train=False,
        )
        pred = _cast_floats(outputs, jnp.float32)
        tot, tasks = model.loss(pred, batch)
        sses, counts = model.head_sse(pred, batch)
        return {
            "loss": tot,
            "tasks_loss": jnp.stack(tasks),
            "head_sse": jnp.stack(sses),
            "head_count": jnp.stack(counts),
            "num_graphs": batch.graph_mask.sum(),
        }

    return eval_step


def make_predict_step(model: HydraModel, compute_dtype=jnp.float32,
                      donate_batch: bool = False):
    """(state, batch) -> per-head predictions (host gathers across batches).

    ``donate_batch``: donate the batch buffers to the step — the serving
    tier's steady-state executor consumes each micro-batch exactly once, so
    its device buffers can be reused in place (accelerators only; CPU keeps
    no-donation like ``donate_state_argnums`` so tests can inspect inputs).
    """
    donated: tuple = ()
    if donate_batch:
        try:
            donated = (1,) if jax.default_backend() == "tpu" else ()
        except Exception:
            donated = ()

    @functools.partial(jax.jit, donate_argnums=donated)
    def predict_step(state: TrainState, batch: GraphBatch):
        c_params = _cast_floats(state.params, compute_dtype)
        c_batch = _cast_floats(batch, compute_dtype)
        outputs = model.apply(
            {"params": c_params, "batch_stats": state.batch_stats},
            c_batch,
            train=False,
        )
        return _cast_floats(outputs, jnp.float32)

    return predict_step
