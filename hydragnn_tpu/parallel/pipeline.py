"""Pipeline parallelism: GPipe-style microbatch pipelining of the conv stack
over a ``stage`` mesh axis.

The reference has no pipeline parallelism anywhere (SURVEY §2.5: TP/PP
absent); this is a TPU-native extension for DEEP stacks (many-layer
equivariant models) whose weights or activations outgrow one chip but whose
layer widths don't warrant tensor sharding.

Design
------
* Conv block 0 (the one non-uniform layer — it lifts ``input_dim`` to
  ``hidden_dim``) and the decode epilogue (pooling + heads) run replicated
  on every stage device; they are a tiny fraction of a deep stack's FLOPs.
* Conv blocks ``1..L-1`` must be parameter-homogeneous (same pytree of
  shapes, true for every registered stack at fixed hidden_dim). Their
  params are stacked to a ``[S, k, ...]`` pytree, sharded over the stage
  axis — each device materializes only its ``k = (L-1)/S`` layers.
* One ``shard_map`` program runs the classic GPipe schedule: ``T = M+S-1``
  ticks; at tick ``t`` stage ``s`` applies its ``k`` blocks (inner
  ``lax.scan`` over stacked layer params, each step re-applying the model's
  ``conv_block`` method with that layer's params substituted in) to
  microbatch ``t - s``, then hands the activation to stage ``s+1`` with a
  ``ppermute`` rotation around the ring. Stage 0 feeds fresh microbatch
  activations into the ring; the last stage's outputs are ``psum``-broadcast
  (every other stage contributes zeros).
* Autodiff goes straight through ``scan``+``ppermute`` — the backward pass
  is the reverse pipeline schedule, derived by AD instead of hand-scheduled.

Semantics: pipelined execution is deterministic — conv dropout is disabled
(GAT with ``dropout > 0`` is rejected up front rather than silently
differing from the data-parallel path). Feature-norm statistics are
selectable via ``norm``:

* ``"batch"`` (default): each conv block normalizes with the CURRENT
  microbatch's statistics — the data-parallel train step's semantics, and
  the only stable choice for deep stacks (a 9-layer GIN on init running
  stats blows activations up ~degree^L, producing astronomically large but
  "finite" losses — the round-2 dryrun's loss=7.2e7). The TRAIN step also
  accumulates running stats (one EMA step per microbatch, averaged — the
  data-parallel step's replica-mean semantics), so a pipelined checkpoint
  later evaluates/fine-tunes on the data-parallel path from real statistics
  rather than init values.
* ``"running"``: eval-mode running averages — bit-exact parity with the
  sequential ``encode(train=False)`` path (what the exact-parity tests pin).

Resilience pass-through: the pipelined train step keeps the generic
``(state, batch) -> (state, metrics)`` contract, so the non-finite step
guard (``resilience/guard.py``) wraps it unchanged in the epoch loop — a
NaN in any microbatch reaches the accumulated loss and the stage-replicated
update is select-skipped in the same dispatch. Divergence rollback and
preemption checkpointing live at the loop/checkpoint layer and need nothing
stage-aware; only supersteps stay pinned at K=1 (``put_microbatches`` is a
per-step placement with no stacked [K, ...] form yet).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..graphs.graph import GraphBatch
from ..models import layer_scan
from ..models.base import CONV_REGISTRY, HydraModel
from ..train.step import (
    TrainState,
    _cast_floats,
    apply_gradients,
    donate_state_argnums,
    scaled_value_and_grad,
)

STAGE_AXIS = "stage"


def make_pipeline_mesh(n_stage: int, devices=None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())[:n_stage]
    if len(devices) != n_stage:
        raise ValueError(f"need {n_stage} devices for {n_stage} stages")
    return Mesh(np.asarray(devices), (STAGE_AXIS,))


def validate_pipeline_support(model: HydraModel, n_stage: int) -> int:
    """Return layers-per-stage k; raise for unsupported configurations."""
    spec = model.spec
    L = spec.num_conv_layers
    if spec.global_attn_engine:
        raise ValueError("pipeline parallelism does not compose with global "
                         "attention engines yet")
    conv_cls = CONV_REGISTRY[spec.mpnn_type]
    if getattr(conv_cls, "collect_layer_outputs", False):
        raise ValueError(f"{spec.mpnn_type} reads every layer's output "
                         "(collect_layer_outputs) — not pipelineable")
    if spec.mpnn_type == "GAT" and spec.dropout > 0:
        raise ValueError(
            "pipelined execution is dropout-free (conv blocks run "
            "deterministically); set Architecture.dropout to 0 for GAT "
            "under pipeline parallelism"
        )
    if L < n_stage + 1:
        raise ValueError(f"{L} conv layers cannot fill {n_stage} stages "
                         "(block 0 is the prologue; need num_conv_layers >= "
                         "n_stage + 1)")
    if (L - 1) % n_stage:
        raise ValueError(f"{L - 1} pipelined layers not divisible by "
                         f"{n_stage} stages")
    return (L - 1) // n_stage


def make_pipelined_forward(
    model: HydraModel, mesh: Mesh, n_micro: int, norm: str = "batch",
    collect_stats: bool = False,
):
    """Build ``fn(variables, microbatches) -> (inv, equiv)`` where
    ``microbatches`` is a GraphBatch stacked to ``[M, ...]`` (see
    ``parallel.stack_device_batches``) and the result carries the encoded
    node features per microbatch ``[M, N, H]``. ``norm``: see module
    docstring ("batch" = per-microbatch statistics, "running" = frozen
    running averages).

    ``collect_stats=True`` (requires ``norm="batch"``) returns
    ``(inv, equiv, new_batch_stats)``: each feature norm's running stats
    after one EMA step per microbatch (from the same old stats), averaged
    over microbatches — identical semantics to the data-parallel step's
    replica-mean stat update. Prologue stats come off the vmapped block-0
    pass; blocks 1..L-1 accumulate valid-tick stats on each stage and leave
    the ring stacked ``[L-1, ...]`` over the stage axis."""
    S = mesh.shape[STAGE_AXIS]
    k = validate_pipeline_support(model, S)
    L = model.spec.num_conv_layers
    M = n_micro
    if norm not in ("batch", "running"):
        raise ValueError(f"norm must be 'batch' or 'running', got {norm!r}")
    if collect_stats and norm != "batch":
        raise ValueError("collect_stats requires norm='batch' (running-stat "
                         "EMA steps are computed from per-microbatch stats)")
    use_batch_stats = norm == "batch"

    def forward(variables, mb: GraphBatch):
        got = jax.tree.leaves(mb)[0].shape[0]
        if got != M:
            raise ValueError(
                f"stacked microbatch has leading dim {got}, expected "
                f"n_micro={M}"
            )
        params = variables["params"]
        stats = variables.get("batch_stats", {})
        collect_ring = collect_stats and "feature_norm_1" in stats

        # prologue: embed + block 0, vmapped over microbatches (replicated)
        def prologue(b):
            if use_batch_stats:
                out, upd = model.apply(variables, b, True,
                                       method=HydraModel.embed_block0,
                                       mutable=["batch_stats"])
                return out, upd.get("batch_stats", {})
            return model.apply(variables, b, False,
                               method=HydraModel.embed_block0), {}

        (inv0, equiv0), pro_upd = jax.vmap(prologue)(mb)
        if not isinstance(equiv0, jax.Array):
            # block 0 handed on what it made of the positions (SchNet's edge
            # basis), a pytree of ``[E, .]`` arrays: the ring carries the
            # positions themselves and every layer of it makes its own
            equiv0 = mb.pos

        # blocks 1..L-1 stacked and the scanned body: ``models/layer_scan.py``,
        # shared with the single-device scan (``Training.scan_conv_layers``)
        stacked = jax.tree.map(lambda x: x.reshape(S, k, *x.shape[1:]),
                               layer_scan.stack_layers(params, stats, 1, L))

        def apply_block(p_tree, inv, equiv, b):
            """``conv_block(1)`` with this layer's params substituted.
            Returns the block output and (when normalizing by batch stats)
            the layer's EMA-stepped ``feature_norm_1`` stats subtree."""
            out, upd = layer_scan.apply_block(
                model, params, stats, 1, p_tree, inv, equiv, b,
                use_batch_stats, use_batch_stats)
            return out, upd.get("norm_s", {})

        def stage_fn(my_params, inv0, equiv0, mb):
            my_params = jax.tree.map(lambda x: x[0], my_params)  # [k, ...]
            sidx = jax.lax.axis_index(STAGE_AXIS)
            T = M + S - 1
            perm = [(i, (i + 1) % S) for i in range(S)]
            acc0 = (
                jax.tree.map(jnp.zeros_like, my_params["norm_s"])
                if collect_ring else None
            )

            def tick(carry, t):
                inv_c, equiv_c, acc = carry
                m = jnp.clip(t - sidx, 0, M - 1)
                b = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, m, 0, False), mb
                )
                fresh_inv = jax.lax.dynamic_index_in_dim(inv0, m, 0, False)
                fresh_equiv = jax.lax.dynamic_index_in_dim(equiv0, m, 0, False)
                inv_in = jnp.where(sidx == 0, fresh_inv, inv_c)
                equiv_in = jnp.where(sidx == 0, fresh_equiv, equiv_c)

                def lay(c, p):
                    (inv, equiv), upd = apply_block(p, c[0], c[1], b)
                    # positions stay where the layer returns its edge basis
                    return (inv, equiv if isinstance(equiv, jax.Array) else c[1]), upd

                (inv_out, equiv_out), upds = jax.lax.scan(
                    lay, (inv_in, equiv_in), my_params
                )
                if acc is not None:
                    # bubble ticks recompute a clipped microbatch on a junk
                    # ring carry — where-select (not multiply) keeps any
                    # non-finite garbage out of the accumulator
                    valid = (t >= sidx) & (t - sidx < M)
                    acc = jax.tree.map(
                        lambda a, u: a + jnp.where(valid, u, 0), acc, upds
                    )
                send = jax.tree.map(
                    lambda a: jax.lax.ppermute(a, STAGE_AXIS, perm),
                    (inv_out, equiv_out),
                )
                # only the last stage's result is the stack output; psum
                # broadcasts it. where-select (not multiply-mask) so a
                # non-finite value from a bubble-tick zero carry can never
                # leak through as 0*inf=NaN
                is_last = sidx == S - 1
                y = jax.lax.psum(
                    (jnp.where(is_last, inv_out, 0),
                     jnp.where(is_last, equiv_out, 0)),
                    STAGE_AXIS,
                )
                return (send[0], send[1], acc), y

            zero = (jnp.zeros_like(inv0[0]), jnp.zeros_like(equiv0[0]), acc0)
            (_, _, acc), ys = jax.lax.scan(tick, zero, jnp.arange(T))
            # microbatch m completes at tick m + S - 1
            out = jax.tree.map(lambda a: a[S - 1 : S - 1 + M], ys)
            if collect_ring:
                # each stage saw each of its microbatches once -> mean
                return out, jax.tree.map(lambda a: a / M, acc)
            return out

        from jax import shard_map

        out = shard_map(
            stage_fn,
            mesh=mesh,
            in_specs=(P(STAGE_AXIS), P(), P(), P()),
            out_specs=((P(), P()), P(STAGE_AXIS)) if collect_ring else P(),
            check_vma=False,
        )(stacked, inv0, equiv0, mb)
        ring = None
        if collect_ring:
            (inv, equiv), ring = out
        else:
            inv, equiv = out
        if not collect_stats:
            return inv, equiv
        # assemble the updated batch_stats pytree: prologue norms from the
        # vmapped pass (node-count-weighted mean over microbatches, so a
        # fill microbatch padding a trailing group carries zero stat
        # weight), ring norms unstacked from the [L-1, ...] stage-axis
        # output
        from .step import merge_replica_stats

        new_stats = dict(stats)
        new_stats.update(
            merge_replica_stats(pro_upd, jax.vmap(lambda b: b.node_mask.sum())(mb))
        )
        if collect_ring:
            for i in range(1, L):
                key = f"feature_norm_{i}"
                if key in stats:
                    new_stats[key] = jax.tree.map(lambda x: x[i - 1], ring)
        return inv, equiv, jax.lax.stop_gradient(new_stats)

    return forward


def make_pipelined_train_step(
    model: HydraModel, optimizer, mesh: Mesh, n_micro: int,
    compute_dtype=jnp.float32, norm: str = "batch", loss_scale=None,
):
    """Jitted pipelined train step: (state, microbatches[M, ...]) ->
    (state, metrics). Loss is the graph-weighted mean over microbatches,
    the same bookkeeping as the data-parallel step. With the default
    ``norm="batch"``, feature-norm RUNNING stats update too: one EMA step
    per microbatch, microbatch-averaged — the same semantics as the
    data-parallel step's replica-mean update, so a pipelined checkpoint
    evaluates/fine-tunes identically on the data-parallel path.

    ``loss_scale`` as in ``train.step.make_train_step``."""
    collect = norm == "batch"
    encode = make_pipelined_forward(model, mesh, n_micro, norm=norm,
                                    collect_stats=collect)
    conv_cls = CONV_REGISTRY[model.spec.mpnn_type]
    if not collect and getattr(conv_cls, "feature_norm", True):
        import warnings

        warnings.warn(
            "pipelined training with norm='running' freezes feature-norm "
            "running stats at their initial values (scale/bias still train).",
            stacklevel=2,
        )

    def loss_fn(params, batch_stats, mb: GraphBatch):
        c_params = _cast_floats(params, compute_dtype)
        c_mb = _cast_floats(mb, compute_dtype)
        variables = {"params": c_params, "batch_stats": batch_stats}
        if collect:
            inv, equiv, new_stats = encode(variables, c_mb)
        else:
            inv, equiv = encode(variables, c_mb)
            new_stats = batch_stats

        def per_micro(inv_m, equiv_m, b, b_raw):
            pred = model.apply(variables, inv_m, equiv_m, b, False,
                               method=HydraModel.decode)
            pred = _cast_floats(pred, jnp.float32)
            tot, tasks = model.loss(pred, b_raw)
            ng = b_raw.graph_mask.sum()
            return tot * ng, jnp.stack(tasks) * ng, ng

        tots, tasks, ngs = jax.vmap(per_micro)(inv, equiv, c_mb, mb)
        denom = jnp.maximum(ngs.sum(), 1.0)
        # the ring accumulates its norms in the compute dtype
        new_stats = _cast_floats(new_stats, jnp.float32)
        return tots.sum() / denom, (tasks.sum(axis=0) / denom, ngs.sum(), new_stats)

    grad_fn = scaled_value_and_grad(loss_fn, loss_scale)

    @partial(jax.jit, donate_argnums=donate_state_argnums())
    def train_step(state: TrainState, mb: GraphBatch):
        (loss, (tasks, ng, new_stats)), grads = grad_fn(
            state.params, state.batch_stats, mb
        )
        new_state = apply_gradients(
            state, grads, new_stats, optimizer, model.spec, loss_scale
        )
        return new_state, {"loss": loss, "tasks_loss": tasks, "num_graphs": ng}

    return train_step


def make_pipelined_eval_step(
    model: HydraModel, mesh: Mesh, n_micro: int,
    compute_dtype=jnp.float32, norm: str = "running",
):
    """Pipelined evaluation: same metrics dict as the data-parallel eval step
    (loss, per-task losses, per-head sse/count, graph count) so the epoch
    loop consumes either interchangeably. ``norm`` defaults to "running" —
    eval-mode running averages, the data-parallel eval step's semantics.
    Running stats accumulate during pipelined training (see
    ``make_pipelined_train_step``), so this keeps the LR scheduler (which
    steps on val loss) on the same trajectory as a data-parallel run."""
    encode = make_pipelined_forward(model, mesh, n_micro, norm=norm)

    @jax.jit
    def eval_step(state: TrainState, mb: GraphBatch):
        c_params = _cast_floats(state.params, compute_dtype)
        c_mb = _cast_floats(mb, compute_dtype)
        variables = {"params": c_params, "batch_stats": state.batch_stats}
        inv, equiv = encode(variables, c_mb)

        def per_micro(inv_m, equiv_m, b, b_raw):
            pred = model.apply(variables, inv_m, equiv_m, b, False,
                               method=HydraModel.decode)
            pred = _cast_floats(pred, jnp.float32)
            tot, tasks = model.loss(pred, b_raw)
            sses, counts = model.head_sse(pred, b_raw)
            ng = b_raw.graph_mask.sum()
            return (tot * ng, jnp.stack(tasks) * ng, jnp.stack(sses),
                    jnp.stack(counts), ng)

        tots, tasks, sses, counts, ngs = jax.vmap(per_micro)(inv, equiv, c_mb, mb)
        denom = jnp.maximum(ngs.sum(), 1.0)
        return {
            "loss": tots.sum() / denom,
            "tasks_loss": tasks.sum(axis=0) / denom,
            "head_sse": sses.sum(axis=0),
            "head_count": counts.sum(axis=0),
            "num_graphs": ngs.sum(),
        }

    return eval_step


def put_microbatches(mb: GraphBatch, mesh: Mesh) -> GraphBatch:
    """Place a [M, ...] stacked GraphBatch replicated over the stage mesh."""
    sh = NamedSharding(mesh, P())
    return jax.tree.map(lambda x: jax.device_put(jnp.asarray(x), sh), mb)
