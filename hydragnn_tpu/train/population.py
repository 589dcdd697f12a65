"""Population training: N trials / ensemble members in ONE jitted program.

The reference runs hyperparameter search as fleets of independent OS
processes (DeepHyper ``ProcessPoolEvaluator``/srun,
``examples/multidataset_hpo/gfm_deephyper_multi.py``) — N interpreters, N
compiles, N data pipelines, N dispatch streams, for trials that differ only
in scalar hyperparameters. On an accelerator that is almost pure waste: the
trials share every shape, so stacking their ``TrainState``s along a leading
member axis and ``jax.vmap``-ing the existing ``(state, batch) -> (state,
metrics)`` train step turns the whole population into one SPMD program —
one compile, one data pipeline, one dispatch per step for all N members.
Composed with the PR 2 superstep (``lax.scan`` outside, ``vmap`` inside),
one host dispatch advances N members x K steps.

What makes members differ inside one program:

* **init seeds** — ``create_population_state`` stacks per-member
  ``create_train_state`` results (deep ensembles: same data, different
  initializations; HPO trials: same init, different hyperparameters);
* **lr / weight decay** — already runtime DATA, not compile-time constants:
  ``train/optimizer.py`` injects them via ``optax.inject_hyperparams`` into
  ``opt_state.hyperparams``, so the stacked optimizer state carries a
  ``[N]`` value per hyperparameter and vmap gives every member its own;
* **loss weights** — ``make_weighted_train_step`` takes the task-weight
  vector as a traced argument; the population step binds a ``[N, n_tasks]``
  stack with ``in_axes=0``.

Per-member divergence (the resilience story under vmap): the non-finite
guard's ``lax.cond`` skip is NOT used here — under vmap a batched cond
lowers to a select over both branches and (measured on CPU) perturbs
healthy members' numerics at the 1e-7 level, which breaks the fp32
bit-parity gate. Instead the population step computes a per-member
finiteness mask and reverts diverged members with the superstep's
``select_state`` where-select — measured bit-transparent: healthy members
of an N-member population match plain unguarded single runs bit for bit
(``tests/test_population.py``). A member whose skip streak crosses the
resilience limit is reported as status ``"diverged"`` and simply stays
frozen at its last finite state; the rest of the population never stalls.

The ensemble variance surfaced in the summary is the uncertainty signal the
ROADMAP's active-learning item consumes next.
"""

from __future__ import annotations

import functools
import json
import os
import time
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .optimizer import set_hyperparam
from .step import (
    TrainState,
    create_train_state,
    donate_state_argnums,
    make_eval_step,
    make_train_step,
    make_weighted_train_step,
    resolve_loss_scale,
    resolve_training_precision,
)
from .superstep import make_superstep, resolve_steps_per_dispatch, select_state


class PopulationState(NamedTuple):
    """N ``TrainState``s stacked along a leading member axis: every leaf of
    ``state`` is ``[N, ...]``. A NamedTuple so it is itself a pytree — it
    rides ``train_epoch``/``make_superstep``/checkpointing unchanged."""

    state: TrainState

    @property
    def n_members(self) -> int:
        return int(self.state.step.shape[0])


def stack_states(states: Sequence[TrainState]) -> PopulationState:
    """Stack per-member states into one device-resident population."""
    return PopulationState(
        state=jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    )


def member_state(pstate: PopulationState, i: int) -> TrainState:
    """Slice member ``i`` back out (host-side inspection / checkpoint of a
    single winner)."""
    return jax.tree.map(lambda x: x[i], pstate.state)


def resolve_population_size(training_cfg: dict) -> int:
    """The single resolver for N (``run_training`` routing and direct
    callers): ``HYDRAGNN_POPULATION`` overrides ``Training.population.size``;
    unset/0/1 disables."""
    from ..utils import flags

    pop = training_cfg.get("population") or {}
    n = flags.get(flags.POPULATION, default=int(pop.get("size", 0) or 0))
    return max(0, int(n))


def create_population_state(
    model,
    optimizer,
    example_batch,
    n_members: int,
    seeds: Sequence[int] | None = None,
    hyperparams: dict[str, Sequence[float] | None] | None = None,
) -> PopulationState:
    """Initialize N members and stack them.

    ``seeds``: per-member init PRNG seeds (deep ensembles). ``None`` gives
    every member the default init — bit-identical to what a single
    ``run_training`` would start from (HPO trials: same init, different
    hyperparameters). ``hyperparams``: per-member injected optimizer
    hyperparameter stacks, e.g. ``{"learning_rate": [1e-3, 3e-4, 1e-4]}``
    (any ``None`` value means "shared config default" and is skipped)."""
    if seeds is not None and len(seeds) != n_members:
        raise ValueError(f"got {len(seeds)} seeds for {n_members} members")
    for name, vals in (hyperparams or {}).items():
        if vals is not None and len(vals) != n_members:
            raise ValueError(
                f"got {len(vals)} {name} values for {n_members} members"
            )
    members = []
    for i in range(n_members):
        rng = jax.random.PRNGKey(int(seeds[i])) if seeds is not None else None
        s = create_train_state(model, optimizer, example_batch, rng=rng)
        for name, vals in (hyperparams or {}).items():
            if vals is not None:
                s = s._replace(
                    opt_state=set_hyperparam(s.opt_state, name, float(vals[i]))
                )
        members.append(s)
    return stack_states(members)


def population_template(model, optimizer, example_batch, n_members: int) -> PopulationState:
    """A restore TEMPLATE with the ``[N]``-stacked structure: one member
    init broadcast N ways. Values are irrelevant — checkpoint restore only
    reads the template's treedef/shapes/dtypes — so this costs ONE
    ``create_train_state`` instead of N (``create_population_state`` pays N
    inits because its VALUES matter). The stacked TrainState carries the
    single-state treedef with ``[N, ...]`` leaves, so the ordinary
    checkpoint machinery (orbax + manifest + sidecar) round-trips a whole
    population — fp32 master weights, per-member opt state (including the
    injected hyperparameter stacks), and per-member step counters — through
    the files a single-state run would write."""
    s = create_train_state(model, optimizer, example_batch)
    return stack_states([s] * int(n_members))


def _members_finite(tree, n: int) -> jax.Array:
    """``[N]`` bool: member ``i``'s floating leaves are all finite.

    The member-axis analogue of the resilience guard's scalar probe
    (``resilience/guard.py::_all_finite``): ``x * 0`` is 0 for finite x and
    NaN for NaN/Inf, so reducing each leaf over everything BUT the member
    axis gives a per-member poison flag in 2 fused ops per leaf."""
    probe = jnp.zeros((n,), jnp.float32)
    for leaf in jax.tree.leaves(tree):
        leaf = jnp.asarray(leaf)
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            probe = probe + (leaf * 0).reshape(n, -1).sum(axis=1).astype(jnp.float32)
    return probe == 0


def make_population_step(
    train_step: Callable,
    task_weights=None,
    donate_argnums=None,
) -> Callable:
    """vmap a per-member ``(state, batch) -> (state, metrics)`` train step
    over the leading member axis: ``(PopulationState, batch) ->
    (PopulationState, metrics)`` with every metric leaf ``[N, ...]``. The
    batch is SHARED (``in_axes=None``): HPO trials and deep ensembles both
    train every member on the same stream.

    ``task_weights`` (``[N, n_tasks]``, optional): per-member loss weights;
    ``train_step`` must then be a :func:`make_weighted_train_step` (3-arg)
    step.

    Pass the PLAIN step — not one wrapped by ``wrap_step_with_guard``: the
    guard's batched ``lax.cond`` perturbs healthy members' numerics under
    vmap (module docstring), and the population step already carries its own
    bit-transparent skip. After the vmapped step runs, members whose loss or
    updated params/stats/opt state went non-finite are reverted with
    ``select_state`` on a ``[N]`` mask and their metrics zeroed
    (``num_graphs`` -> 0, so weighted epoch aggregates ignore them exactly
    like fill batches); ``metrics["skipped"]`` reports the ``[N]`` skip
    mask. Composes with ``make_superstep`` (scan outside, vmap inside): one
    jitted dispatch then advances N members x K steps."""
    donate = donate_state_argnums() if donate_argnums is None else donate_argnums
    if task_weights is not None:
        w = jnp.asarray(task_weights, jnp.float32)
        if w.ndim != 2:
            raise ValueError(
                f"task_weights must be [n_members, n_tasks], got shape {w.shape}"
            )
        vstep = jax.vmap(train_step, in_axes=(0, None, 0))

        def run(state, batch):
            return vstep(state, batch, w)
    else:
        run = jax.vmap(train_step, in_axes=(0, None))

    @functools.partial(jax.jit, donate_argnums=donate)
    def population_step(pstate: PopulationState, batch):
        new_state, metrics = run(pstate.state, batch)
        # Per-member divergence skip: one where-select per leaf on the [N]
        # finiteness mask. Checks mirror the resilience guard: the loss
        # (NaN forward), params (finite loss / Inf update), batch stats, and
        # optimizer state (an overflowed Adam moment silently zeroes that
        # parameter's updates forever if allowed to stick).
        ok = _members_finite(
            (
                metrics["loss"],
                new_state.params,
                new_state.batch_stats,
                new_state.opt_state,
            ),
            pstate.n_members,
        )
        new_state = select_state(ok, new_state, pstate.state)
        metrics = select_state(ok, metrics, jax.tree.map(jnp.zeros_like, metrics))
        metrics["skipped"] = jnp.logical_not(ok).astype(jnp.int32)
        return PopulationState(state=new_state), metrics

    return population_step


def make_population_eval_step(model, compute_dtype=jnp.float32) -> Callable:
    """vmapped eval: ``(stacked TrainState, batch) -> metrics`` with a
    leading ``[N]`` axis on every metric — feeds ``loop.evaluate`` with the
    member-aware accumulator for per-member val/test losses and RMSEs."""
    eval_step = make_eval_step(model, compute_dtype=compute_dtype)
    return jax.jit(jax.vmap(eval_step, in_axes=(0, None)))


def accumulate_members(step_metrics: list, extra_keys: tuple = (), *, n_members: int):
    """Member-resolved version of ``loop._accumulate``: graph-count-weighted
    reduction keeping the ``[N]`` member axis. Accepts per-step metrics
    (leaves ``[N, ...]``) and superstep-stacked ones (``[K, N, ...]``) —
    ``n_members`` disambiguates the two, which is why this cannot fold into
    ``_accumulate`` (a bare ``[X]`` vector could be either axis). Returns
    ``(loss[N], tasks[N, T], extras{k: [N, ...]})``; a member whose every
    step was skipped has zero weight and reports NaN (nothing trained — a
    0.0 would beat every real loss in best-member selection)."""
    step_metrics = jax.device_get(step_metrics)
    n = int(n_members)
    tot = np.zeros(n, np.float64)
    tasks = None
    n_graphs = np.zeros(n, np.float64)
    extras: dict = {k: None for k in extra_keys}
    for m in step_metrics:
        g = np.asarray(m["num_graphs"], np.float64).reshape(-1, n)  # [K, N]
        loss = np.asarray(m["loss"], np.float64).reshape(-1, n)
        with np.errstate(invalid="ignore"):
            # a skipped member's metrics are zeroed (0 * 0 contributes
            # nothing), but a non-finite loss can still reach here when the
            # caller runs an unguarded step — keep the weighted sum honest
            tot += (loss * g).sum(axis=0)
        t = np.asarray(m["tasks_loss"], np.float64).reshape(g.shape[0], n, -1)
        t = (t * g[..., None]).sum(axis=0)  # [N, T]
        tasks = t if tasks is None else tasks + t
        for k in extra_keys:
            v = np.asarray(m[k], np.float64).reshape(g.shape[0], n, -1).sum(axis=0)
            extras[k] = v if extras[k] is None else extras[k] + v
        n_graphs += g.sum(axis=0)
    denom = np.maximum(n_graphs, 1.0)
    loss = tot / denom
    loss = np.where(n_graphs > 0, loss, np.nan)
    if tasks is None:
        tasks = np.zeros((n, 0), np.float64)
    else:
        tasks = tasks / denom[:, None]
        tasks = np.where(n_graphs[:, None] > 0, tasks, np.nan)
    return loss, tasks, extras


class MemberTracker:
    """Per-member consecutive-skip streaks over the population's on-device
    ``skipped`` metrics — the population counterpart of the resilience
    layer's ``SkipTracker``, with one decisive difference: it NEVER raises.
    A diverged member must not take the other N-1 members down with a
    rollback; it is marked ``"diverged"`` and left frozen (its per-step
    where-select keeps reverting it), while the healthy members keep
    training bit-identically. Reads are deferred exactly like SkipTracker's
    (only values older than the loop's in-flight window materialize), so
    tracking adds zero pipeline stalls; duck-typed so ``train_epoch``'s
    resilience hook drives it unmodified."""

    def __init__(self, n_members: int, max_consecutive: int, lag: int = 32):
        self.n_members = int(n_members)
        self.max_consecutive = int(max_consecutive)
        self.lag = max(0, int(lag))
        self.consecutive = np.zeros(self.n_members, np.int64)
        self.total = np.zeros(self.n_members, np.int64)
        self.diverged = np.zeros(self.n_members, bool)
        self.steps = 0
        from collections import deque

        self._pending: "deque" = deque()

    def push(self, skipped) -> None:
        self._pending.append(skipped)
        while len(self._pending) > self.lag:
            self._drain_one()

    def finish(self) -> None:
        while self._pending:
            self._drain_one()

    def _drain_one(self) -> None:
        arr = np.asarray(
            jax.device_get(self._pending.popleft()), np.int64
        ).reshape(-1, self.n_members)  # [K, N]
        for row in arr:
            self.steps += 1
            self.total += row
            self.consecutive = np.where(row > 0, self.consecutive + 1, 0)
            if self.max_consecutive > 0:
                self.diverged |= self.consecutive >= self.max_consecutive

    def statuses(self) -> list[str]:
        return ["diverged" if d else "ok" for d in self.diverged]

    def state_dict(self) -> dict:
        """Checkpoint-sidecar form of the tracker (drains deferred reads
        first — a mid-lag snapshot would under-count the streaks)."""
        self.finish()
        return {
            "diverged": [bool(d) for d in self.diverged],
            "consecutive": [int(c) for c in self.consecutive],
            "total": [int(t) for t in self.total],
            "steps": int(self.steps),
        }

    def load_state_dict(self, d: dict) -> None:
        """Restore a saved tracker: a member marked diverged STAYS diverged
        across a resume (its restored state is the last finite one the
        where-select froze; forgetting the mark would let it report "ok"
        while re-diverging on its first resumed step)."""
        self.diverged = np.asarray(
            d.get("diverged", [False] * self.n_members), bool
        ).copy()
        self.consecutive = np.asarray(
            d.get("consecutive", [0] * self.n_members), np.int64
        ).copy()
        self.total = np.asarray(
            d.get("total", [0] * self.n_members), np.int64
        ).copy()
        self.steps = int(d.get("steps", 0))


class _PopulationEpochHooks:
    """Duck-typed stand-in for the ``Resilience`` context ``train_epoch``
    threads through an epoch: no chaos, no watchdog, no preemption — just
    the deferred per-member skip tracking. (The full resilience context is
    deliberately NOT reused: its tracker raises ``DivergenceDetected`` and
    rolls the WHOLE state back, which is exactly wrong for one bad member
    in an otherwise healthy population.)"""

    watchdog = None
    chaos = None

    def __init__(self, tracker: MemberTracker):
        self._tracker = tracker
        self.current_epoch = 0
        self.skipped_total = 0
        self.interrupted = False
        self.epoch_raw_done = 0

    def preempt_requested(self) -> bool:
        return False

    def new_tracker(self, lag: int) -> MemberTracker:
        self._tracker.lag = max(0, int(lag))
        return self._tracker


def _normalize_task_weights(weights, n_tasks: int) -> list[float]:
    """Per-member weights normalized exactly like ``ModelSpec.from_config``
    (w / sum|w|) so a member whose weights equal the spec's is bit-identical
    to a statically-weighted run."""
    w = [float(x) for x in weights]
    if len(w) != n_tasks:
        raise ValueError(f"expected {n_tasks} task weights, got {len(w)}")
    wsum = sum(abs(x) for x in w)
    return [x / wsum for x in w]


def population_meta(n: int, epochs_done: int, tracker: MemberTracker | None = None) -> dict:
    """Checkpoint-sidecar block for a population save: the member count (a
    pre-restore sanity check — restoring an N-stack into an M-template
    would die inside orbax with a shape soup), how many epochs the saved
    state has fully trained (the continue resume point), and the per-member
    divergence bookkeeping."""
    meta = {
        "population": int(n),
        "population_epochs_done": int(epochs_done),
    }
    if tracker is not None:
        meta["member_tracker"] = tracker.state_dict()
        meta["member_status"] = tracker.statuses()
    return meta


def fit_population(
    model,
    optimizer,
    train_loader,
    val_loader,
    config_nn: dict,
    *,
    n_members: int,
    seeds: Sequence[int] | None = None,
    learning_rates: Sequence[float] | None = None,
    weight_decays: Sequence[float] | None = None,
    task_weights: Sequence[Sequence[float]] | None = None,
    verbosity: int = 0,
    walltime_check=None,
    initial_state: PopulationState | None = None,
    start_epoch: int = 0,
    tracker_state: dict | None = None,
    log_name: str | None = None,
    path: str = "./logs/",
) -> tuple[PopulationState, dict]:
    """The population engine: train N members as one vmapped (and, at
    ``Training.steps_per_dispatch``/``HYDRAGNN_SUPERSTEP`` K>1,
    scan-folded) program for ``Training.num_epoch`` epochs.

    Checkpoint/continue (``Training.continue`` + ``Training.population``):
    ``initial_state`` is a RESTORED ``[N]``-stacked population (fp32 master
    weights + per-member opt state incl. injected hyperparameter stacks —
    see :func:`population_template`); training resumes at ``start_epoch``
    with the per-member divergence bookkeeping re-seeded from
    ``tracker_state``. The epoch stream is deterministic in (seed, epoch),
    so a resumed run's remaining epochs bit-match an uninterrupted run's.
    With ``log_name`` set and ``Training.resilience.checkpoint_every_epoch``
    on, every epoch end writes a rolling population checkpoint whose sidecar
    carries the member statuses — the resume point this path consumes.

    Returns ``(pstate, summary)`` where ``summary`` carries per-member
    records (status, final train/val loss, the member's hyperparameters)
    plus ensemble mean/variance of the member losses — the ensemble spread
    that doubles as an epistemic-uncertainty signal."""
    from ..utils import flags
    from ..utils.print_utils import print_distributed
    from .loop import train_epoch, evaluate

    training = config_nn["Training"]
    num_epoch = int(training["num_epoch"])
    precision = resolve_training_precision(training)
    n = int(n_members)
    if n < 1:
        raise ValueError(f"population training needs >= 1 member, got {n}")

    n_tasks = len(model.spec.task_weights)
    tw = None
    if task_weights is not None:
        if len(task_weights) != n:
            raise ValueError(
                f"got {len(task_weights)} task-weight rows for {n} members"
            )
        tw = [_normalize_task_weights(row, n_tasks) for row in task_weights]
    # a member trains the model's own objective (train/step.py), its task
    # weights traced where the members differ in them
    make_step = make_train_step if tw is None else make_weighted_train_step
    step = make_step(
        model, optimizer, compute_dtype=precision,
        loss_scale=resolve_loss_scale(training),
    )
    pop_step = make_population_step(step, task_weights=tw)
    k = resolve_steps_per_dispatch(training)
    dispatch_step = make_superstep(pop_step, k) if k > 1 else pop_step
    eval_step = make_population_eval_step(model, compute_dtype=precision)

    if initial_state is not None:
        if initial_state.n_members != n:
            raise ValueError(
                f"restored population has {initial_state.n_members} members "
                f"but the config asks for {n}"
            )
        pstate = initial_state  # hyperparam stacks ride the restored opt state
    else:
        example = next(iter(train_loader))
        pstate = create_population_state(
            model, optimizer, example, n, seeds=seeds,
            hyperparams={
                "learning_rate": learning_rates,
                "weight_decay": weight_decays,
            },
        )

    res_cfg = training.get("resilience") or {}
    from ..resilience import config_defaults

    max_skips = int(
        res_cfg.get(
            "max_consecutive_skips", config_defaults()["max_consecutive_skips"]
        )
    )
    tracker = MemberTracker(n, max_skips)
    if tracker_state:
        tracker.load_state_dict(tracker_state)
    hooks = _PopulationEpochHooks(tracker)
    acc = functools.partial(accumulate_members, n_members=n)

    if k > 1 and hasattr(train_loader, "set_superstep"):
        train_loader.set_superstep(k)
    skip_valtest = not flags.get(flags.VALTEST)
    if len(getattr(val_loader, "samples", ())) == 0:
        skip_valtest = True

    checkpoint_every = bool(res_cfg.get("checkpoint_every_epoch")) and log_name

    def _rolling_save(epoch: int) -> None:
        """Per-epoch population checkpoint: the stacked state through the
        ordinary machinery, plus the sidecar a continue needs (member count
        for a pre-restore sanity check, epochs done, tracker state)."""
        from .checkpoint import save_checkpoint

        save_checkpoint(
            pstate.state, log_name, epoch, path=path,
            meta=population_meta(n, epoch + 1, tracker),
        )

    train_loss = np.full(n, np.nan)
    val_loss = np.full(n, np.nan)
    history = []
    from .. import telemetry as tel

    for epoch in range(start_epoch, num_epoch):
        train_loader.set_epoch(epoch)
        hooks.current_epoch = epoch
        tel.set_context(epoch=epoch)
        t_epoch0 = time.monotonic()
        pstate, train_loss, _ = train_epoch(
            dispatch_step, pstate, train_loader, verbosity,
            steps_per_dispatch=k, resilience=hooks, accumulate=acc,
        )
        if not skip_valtest:
            val_loss, _, _ = evaluate(
                eval_step, pstate.state, val_loader, verbosity, accumulate=acc
            )
        if checkpoint_every:
            _rolling_save(epoch)
        history.append(
            {
                "epoch": epoch,
                "train_loss": [float(x) for x in np.asarray(train_loss)],
                "val_loss": [float(x) for x in np.asarray(val_loss)],
            }
        )
        # scalar headline losses (finite-member mean) so the CLI's
        # epoch-throughput section renders population runs too; the
        # per-member vectors ride alongside under member_* keys
        def _finite_mean(xs):
            finite_xs = [x for x in np.asarray(xs, np.float64) if np.isfinite(x)]
            return float(np.mean(finite_xs)) if finite_xs else None

        tel.emit(
            "epoch", epoch=epoch, members=n,
            duration_s=round(time.monotonic() - t_epoch0, 4),
            raw_batches=int(getattr(hooks, "epoch_raw_done", 0) or 0),
            train_loss=_finite_mean(train_loss),
            val_loss=None if skip_valtest else _finite_mean(val_loss),
            member_train_loss=history[-1]["train_loss"],
            member_val_loss=(
                None if skip_valtest else history[-1]["val_loss"]
            ),
        )
        _fmt = lambda xs: "[" + ", ".join(f"{x:.6f}" for x in np.asarray(xs)) + "]"
        print_distributed(
            verbosity,
            f"Epoch: {epoch:04d}, population({n}) train {_fmt(train_loss)}"
            + ("" if skip_valtest else f", val {_fmt(val_loss)}"),
        )
        if walltime_check is not None and walltime_check():
            print_distributed(
                verbosity, f"Walltime guard tripped at epoch {epoch}"
            )
            break

    statuses = tracker.statuses()
    member_loss = np.asarray(train_loss if skip_valtest else val_loss, np.float64)
    # a diverged member's last accumulated loss is stale/meaningless — it
    # must never look like a finite result downstream (HPO best selection)
    member_objectives = [
        float("inf") if st == "diverged" or not np.isfinite(v) else float(v)
        for st, v in zip(statuses, member_loss)
    ]
    finite = [v for v in member_objectives if np.isfinite(v)]
    summary = {
        "n_members": n,
        "steps_per_dispatch": k,
        "objective_split": "train" if skip_valtest else "val",
        "members": [
            {
                "member": i,
                "status": statuses[i],
                "objective": member_objectives[i],
                "train_loss": float(np.asarray(train_loss)[i]),
                "val_loss": float(np.asarray(val_loss)[i]),
                "skipped_steps": int(tracker.total[i]),
                "seed": None if seeds is None else int(seeds[i]),
                "learning_rate": None if learning_rates is None
                else float(learning_rates[i]),
                "weight_decay": None if weight_decays is None
                else float(weight_decays[i]),
                "task_weights": None if tw is None else tw[i],
            }
            for i in range(n)
        ],
        # ensemble spread over the surviving members: the uncertainty signal
        # (disagreement) the active-learning loop thresholds on
        "ensemble": {
            "mean": float(np.mean(finite)) if finite else None,
            "variance": float(np.var(finite)) if finite else None,
            "n_finite": len(finite),
        },
        # the divergence bookkeeping in sidecar form, so a FINAL save's meta
        # can carry it too and a later continue (num_epoch raised) resumes
        # the streak/diverged state, not just the weights
        "member_tracker": tracker.state_dict(),
        "start_epoch": int(start_epoch),
        "history": history,
    }
    return pstate, summary


def train_population(
    model,
    optimizer,
    train_loader,
    val_loader,
    test_loader,
    config_nn: dict,
    log_name: str,
    verbosity: int = 0,
    walltime_check=None,
    initial_state: PopulationState | None = None,
    start_epoch: int = 0,
    tracker_state: dict | None = None,
    path: str = "./logs/",
) -> tuple[PopulationState, dict]:
    """Config-driven front of :func:`fit_population`: reads the
    ``Training.population`` block (size / per-member seeds, learning rates,
    weight decays, task weights), trains the population, evaluates the test
    split per member, and writes the summary next to the run logs
    (``<path>/<run>/population.json`` — the same ``path=`` root
    ``checkpoint.py`` threads everywhere, so a relocated log tree relocates
    the summary with it). ``initial_state``/``start_epoch``/
    ``tracker_state`` are the ``Training.continue`` resume point
    (``run_training`` restores them via :func:`population_template` + the
    checkpoint sidecar's :func:`population_meta` block)."""
    training = config_nn["Training"]
    pop_cfg = training.get("population") or {}
    n = resolve_population_size(training)
    seeds = pop_cfg.get("seeds")
    if seeds is None:
        # deep-ensemble default: distinct inits are the whole point of an
        # ensemble — members that only ever differ by rounding are not one
        seeds = list(range(n))
    pstate, summary = fit_population(
        model, optimizer, train_loader, val_loader, config_nn,
        n_members=n,
        seeds=seeds,
        learning_rates=pop_cfg.get("learning_rates"),
        weight_decays=pop_cfg.get("weight_decays"),
        task_weights=pop_cfg.get("task_weights"),
        verbosity=verbosity,
        walltime_check=walltime_check,
        initial_state=initial_state,
        start_epoch=start_epoch,
        tracker_state=tracker_state,
        log_name=log_name,
        path=path,
    )
    from ..utils import flags
    from .loop import evaluate

    if flags.get(flags.VALTEST) and len(getattr(test_loader, "samples", ())):
        precision = resolve_training_precision(training)
        eval_step = make_population_eval_step(model, compute_dtype=precision)
        test_loss, _, test_rmse = evaluate(
            eval_step, pstate.state, test_loader, verbosity, span="test",
            accumulate=functools.partial(accumulate_members, n_members=n),
        )
        summary["test_loss"] = [float(x) for x in np.asarray(test_loss)]
        summary["test_rmse"] = np.asarray(test_rmse).tolist()
    try:
        # the configurable path= root, NOT a hardcoded "./logs" — the
        # summary must land next to the run's checkpoints wherever the
        # caller pointed the log tree
        summary_path = os.path.join(path, log_name, "population.json")
        os.makedirs(os.path.dirname(summary_path), exist_ok=True)
        with open(summary_path, "w") as f:
            json.dump(summary, f, indent=2)
    except OSError:
        pass
    return pstate, summary


# dotted config paths run_hpo(backend="vmap") may vary INSIDE one vmapped
# population (runtime data in the stacked state), mapped to fit_population
# kwargs. Everything else (architecture, batch size, ...) changes the
# compiled program and falls back to per-trial evaluation.
VMAP_SCALAR_KEYS = {
    "NeuralNetwork.Training.Optimizer.learning_rate": "learning_rates",
    "NeuralNetwork.Training.Optimizer.weight_decay": "weight_decays",
    "NeuralNetwork.Architecture.task_weights": "task_weights",
}


def make_population_objective(
    samples=None, rank: int = 0, world: int = 1
) -> Callable[[dict, list], list]:
    """Build the population trial evaluator ``run_hpo(backend="vmap")``
    consumes: ``(base_config, member_assignments) -> [(objective, status)]``.

    ``member_assignments`` is a list of dicts keyed by
    :data:`VMAP_SCALAR_KEYS` dotted paths; all members train in ONE vmapped
    program on the data named by ``base_config`` (or the in-memory
    ``samples``), and each member's objective is its validation loss (train
    loss when no val split exists). Diverged members score ``inf`` — the
    same never-beats-finite semantics as subprocess trials."""

    def population_objective(base_config, member_assignments) -> list:
        from ..config import load_config, update_config
        from ..models.create import create_model_config
        from ..preprocess.load_data import dataset_loading_and_splitting
        from .optimizer import select_optimizer

        config = load_config(base_config)
        train_loader, val_loader, _test_loader = dataset_loading_and_splitting(
            config, samples=samples, rank=rank, world=world
        )
        config = update_config(config, train_loader.samples)
        model = create_model_config(config)
        n = len(member_assignments)
        unknown = {
            key for a in member_assignments for key in a
        } - set(VMAP_SCALAR_KEYS)
        if unknown:
            raise ValueError(
                f"non-vmappable keys in population assignments: {sorted(unknown)}"
            )
        opt_cfg = config["NeuralNetwork"]["Training"]["Optimizer"]
        wd_key = "NeuralNetwork.Training.Optimizer.weight_decay"
        if any(wd_key in a for a in member_assignments):
            # per-member decays need the decay injected, which
            # select_optimizer only does for an EXPLICIT config value
            # (implicit decay keeps the historical opt_state pytree)
            from .optimizer import ensure_injected_weight_decay

            ensure_injected_weight_decay(opt_cfg)
        optimizer = select_optimizer(opt_cfg)
        wd_default = opt_cfg.get("weight_decay")
        defaults = {
            "learning_rates": float(opt_cfg["learning_rate"]),
            "weight_decays": wd_default,
            "task_weights": list(
                config["NeuralNetwork"]["Architecture"].get("task_weights")
                or [1.0] * len(model.spec.task_weights)
            ),
        }
        kwargs: dict[str, Any] = {}
        for dotted, kw in VMAP_SCALAR_KEYS.items():
            if any(dotted in a for a in member_assignments):
                kwargs[kw] = [
                    a.get(dotted, defaults[kw]) for a in member_assignments
                ]
        _, summary = fit_population(
            model, optimizer, train_loader, val_loader,
            config["NeuralNetwork"], n_members=n, verbosity=0, **kwargs,
        )
        return [
            (m["objective"], m["status"]) for m in summary["members"]
        ]

    return population_objective


__all__ = [
    "PopulationState",
    "MemberTracker",
    "VMAP_SCALAR_KEYS",
    "accumulate_members",
    "create_population_state",
    "fit_population",
    "make_population_eval_step",
    "make_population_objective",
    "make_population_step",
    "member_state",
    "population_meta",
    "population_template",
    "resolve_population_size",
    "stack_states",
    "train_population",
]
