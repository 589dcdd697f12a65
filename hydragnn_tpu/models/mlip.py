"""MLIP: energy-conserving interatomic potentials — forces via ``jax.grad``.

Reference: the ``EnhancedModelWrapper`` composition (``hydragnn/models/
create.py:590-758``). There, forces require ``data.pos.requires_grad``, an
inner ``torch.autograd.grad(energy, pos, create_graph=True)`` and an FSDP2
double-backward workaround (``train_validate_test.py:150-169, 722-754``).

Here the model's energy is a *pure function* of positions, so forces are one
``jax.grad`` and the outer parameter gradient is grad-of-grad — no workaround,
no mutable flags; the whole energy+force loss compiles into the same XLA
program as everything else. This is the architectural win of the functional
design.

Loss composition (``energy_force_loss``, reference ``create.py:626-738``):
    L = w_E * loss(E, E_true) + w_Ea * loss(E/n_atoms, E_true/n_atoms)
        + w_F * loss(F, F_true),   F = -dE/dpos
with per-task losses reported as [energy, energy_per_atom, force].

Constraints kept from the reference: exactly one output head (``:646-648``);
graph-type heads require sum pooling (``:659-662``); node-type heads are
summed into a graph energy (``:654-658``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..config.schema import ModelSpec
from ..graphs.graph import GraphBatch
from ..graphs import segment
from .base import HydraModel
from .common import get_loss


def validate_mlip_spec(spec: ModelSpec) -> None:
    if spec.num_heads != 1:
        raise ValueError("Force predictions require exactly one head (create.py:646-648)")
    if spec.activation in ("relu", "lrelu_01", "lrelu_025", "lrelu_05"):
        import warnings

        warnings.warn(
            "Force training with piecewise-linear activations (relu/leaky-relu) "
            "learns poorly: forces are energy gradients, and dE/dr is "
            "piecewise-constant under relu. Use 'silu', 'tanh', or 'gelu' "
            "(set NeuralNetwork.Architecture.activation_function)."
        )
    if spec.output_type[0] == "graph" and spec.graph_pooling not in ("add", "sum"):
        raise ValueError(
            "Graph head force loss requires sum pooling (graph_pooling='add')"
        )
    if (
        spec.energy_weight <= 0
        and spec.energy_peratom_weight <= 0
        and spec.force_weight <= 0
    ):
        raise ValueError(
            "All interatomic potential loss weights are zero; set at least one of "
            "energy_weight, energy_peratom_weight, or force_weight"
        )


def make_graph_energy_fn(model: HydraModel):
    """(variables, pos, batch) -> per-graph energies [G] (padding graphs 0).
    THE graph-energy closure: the training objective, the eval steps, MD and
    serving all differentiate this one function of positions. With
    ``train=True`` (dropout ``rngs``, batch statistics updated) it returns
    ``(energies, new batch_stats)``."""
    spec = model.spec

    def energy_fn(variables, pos, batch: GraphBatch, train: bool = False, rngs=None):
        b = batch.replace(pos=pos)
        if train:
            pred, updates = model.apply(
                variables, b, train=True, mutable=["batch_stats"], rngs=rngs
            )
        else:
            pred = model.apply(variables, b, train=False)
        if spec.var_output:
            pred = pred[0]
        if spec.output_type[0] == "node":
            node_e = pred[0] * b.node_mask[:, None]
            graph_e = segment.segment_sum(node_e[:, 0], b.batch, b.num_graphs)
        else:
            graph_e = pred[0][:, 0]
        graph_e = graph_e * batch.graph_mask
        return (graph_e, updates["batch_stats"]) if train else graph_e

    return energy_fn


def make_energy_and_forces(model: HydraModel):
    """(variables, batch) -> (graph_energy [G], forces [N, 3]).

    forces = -dE/dpos with E = sum of per-graph energies; every atom belongs
    to exactly one graph so the summed gradient is the per-atom force.
    """
    energy_fn = make_graph_energy_fn(model)

    def energy_and_forces(variables, batch: GraphBatch):
        def total_energy(pos):
            e = energy_fn(variables, pos, batch)
            return e.sum(), e

        (_, graph_e), grad_pos = jax.value_and_grad(total_energy, has_aux=True)(
            batch.pos
        )
        forces = -grad_pos * batch.node_mask[:, None]
        return graph_e, forces

    return energy_and_forces


def energy_force_loss(spec: ModelSpec, graph_e, forces, batch: GraphBatch):
    """Returns (total loss, [energy, energy_per_atom, force] task losses)."""
    loss_fn = get_loss(spec.loss_type)
    gmask = batch.graph_mask
    e_true = batch.energy_y[:, 0]

    e_loss = loss_fn(graph_e[:, None], e_true[:, None], gmask)
    natoms = jnp.maximum(batch.n_node.astype(graph_e.dtype), 1.0)
    ea_loss = loss_fn(
        (graph_e / natoms)[:, None], (e_true / natoms)[:, None], gmask
    )
    f_loss = loss_fn(forces, batch.forces_y, batch.node_mask)

    tot = (
        spec.energy_weight * e_loss
        + spec.energy_peratom_weight * ea_loss
        + spec.force_weight * f_loss
    )
    return tot, [e_loss, ea_loss, f_loss]


def make_mlip_train_step(model: HydraModel, optimizer, compute_dtype=jnp.float32,
                         loss_scale=None):
    """Jitted MLIP train step: outer grad over (inner force grad + losses),
    ``train.step.energy_force_objective`` through the single-device step.

    ``loss_scale`` as in ``train.step.make_train_step``. Only the OUTER
    (param) objective is scaled — the inner position grad must stay in
    physical units because the forces it produces feed the loss itself.
    The benchmark's readers find this program by the jitted name
    ``train_step`` and the scopes ``mlip_loss`` and ``optimizer``."""
    from ..train import step as _step

    step = _step.single_device_step(
        model, optimizer, _step.energy_force_objective(model), compute_dtype, loss_scale
    )

    @functools.partial(jax.jit, donate_argnums=_step.donate_state_argnums())
    def train_step(state, batch: GraphBatch):
        return step(state, batch)

    return train_step


def make_mlip_eval_step(model: HydraModel, compute_dtype=jnp.float32):
    from ..train.step import TrainState, _cast_floats

    spec = model.spec
    energy_and_forces = make_energy_and_forces(model)

    @jax.jit
    def eval_step(state: TrainState, batch: GraphBatch):
        c_params = _cast_floats(state.params, compute_dtype)
        c_batch = _cast_floats(batch, compute_dtype)
        variables = {"params": c_params, "batch_stats": state.batch_stats}
        graph_e, forces = energy_and_forces(variables, c_batch)
        graph_e = graph_e.astype(jnp.float32)
        forces = forces.astype(jnp.float32)
        tot, tasks = energy_force_loss(spec, graph_e, forces, batch)

        # RMSE accumulators: [energy, force]
        gm = batch.graph_mask
        e_sse = (((graph_e - batch.energy_y[:, 0]) ** 2) * gm).sum()
        e_cnt = gm.sum()
        f_sse = (((forces - batch.forces_y) ** 2) * batch.node_mask[:, None]).sum()
        f_cnt = batch.node_mask.sum() * 3
        return {
            "loss": tot,
            "tasks_loss": jnp.stack(tasks),
            "head_sse": jnp.stack([e_sse, f_sse]),
            "head_count": jnp.stack([e_cnt, f_cnt]),
            "num_graphs": batch.graph_mask.sum(),
        }

    return eval_step
