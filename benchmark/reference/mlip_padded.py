"""``reference/mlip.py``'s energy-and-force training with every block laid
into ONE static shape, so that a run compiles the plain reference once.

Why. ``mlip.py`` hands ``node_energy`` the real graphs of a step and nothing
else, so every compared step is a shape of its own and a compile of its own:
a minute each where the model is a grad-of-grad of 70 dense layers
(DimeNet++), three minutes a run that compares three steps. Here a block is
ONE graph, and every block of every compared step is filled up to the
largest graph's atoms and edges: one program, run once a graph. What is
computed for the real atoms is what ``mlip.py`` computes (``L_d`` is a sum
over graphs, so blocks add up exactly; ``benchmark/tests`` holds the two
files against each other).

The filling needs no mask inside ``node_energy``. A filling atom has no
neighbour but itself: each of its edges is a self-loop whose shift vector is
``2 x cutoff`` long, so its length lies beyond the cutoff, where the
architecture's envelope and its derivatives are exactly zero, and at most
``max_neighbours`` of them leave one atom (the reference's cap). Filling
atoms belong to a graph of their own, which the loss leaves out; their
forces are left out by a mask on the atoms. So the architecture has to state
``cutoff`` and ``max_neighbours`` among its hyperparameters, and has to give
an edge beyond the cutoff no weight: DimeNet++ does.

Everything else (the loss, AdamW, the norms that are compared) is
``mlip.py``'s own code, imported from the file beside this one.
"""

from __future__ import annotations

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np


def _beside(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


mlip = _beside("mlip")
adamw_update, leaf_norms = mlip.adamw_update, mlip.leaf_norms  # what ``tools/leaf.py`` asks of an objective


def fill(b: dict, n_atoms: int, n_edges: int, far: float, cap: int) -> dict:
    """One real graph's arrays (``mlip.concat`` of it) -> the same graph in
    ``n_atoms`` atoms and ``n_edges`` edges (numpy, host): graph 0 is the real
    one, graph 1 holds the filling."""
    n, e = len(b["x"]), len(b["senders"])
    more_n, more_e = n_atoms - n, n_edges - e
    if more_n * cap < more_e:
        raise ValueError(f"{more_e} filling edges need more than {more_n} filling atoms at {cap} each")
    loops = n + np.arange(more_e, dtype=np.int32) // cap  # at most ``cap`` leave an atom
    shift = np.zeros((more_e, 3), np.float32)
    shift[:, 0] = far
    zeros = lambda *shape: np.zeros(shape, np.float32)
    return {
        "x": np.concatenate([b["x"], zeros(more_n, b["x"].shape[1])]),
        "pos": np.concatenate([b["pos"], zeros(more_n, 3)]),
        "senders": np.concatenate([b["senders"], loops]),
        "receivers": np.concatenate([b["receivers"], loops]),
        "shifts": np.concatenate([b["shifts"], shift]),
        "graph": np.concatenate([b["graph"], np.ones(more_n, np.int32)]),
        "atom": np.concatenate([np.ones(n, np.float32), zeros(more_n)]),
        "n_atoms": b["n_atoms"],
        "energy": b["energy"],
        "forces": np.concatenate([b["forces"], zeros(more_n, 3)]),
    }


@functools.partial(jax.jit, static_argnums=(0, 1))
def _graph_terms(node_energy, hp, params, b, inv_graphs, inv_force_rows):
    """One filled graph's share of L_d and of dL_d/dparams: ``mlip.py``'s
    ``_block_terms`` with the filling's graph and atoms left out."""

    def share(p):
        def total(pos):
            e_node = node_energy(p, dict(hp), b["x"], pos, b["senders"], b["receivers"],
                                 b["shifts"], matmul=mlip.MATMUL[dict(hp).get("emulate", "")])
            e_graph = jax.ops.segment_sum(e_node, b["graph"], 2)[:1]
            return e_graph.sum(), e_graph

        (_, e_graph), de_dpos = jax.value_and_grad(total, has_aux=True)(b["pos"])
        d = dict(hp)
        err = e_graph - b["energy"]
        return (
            d["energy_weight"] * (err ** 2).sum() * inv_graphs
            + d["energy_peratom_weight"] * ((err / b["n_atoms"]) ** 2).sum() * inv_graphs
            + d["force_weight"] * (((-de_dpos - b["forces"]) ** 2) * b["atom"][:, None]).sum()
            * inv_force_rows
        )

    return jax.value_and_grad(share)(params)


def step_loss_and_grad(node_energy, hp: dict, params: dict, sub_batches, input_scale: float,
                       shape: tuple[int, int] | None = None):
    """(L, dL/dparams) of one step, graph by graph at the one ``shape``
    (default: the one that holds this step's own graphs)."""
    shape = shape or one_shape([sub_batches], hp["max_neighbours"])
    hp_static = tuple(sorted(hp.items()))
    total_graphs = float(sum(len(sb) for sb in sub_batches))
    loss = 0.0
    grad = jax.tree.map(jnp.zeros_like, params)
    for sb in sub_batches:
        weight = len(sb) / total_graphs
        inv_rows = weight / (3.0 * sum(len(g["z"]) for g in sb))
        for g in sb:
            b = fill(mlip.concat([g], input_scale), *shape, far=2.0 * hp["cutoff"],
                     cap=hp["max_neighbours"])
            l, dl = _graph_terms(node_energy, hp_static, params,
                                 {k: jnp.asarray(v) for k, v in b.items()},
                                 jnp.float32(weight / len(sb)), jnp.float32(inv_rows))
            loss = loss + l
            grad = jax.tree.map(jnp.add, grad, dl)
    return loss, grad


def one_shape(steps, cap: int) -> tuple[int, int]:
    """(atoms, edges) that hold every graph of ``steps`` and its filling."""
    graphs = [g for sub_batches in steps for sb in sub_batches for g in sb]
    n_edges = max(len(g["senders"]) for g in graphs)
    n_atoms = max(len(g["z"]) - (-(n_edges - len(g["senders"])) // cap) for g in graphs)
    return n_atoms, n_edges


def follow(node_energy, hp: dict, opt: dict, params0: dict, steps, input_scale: float) -> dict:
    """``mlip.follow`` over filled graphs: each step's loss, the per-leaf norm
    of the first gradient and of the parameters' change after the last step."""
    shape = one_shape(steps, hp["max_neighbours"])
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params0.items()}
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for t, sub_batches in enumerate(steps, start=1):
        loss, grad = step_loss_and_grad(node_energy, hp, params, sub_batches, input_scale, shape)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = leaf_norms(grad)
        params, m, v = adamw_update(params, grad, m, v, t, opt["learning_rate"],
                                    opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"])
    change = leaf_norms({k: params[k] - jnp.asarray(params0[k], jnp.float32) for k in params})
    return {"losses": losses, "grad_norm": first_grad, "change_norm": change}
