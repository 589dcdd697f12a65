"""``reference/mlip.py``'s energy-and-force training for a model that keeps
BATCH STATISTICS: a step is one block, and every compared step is laid into
ONE static shape, so that a run compiles the plain reference once.

Why a file of its own. Batch statistics (a batch norm's moments in training
mode) couple the graphs of a step: ``mlip.py`` and ``mlip_padded.py`` split a
step into blocks of graphs that add up exactly, which such a model's loss does
not. Here the whole step's real graphs are concatenated (``mlip.concat``),
filled up to the largest compared step's atoms and edges, and handed over
with two masks. What the architecture file supplies (``reference/gps.py`` is
the first):

    node_energy(params, hp, block, pos, stats, matmul=) -> (e_node [N], new stats)
    node_energy.extras(graph dict, hp=) -> {name: [n, .] or [e, .] array}   (optional)
    node_energy.initial_stats(params) -> the statistics a fresh model starts from
    node_energy.unmoved(hp) -> names of the leaves the loss cannot move       (optional)

``block`` holds ``x``, ``senders``, ``receivers``, ``shifts``, ``graph`` (the
filling's atoms are graph ``G``, one past the real ones), ``atom`` and ``edge``
(1.0 on real atoms / edges, 0.0 on the filling) and, concatenated and
zero-filled like the rest, whatever ``extras`` made for each graph (named
``node_<name>`` for a leading dimension of atoms, ``edge_<name>`` of edges).
``stats`` is the flat dict of batch statistics before the step; the second
answer is the same dict after it.

The filling needs nothing of the architecture but that it honours the masks
where atoms meet: a filling atom has no neighbour but itself (its edges are
self-loops ``2 x radius`` long), belongs to a graph of its own that the loss
leaves out, and its force is left out by ``atom``. The loss, AdamW and the
norms that are compared are ``mlip.py``'s own, imported from the file beside
this one. ``follow`` is handed the statistics' initial values (``stats0``)
and answers a fourth dict, ``stats_norm``: each statistic's norm after the
last step (``lib/check.py``); a caller that hands none (``tools/controls.py``)
gets the architecture's ``initial_stats``.

Leaves the loss cannot move. A bias added just before a batch norm, or to
every key of a softmax alike, has a gradient of exactly zero in exact
arithmetic: what either side computes for it is rounding (1e-7 of its
neighbours'), and Adam, which divides a gradient by its own size, turns that
rounding into a step as long as any other leaf's. Such a leaf's CHANGE is
noise on both sides, so ``change_norm`` leaves out the leaves the architecture
names (``unmoved``), each only after the reference's own first gradient shows
it under ``UNMOVED`` of the median leaf's: a leaf named wrongly stays in. They
stay in ``grad_norm``, whose gaps are taken against the median leaf's norm:
a program that DID move them would be over its limit there.
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


UNMOVED = 1e-3  # of the median leaf's first-gradient norm

mlip = _beside("mlip")
adamw_update, leaf_norms = mlip.adamw_update, mlip.leaf_norms  # what ``tools/leaf.py`` asks of an objective


def one_shape(steps) -> tuple[int, int, int]:
    """(atoms, edges, graphs) that hold every compared step and at least one
    filling atom (the filling's edges need an atom to loop on)."""
    atoms = max(sum(len(g["z"]) for sb in step for g in sb) for step in steps)
    edges = max(sum(len(g["senders"]) for sb in step for g in sb) for step in steps)
    graphs = max(sum(len(sb) for sb in step) for step in steps)
    return -(-(atoms + 1) // 8) * 8, edges, graphs


def block_of(graphs: list[dict], input_scale: float, shape, far: float, extras=None) -> dict:
    """The step's real graphs as one block of ``shape`` (numpy, host)."""
    n_atoms, n_edges, n_graphs = shape
    b = mlip.concat(graphs, input_scale)
    n, e, g = len(b["x"]), len(b["senders"]), len(graphs)
    more_n, more_e, more_g = n_atoms - n, n_edges - e, n_graphs - g
    loops = n + np.arange(more_e, dtype=np.int32) % max(more_n, 1)
    shift = np.zeros((more_e, 3), np.float32)
    shift[:, 0] = far
    zeros = lambda *s: np.zeros(s, np.float32)
    out = {
        "x": np.concatenate([b["x"], zeros(more_n, b["x"].shape[1])]),
        "pos": np.concatenate([b["pos"], zeros(more_n, 3)]),
        "senders": np.concatenate([b["senders"], loops]),
        "receivers": np.concatenate([b["receivers"], loops]),
        "shifts": np.concatenate([b["shifts"], shift]),
        "graph": np.concatenate([b["graph"], np.full(more_n, n_graphs, np.int32)]),
        "atom": np.concatenate([np.ones(n, np.float32), zeros(more_n)]),
        "edge": np.concatenate([np.ones(e, np.float32), zeros(more_e)]),
        "real_graph": np.concatenate([np.ones(g, np.float32), zeros(more_g)]),
        "n_atoms": np.concatenate([b["n_atoms"], np.ones(more_g, np.float32)]),
        "energy": np.concatenate([b["energy"], zeros(more_g)]),
        "forces": np.concatenate([b["forces"], zeros(more_n, 3)]),
    }
    if extras is not None:
        made = [extras(graph) for graph in graphs]
        for name in made[0]:
            rows = np.concatenate([m[name] for m in made]).astype(np.float32)
            kind, more = ("node", more_n) if len(rows) == n else ("edge", more_e)
            out[f"{kind}_{name}"] = np.concatenate([rows, zeros(more, rows.shape[1])])
    return out


@functools.partial(jax.jit, static_argnums=(0, 1))
def _step_terms(node_energy, hp, params, stats, b, inv_graphs, inv_force_rows):
    """(L, dL/dparams, statistics after the step) of one step's block:
    ``mlip.py``'s ``_block_terms`` with the filling left out, the statistics
    threaded through the position gradient as an auxiliary answer."""
    d = dict(hp)
    n_graphs = b["energy"].shape[0]

    def loss_of(p):
        def total(pos):
            e_node, new_stats = node_energy(p, d, b, pos, stats,
                                            matmul=mlip.MATMUL[d.get("emulate", "")])
            e_graph = jax.ops.segment_sum(e_node * b["atom"], b["graph"], n_graphs + 1)[:n_graphs]
            return e_graph.sum(), (e_graph, new_stats)

        (_, (e_graph, new_stats)), de_dpos = jax.value_and_grad(total, has_aux=True)(b["pos"])
        err = (e_graph - b["energy"]) * b["real_graph"]
        loss = (
            d["energy_weight"] * (err ** 2).sum() * inv_graphs
            + d["energy_peratom_weight"] * ((err / b["n_atoms"]) ** 2).sum() * inv_graphs
            + d["force_weight"] * (((-de_dpos - b["forces"]) ** 2) * b["atom"][:, None]).sum()
            * inv_force_rows
        )
        return loss, new_stats

    (loss, new_stats), grad = jax.value_and_grad(loss_of, has_aux=True)(params)
    return loss, grad, new_stats


def follow(node_energy, hp: dict, opt: dict, params0: dict, steps, input_scale: float,
           stats0: dict | None = None) -> dict:
    """``mlip.follow`` a step at a time, the statistics carried from step to
    step: each step's loss, the per-leaf norm of the first gradient, of the
    parameters' change and of every statistic after the last step. A step is
    ONE sub-batch (one device): the statistics of a mesh step are another
    rule, which comes with the cell that needs it."""
    if any(len(sub_batches) != 1 for sub_batches in steps):
        raise NotImplementedError("batch statistics over several sub-batches of a step")
    shape = one_shape(steps)
    hp_static = tuple(sorted(hp.items()))
    extras = getattr(node_energy, "extras", None)
    extras = functools.partial(extras, hp=hp) if extras is not None else None
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params0.items()}
    if stats0 is None:
        stats0 = node_energy.initial_stats(params0)
    stats = {k: jnp.asarray(v, jnp.float32) for k, v in stats0.items()}
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for t, (graphs,) in enumerate(steps, start=1):
        b = block_of(graphs, input_scale, shape, far=2.0 * hp["radius"], extras=extras)
        loss, grad, stats = _step_terms(
            node_energy, hp_static, params, stats, {k: jnp.asarray(a) for k, a in b.items()},
            jnp.float32(1.0 / len(graphs)),
            jnp.float32(1.0 / (3.0 * sum(len(g["z"]) for g in graphs))))
        losses.append(float(loss))
        if first_grad is None:
            first_grad = leaf_norms(grad)
        params, m, v = adamw_update(params, grad, m, v, t, opt["learning_rate"],
                                    opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"])
    floor = UNMOVED * float(np.median(list(first_grad.values())))
    named = getattr(node_energy, "unmoved", lambda hp: [])(hp)
    unmoved = {k for k in named if first_grad[k] < floor}
    print(f"reference: {len(unmoved)} of the {len(named)} leaves the architecture names as ones "
          f"the loss cannot move have a first gradient under {floor:.1e} and are left out of "
          "change_norm", flush=True)
    change = leaf_norms({k: params[k] - jnp.asarray(params0[k], jnp.float32)
                         for k in params if k not in unmoved})
    return {"losses": losses, "grad_norm": first_grad, "change_norm": change,
            "stats_norm": leaf_norms(stats)}
