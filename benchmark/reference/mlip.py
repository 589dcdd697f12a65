"""Plain reference for energy-and-force training, shared by the architectures.

``jax.numpy`` in float32, no kernels, no padding, no buckets: the real graphs
of a sub-batch are concatenated and nothing else is in the arrays. Imports
nothing of the program. An architecture file beside this one supplies
``node_energy(params, hp, x, pos, senders, receivers, shifts) -> [N]``.

What is followed (HydraGNN's ``energy_force_loss`` and its mesh step):

    E_g = sum of node energies of graph g;  F = -dE/dpos
    L_d = w_E mean_g (E_g - E*_g)^2 + w_Ea mean_g ((E_g - E*_g)/n_g)^2
          + w_F mean_{atoms, xyz} (F - F*)^2          for sub-batch d
    L   = sum_d G_d L_d / sum_d G_d                   over the step's sub-batches
    AdamW on dL/dparams (optax.adamw: bias-corrected moments, decoupled decay).

A sub-batch is processed in blocks of whole graphs so that the reference fits
beside nothing: L_d is a sum over graphs, so blocks add up exactly. Not so for
a model with batch statistics: they couple the graphs of a step, so an
objective file for such a model may not split a sub-batch into blocks (it is
handed ``stats0`` and answers ``stats_norm``; it comes with the configuration
that needs it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _matmul_passes(passes: int):
    """A float32 product as the TPU's MXU makes it from bfloat16 passes, for
    where no TPU is (the control kept as a CPU test): operands split into a
    bfloat16 head and a bfloat16 remainder; one pass multiplies the heads,
    three add the two cross terms (``high``). Products of bfloat16 values are
    exact in float32, so only the accumulation order differs from the chip."""

    def matmul(x, w):
        xh, wh = _bf16(x), _bf16(w)
        out = jnp.matmul(xh, wh, precision="highest")
        if passes == 3:
            out = out + jnp.matmul(xh, _bf16(w - wh), precision="highest") \
                + jnp.matmul(_bf16(x - xh), wh, precision="highest")
        return out

    return matmul


# hp["emulate"]: "" = the ambient jax_default_matmul_precision (what a run uses)
MATMUL = {"": jnp.matmul, "high": _matmul_passes(3), "default": _matmul_passes(1)}


def concat(graphs: list[dict], input_scale: float) -> dict:
    """Real graphs -> one set of arrays (numpy, host)."""
    n = np.array([len(g["z"]) for g in graphs])
    off = np.concatenate([[0], np.cumsum(n)[:-1]])
    return {
        "x": np.concatenate([g["z"] for g in graphs]).astype(np.float32)[:, None] * np.float32(input_scale),
        "pos": np.concatenate([g["pos"] for g in graphs]).astype(np.float32),
        "senders": np.concatenate([g["senders"] + o for g, o in zip(graphs, off)]).astype(np.int32),
        "receivers": np.concatenate([g["receivers"] + o for g, o in zip(graphs, off)]).astype(np.int32),
        "shifts": np.concatenate([g["shifts"] for g in graphs]).astype(np.float32),
        "graph": np.repeat(np.arange(len(graphs)), n).astype(np.int32),
        "n_atoms": n.astype(np.float32),
        "energy": np.array([g["energy"] for g in graphs], np.float32),
        "forces": np.concatenate([g["forces"] for g in graphs]).astype(np.float32),
    }


def blocks(graphs: list[dict], max_atoms: int) -> list[list[dict]]:
    """Whole graphs, in order, at most ``max_atoms`` atoms to a block."""
    out, cur, tot = [], [], 0
    for g in graphs:
        if cur and tot + len(g["z"]) > max_atoms:
            out.append(cur)
            cur, tot = [], 0
        cur.append(g)
        tot += len(g["z"])
    if cur:
        out.append(cur)
    return out


@functools.partial(jax.jit, static_argnums=(0, 1))
def _block_terms(node_energy, hp, params, b, inv_graphs, inv_force_rows):
    """One block's share of L_d and of dL_d/dparams."""

    def share(p):
        def total(pos):
            e_node = node_energy(p, dict(hp), b["x"], pos, b["senders"],
                                 b["receivers"], b["shifts"],
                                 matmul=MATMUL[dict(hp).get("emulate", "")])
            e_graph = jax.ops.segment_sum(e_node, b["graph"], b["energy"].shape[0])
            return e_graph.sum(), e_graph

        (_, e_graph), de_dpos = jax.value_and_grad(total, has_aux=True)(b["pos"])
        d = dict(hp)
        err = e_graph - b["energy"]
        return (
            d["energy_weight"] * (err ** 2).sum() * inv_graphs
            + d["energy_peratom_weight"] * ((err / b["n_atoms"]) ** 2).sum() * inv_graphs
            + d["force_weight"] * ((-de_dpos - b["forces"]) ** 2).sum() * inv_force_rows
        )

    return jax.value_and_grad(share)(params)


def step_loss_and_grad(node_energy, hp: dict, params: dict, sub_batches: list[list[dict]],
                       input_scale: float, max_atoms: int = 2048):
    """(L, dL/dparams) of one step over its sub-batches (one per device)."""
    hp_static = tuple(sorted(hp.items()))
    total_graphs = float(sum(len(sb) for sb in sub_batches))
    loss = 0.0
    grad = jax.tree.map(jnp.zeros_like, params)
    for sb in sub_batches:
        weight = len(sb) / total_graphs
        inv_graphs = weight / len(sb)
        inv_rows = weight / (3.0 * sum(len(g["z"]) for g in sb))
        for blk in blocks(sb, max_atoms):
            b = {k: jnp.asarray(v) for k, v in concat(blk, input_scale).items()}
            l, g = _block_terms(node_energy, hp_static, params, b,
                                jnp.float32(inv_graphs), jnp.float32(inv_rows))
            loss = loss + l
            grad = jax.tree.map(jnp.add, grad, g)
    return loss, grad


def adamw_update(params, grad, m, v, t: int, lr, b1, b2, eps, weight_decay):
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grad)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grad)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    new = jax.tree.map(
        lambda p, a, s: p - lr * ((a / c1) / (jnp.sqrt(s / c2) + eps) + weight_decay * p),
        params, m, v)
    return new, m, v


def leaf_norms(tree: dict) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


def follow(node_energy, hp: dict, opt: dict, params0: dict, steps: list[list[list[dict]]],
           input_scale: float, max_atoms: int = 2048) -> dict:
    """Train through ``steps`` (each a list of sub-batches of graph dicts)
    from ``params0`` (flat dict). Returns each step's loss, the per-leaf norm
    of the first gradient and of the parameters' change after the last step."""
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params0.items()}
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for t, sub_batches in enumerate(steps, start=1):
        loss, grad = step_loss_and_grad(node_energy, hp, params, sub_batches,
                                        input_scale, max_atoms)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = leaf_norms(grad)
        params, m, v = adamw_update(params, grad, m, v, t, opt["learning_rate"],
                                    opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"])
    change = leaf_norms({k: params[k] - jnp.asarray(params0[k], jnp.float32) for k in params})
    return {"losses": losses, "grad_norm": first_grad, "change_norm": change}
