"""Plain reference: EGNN (Satorras, Hoogeboom, Welling, arXiv:2102.09844) as
HydraGNN runs it, one node energy per atom.

Paper, eq. 3-6, per layer l:
    m_ij    = phi_e(h_i, h_j, ||x_i - x_j||^2, a_ij)
    x_i    += C sum_j (x_i - x_j) phi_x(m_ij)
    h_i     = phi_h(h_i, sum_j m_ij)

Departures HydraGNN's EGCLStack makes (each followed here, since the program
is what is compared):
  - phi_e takes the distance, not its square, and no edge attribute a_ij;
  - the difference vector is pos[receiver] - pos[sender] + shift, normalised
    by (distance + 1); phi_x ends in a bias-free linear layer then tanh, the
    product is clipped to +-100, and the update is the MEAN over a sender's
    edges (C = 1 / degree); no update in the last layer;
  - messages are summed at the edge's sender;
  - phi_h has no residual connection, and the stack applies the activation
    to h after every layer;
  - h_0 is the raw node feature (no embedding layer); the head is an MLP on
    the last h, one energy per node.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ACT = {"silu": jax.nn.silu, "relu": jax.nn.relu, "tanh": jnp.tanh, "gelu": jax.nn.gelu}


def hyperparameters(config: dict) -> dict:
    arch = config["NeuralNetwork"]["Architecture"]
    return {
        "layers": int(arch["num_conv_layers"]),
        "activation": arch["activation_function"],
        "coordinate_updates": bool(arch.get("equivariance")),
        "head_layers": int(arch["output_heads"]["node"]["num_headlayers"]),
        "energy_weight": float(arch.get("energy_weight", 0.0)),
        "energy_peratom_weight": float(arch.get("energy_peratom_weight", 0.0)),
        "force_weight": float(arch.get("force_weight", 0.0)),
    }


def _dense_with(params, name, x, bias=True, matmul=jnp.matmul):
    y = matmul(x, params[f"{name}/kernel"])
    return y + params[f"{name}/bias"] if bias else y


def node_energy(params, hp, x, pos, senders, receivers, shifts, matmul=jnp.matmul):
    _dense = functools.partial(_dense_with, matmul=matmul)
    act = ACT[hp["activation"]]
    n = x.shape[0]
    h = x
    for layer in range(hp["layers"]):
        p = f"graph_convs_{layer}"
        vec = pos[receivers] - pos[senders] + shifts
        dist = jnp.sqrt(jnp.sum(vec * vec, axis=-1, keepdims=True) + 1e-18)
        m = jnp.concatenate([h[senders], h[receivers], dist], axis=-1)
        m = act(_dense(params, f"{p}/edge_mlp/dense_0", m))
        m = act(_dense(params, f"{p}/edge_mlp/dense_1", m))
        if hp["coordinate_updates"] and layer < hp["layers"] - 1:
            gate = jax.nn.relu(_dense(params, f"{p}/coord_mlp_mlp_0", m))
            gate = jnp.tanh(_dense(params, f"{p}/coord_mlp_mlp_out", gate, bias=False))
            move = jnp.clip(vec / (dist + 1.0) * gate, -100.0, 100.0)
            degree = jax.ops.segment_sum(jnp.ones_like(dist[:, 0]), senders, n)
            pos = pos + jax.ops.segment_sum(move, senders, n) / jnp.maximum(degree, 1.0)[:, None]
        agg = jax.ops.segment_sum(m, senders, n)
        h = jnp.concatenate([h, agg], axis=-1)
        h = act(_dense(params, f"{p}/node_mlp/dense_0", h))
        h = act(_dense(params, f"{p}/node_mlp/dense_1", h))  # the stack's activation
    for i in range(hp["head_layers"]):
        h = act(_dense(params, f"head0_branch-0/dense_{i}", h))
    return _dense(params, f"head0_branch-0/dense_{hp['head_layers']}", h)[:, 0]
