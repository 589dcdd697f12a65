"""Plain reference: SchNet (Schuett, Kindermans, Sauceda, Chmiela, Tkatchenko,
Mueller, arXiv:1706.08566; J. Chem. Phys. 148, 241722) as HydraGNN runs it,
one node energy per atom.

Published interaction block, edge ji = (j -> i), d = |pos_i - pos_j + shift|,
c the cutoff, G Gaussians Delta = c / (G - 1) apart, ssp(x) = ln(1/2 e^x + 1/2):

    g_k(d) = exp(-(d - k Delta)^2 / 2 Delta^2)                  k = 0..G-1
    W_ji   = (W_f2 ssp(W_f1 g(d) + b_f1) + b_f2) 1/2 (cos(pi d / c) + 1)
    m_i    = sum_{j -> i} (W_1 x_j) o W_ji                      W_1 without bias
    x_i   <- x_i + W_3 ssp(W_2 m_i + b_2) + b_3
    E      = sum_i w_2 . ssp(W_r x_i + b_r)

Departures HydraGNN's SCFStack makes (it wraps PyG's ``CFConv`` alone; each
followed here):
  - no embedding of the atomic number: the first layer's W_1 maps the raw node
    feature (width 1) to the filters;
  - no W_3 and no residual: a layer is x_i <- act(W_2 m_i + b_2), the stack's
    activation applied after every layer (the configuration's, ssp here);
  - the head is an MLP on the last x with the same activation, one energy per
    node, summed to the structure's energy by the objective.

Straightforward ``jax.numpy`` in float32: a gather, a multiply, a
``jax.ops.segment_sum``; no kernel, no fused operation, no padding, nothing of
the program imported. An edge longer than the cutoff weighs exactly zero
(``reference/mlip_padded.py`` fills graphs with such edges).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def ssp(x):
    return jnp.logaddexp(x, 0.0) - math.log(2.0)


ACT = {"shifted_softplus": ssp, "ssp": ssp, "silu": jax.nn.silu, "relu": jax.nn.relu,
       "tanh": jnp.tanh}


def hyperparameters(config: dict) -> dict:
    arch = config["NeuralNetwork"]["Architecture"]
    return {
        "layers": int(arch["num_conv_layers"]),
        "activation": arch["activation_function"],
        "num_gaussians": int(arch["num_gaussians"]),
        "cutoff": float(arch["radius"]),
        "max_neighbours": int(arch["max_neighbours"]),
        "head_layers": int(arch["output_heads"]["node"]["num_headlayers"]),
        "energy_weight": float(arch.get("energy_weight", 0.0)),
        "energy_peratom_weight": float(arch.get("energy_peratom_weight", 0.0)),
        "force_weight": float(arch.get("force_weight", 0.0)),
    }


def node_energy(params, hp, x, pos, senders, receivers, shifts, matmul=jnp.matmul):
    def dense(name, h, bias=True):
        y = matmul(h, params[f"{name}/kernel"])
        return y + params[f"{name}/bias"] if bias else y

    act = ACT[hp["activation"]]
    n, c, g = x.shape[0], hp["cutoff"], hp["num_gaussians"]
    vec = pos[receivers] - pos[senders] + shifts
    d = jnp.sqrt(jnp.sum(vec * vec, axis=-1))
    centres = jnp.arange(g, dtype=jnp.float32) * (c / (g - 1))
    smeared = jnp.exp(-0.5 * ((d[:, None] - centres[None, :]) / (c / (g - 1))) ** 2)
    # hp["no_cutoff"]: a control for the benchmark's limits, never a run's
    window = jnp.where(d <= c, 0.5 * (jnp.cos(d * math.pi / c) + 1.0), 0.0)
    if hp.get("no_cutoff"):
        window = jnp.where(d <= c, 1.0, 0.0)

    h = x
    for layer in range(hp["layers"]):
        p = f"graph_convs_{layer}"
        w = dense(f"{p}/filter2", ssp(dense(f"{p}/filter1", smeared))) * window[:, None]
        messages = dense(f"{p}/lin1", h, bias=False)[senders] * w
        h = act(dense(f"{p}/lin2", jax.ops.segment_sum(messages, receivers, n)))
    for i in range(hp["head_layers"]):
        h = act(dense(f"head0_branch-0/dense_{i}", h))
    return dense(f"head0_branch-0/dense_{hp['head_layers']}", h)[:, 0]
