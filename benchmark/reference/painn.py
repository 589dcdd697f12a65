"""Plain reference: PaiNN (Schuett, Unke, Gastegger, arXiv:2102.03150) as
HydraGNN runs it, one node energy per atom.

Paper, eq. 7-9: message  ds_i = sum_j phi_s(s_j) o W_s(r_ij),
                         dv_i = sum_j v_j o phi_vv(s_j) o W_vv(r_ij)
                                + phi_vs(s_j) o W_vs(r_ij) r_ij/|r_ij|;
                update   dv_i = a_vv o U v_i,
                         ds_i = a_sv o <U v_i, V v_i> + a_ss,
                         a = MLP([s_i, ||V v_i||]);
radial filters W(r) = Linear(sin(n pi r / r_c) / r) f_cut(r), cosine cutoff.

Departures HydraGNN's PAINNStack makes (each followed here):
  - no embedding of the atomic number: the first block runs at the width of
    the raw node feature (1) and a Linear-tanh-Linear lifts s to F after it,
    as after every block; v gets a bias-free Linear after every block but
    the last, whose vector update is dropped;
  - the "other end" of an edge is its receiver, messages are summed at its
    sender, the stack applies the activation to s after every block;
  - the update MLP's input is [||V v||, s], the norm taken with +1e-16;
  - the head is an MLP on the last s, one energy per node.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

ACT = {"silu": jax.nn.silu, "relu": jax.nn.relu, "tanh": jnp.tanh, "gelu": jax.nn.gelu}


def hyperparameters(config: dict) -> dict:
    arch = config["NeuralNetwork"]["Architecture"]
    return {
        "layers": int(arch["num_conv_layers"]),
        "activation": arch["activation_function"],
        "num_radial": int(arch["num_radial"]),
        "cutoff": float(arch["radius"]),
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
    n, rc = x.shape[0], hp["cutoff"]
    vec = pos[receivers] - pos[senders] + shifts
    dist = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + 1e-18)
    unit = vec / dist[:, None]
    k = jnp.arange(1, hp["num_radial"] + 1, dtype=jnp.float32)
    rbf = jnp.sin(k * math.pi * dist[:, None] / rc) / dist[:, None]
    cut = jnp.where(dist <= rc, 0.5 * (jnp.cos(dist * math.pi / rc) + 1.0), 0.0)

    s = x
    v = jnp.zeros((n, 3, x.shape[-1]), jnp.float32)
    for layer in range(hp["layers"]):
        p = f"graph_convs_{layer}"
        last = layer == hp["layers"] - 1
        # message
        w = _dense(params, f"{p}/message/filter_layer", rbf) * cut[:, None]
        phi = _dense(params, f"{p}/message/scalar_mlp_0", s)
        phi = _dense(params, f"{p}/message/scalar_mlp_1", jax.nn.silu(phi))
        gate_v, gate_r, msg_s = jnp.split(w * phi[receivers], 3, axis=-1)
        msg_v = v[receivers] * gate_v[:, None, :] + gate_r[:, None, :] * unit[:, :, None]
        s = s + jax.ops.segment_sum(msg_s, senders, n)
        v = v + jax.ops.segment_sum(msg_v, senders, n)
        # update
        uv = _dense(params, f"{p}/update/update_U", v, bias=False)
        vv = _dense(params, f"{p}/update/update_V", v, bias=False)
        a = jnp.concatenate([jnp.sqrt(jnp.sum(vv * vv, axis=1) + 1e-16), s], axis=-1)
        a = _dense(params, f"{p}/update/update_mlp_0", a)
        a = _dense(params, f"{p}/update/update_mlp_1", jax.nn.silu(a))
        inner = jnp.sum(uv * vv, axis=1)
        if last:
            a_sv, a_ss = jnp.split(a, 2, axis=-1)
        else:
            a_vv, a_sv, a_ss = jnp.split(a, 3, axis=-1)
            v = v + a_vv[:, None, :] * uv
        s = s + a_sv * inner + a_ss
        # lift to the block's output width
        s = _dense(params, f"{p}/node_embed_1", jnp.tanh(_dense(params, f"{p}/node_embed_0", s)))
        if not last:
            v = _dense(params, f"{p}/vec_embed", v, bias=False)
        s = act(s)  # the stack's activation
    for i in range(hp["head_layers"]):
        s = act(_dense(params, f"head0_branch-0/dense_{i}", s))
    return _dense(params, f"head0_branch-0/dense_{hp['head_layers']}", s)[:, 0]
