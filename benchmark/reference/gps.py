"""Plain reference: GPS (Rampasek et al., arXiv:2205.12454) round an EGNN
conv, as HydraGNN's ``globalAtt/gps.py`` wraps its stacks; one node energy
per atom. ``jax.numpy`` in float32, a Python loop over the layers, attention
as the flat ``[N, N]`` masked softmax, batch norm with explicit masked
moments: no scan, no dense per-graph blocks, no kernel. Imports nothing of
the program.

    x0      = W_l [W_n x ; W_p PE]                                        once, no bias
    e_ij    = W_r relPE_ij                                                each layer, no bias
    h_loc   = BN1( EGNN(x, pos, e) + x )                                  coordinates move
    h_att   = BN2( W_o softmax_{j in g(i)} (q_i . k_j / sqrt(d)) v_j + x )
    y       = h_loc + h_att;   x' = act( BN3( y + W_2 act(W_1 y) ) )
    E_i     = MLP(x_L)_i

The EGNN layer is ``reference/egnn.py``'s with an edge attribute in phi_e's
input (HydraGNN's E_GCL: ``[h_s, h_r, d, e]``); see that file for its
departures from the paper (distance not squared, mean coordinate update,
messages summed at the sender, no update in the last layer). The trailing
``act`` is the stack's, after every layer. BN is training-mode batch norm
over the REAL atoms of the step (biased variance, eps 1e-5), and its running
moments move by 0.1 of the way to the batch's: they are the statistics the
comparison reads, threaded through ``node_energy`` as a flat dict.

Laplacian encodings (``encodings``): the ``pe_dim`` lowest non-trivial
eigenvectors of I - D^-1/2 A D^-1/2 of the structure's neighbour graph (A
symmetrised, 0/1), columns of zeros where the structure has too few atoms.
An eigenvector's sign is free; the rule (``fix_signs``): among the entries
whose magnitude is within 1e-6, relative, of the vector's largest, the entry
of the lowest atom index is positive. The program states the same rule in
``preprocess/encodings.py``; ``tests/test_gps_reference.py`` holds them
together. Relative encodings are ``|pe_i - pe_j|`` on each edge.

Protocol: ``reference/mlip_batch.py`` (a model with batch statistics takes a
step as one block, with masks for the filling).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ACT = {"silu": jax.nn.silu, "relu": jax.nn.relu, "tanh": jnp.tanh, "gelu": jax.nn.gelu}
BN_STEP, BN_EPS = 0.1, 1e-5
SIGN_TIE = 1e-6


def hyperparameters(config: dict) -> dict:
    arch = config["NeuralNetwork"]["Architecture"]
    return {
        "layers": int(arch["num_conv_layers"]),
        "heads": int(arch["global_attn_heads"]),
        "pe_dim": int(arch["pe_dim"]),
        "radius": float(arch["radius"]),
        "activation": arch["activation_function"],
        "coordinate_updates": bool(arch.get("equivariance")),
        "head_layers": int(arch["output_heads"]["node"]["num_headlayers"]),
        "energy_weight": float(arch.get("energy_weight", 0.0)),
        "energy_peratom_weight": float(arch.get("energy_peratom_weight", 0.0)),
        "force_weight": float(arch.get("force_weight", 0.0)),
    }


# -- encodings (numpy, host) -----------------------------------------------------------

def fix_signs(vectors: np.ndarray) -> np.ndarray:
    out = np.array(vectors, copy=True)
    for j in range(out.shape[1]):
        mag = np.abs(out[:, j])
        if mag.max(initial=0.0) > 0.0:
            first = int(np.flatnonzero(mag >= mag.max() * (1.0 - SIGN_TIE))[0])
            if out[first, j] < 0.0:
                out[:, j] *= -1.0
    return out


def encodings(graph: dict, hp: dict) -> dict:
    """{"pe": [n, k], "rel_pe": [e, k]} of one structure."""
    n, k = len(graph["z"]), int(hp["pe_dim"])
    adj = np.zeros((n, n))
    adj[graph["senders"], graph["receivers"]] = 1.0
    adj = np.maximum(adj, adj.T)
    scale = 1.0 / np.sqrt(np.maximum(adj.sum(axis=1), 1e-12))
    lap = np.eye(n) - scale[:, None] * adj * scale[None, :]
    values, vectors = np.linalg.eigh(lap)
    pe = vectors[:, np.argsort(values)[1:k + 1]]
    pe = np.pad(pe, ((0, 0), (0, k - pe.shape[1])))
    pe = fix_signs(pe).astype(np.float32)
    return {"pe": pe, "rel_pe": np.abs(pe[graph["senders"]] - pe[graph["receivers"]])}


# -- the model ---------------------------------------------------------------------------

def _dense_with(params, name, x, bias=True, matmul=jnp.matmul):
    y = matmul(x, params[f"{name}/kernel"])
    return y + params[f"{name}/bias"] if bias else y


def _batch_norm(params, stats, new_stats, name, x, atom):
    """Training-mode batch norm over the rows ``atom`` marks; records the
    moved running moments in ``new_stats``."""
    count = jnp.maximum(atom.sum(), 1.0)
    mean = (x * atom[:, None]).sum(axis=0) / count
    var = (((x - mean) ** 2) * atom[:, None]).sum(axis=0) / count
    for key, value in (("mean", mean), ("var", var)):
        old = stats[f"{name}/{key}"]
        new_stats[f"{name}/{key}"] = old + BN_STEP * (value - old)
    return (x - mean) / jnp.sqrt(var + BN_EPS) * params[f"{name}/scale"] + params[f"{name}/bias"]


def node_energy(params, hp, block, pos, stats, matmul=jnp.matmul):
    _dense = functools.partial(_dense_with, matmul=matmul)
    act = ACT[hp["activation"]]
    senders, receivers, atom = block["senders"], block["receivers"], block["atom"]
    n = atom.shape[0]
    heads = hp["heads"]
    new_stats = {}
    # a key may be attended by a query of the same graph, if it is a real atom
    allowed = (block["graph"][:, None] == block["graph"][None, :]) & (atom[None, :] > 0)
    # a filling atom's row has no key: its weights are 0 over 1, not 0 over 0
    unattended = 1.0 - jnp.any(allowed, axis=-1, keepdims=True).astype(jnp.float32)[None]

    h = jnp.concatenate([_dense(params, "node_emb", block["x"], bias=False),
                         _dense(params, "pos_emb", block["node_pe"], bias=False)], axis=-1)
    h = _dense(params, "node_lin", h, bias=False)
    for layer in range(hp["layers"]):
        p = f"graph_convs_{layer}"
        # local: EGNN with the embedded relative encodings as edge attribute
        e = _dense(params, f"{p}/rel_pos_emb", block["edge_rel_pe"], bias=False)
        vec = pos[receivers] - pos[senders] + block["shifts"]
        dist = jnp.sqrt(jnp.sum(vec * vec, axis=-1, keepdims=True) + 1e-18)
        m = jnp.concatenate([h[senders], h[receivers], dist, e], axis=-1)
        m = act(_dense(params, f"{p}/local/edge_mlp/dense_0", m))
        m = act(_dense(params, f"{p}/local/edge_mlp/dense_1", m))
        if hp["coordinate_updates"] and layer < hp["layers"] - 1:
            gate = jax.nn.relu(_dense(params, f"{p}/local/coord_mlp_mlp_0", m))
            gate = jnp.tanh(_dense(params, f"{p}/local/coord_mlp_mlp_out", gate, bias=False))
            move = jnp.clip(vec / (dist + 1.0) * gate, -100.0, 100.0) * block["edge"][:, None]
            degree = jax.ops.segment_sum(block["edge"], senders, n)
            pos = pos + jax.ops.segment_sum(move, senders, n) / jnp.maximum(degree, 1.0)[:, None]
        agg = jax.ops.segment_sum(m * block["edge"][:, None], senders, n)
        local = jnp.concatenate([h, agg], axis=-1)
        local = act(_dense(params, f"{p}/local/node_mlp/dense_0", local))
        local = _dense(params, f"{p}/local/node_mlp/dense_1", local)
        local = _batch_norm(params, stats, new_stats, f"{p}/norm1", local + h, atom)
        # global: softmax attention over the atoms of the same structure
        q, k, v = (_dense(params, f"{p}/attn/{name}", h).reshape(n, heads, -1)
                   for name in ("q", "k", "v"))
        logits = jnp.einsum("nhd,mhd->hnm", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
        logits = jnp.where(allowed[None], logits, -1e30)
        top = jnp.max(logits, axis=-1, keepdims=True)
        weight = jnp.where(allowed[None], jnp.exp(logits - top), 0.0)
        weight = weight / (weight.sum(axis=-1, keepdims=True) + unattended)
        att = jnp.einsum("hnm,mhd->nhd", weight, v).reshape(n, -1)
        att = _batch_norm(params, stats, new_stats, f"{p}/norm2",
                          _dense(params, f"{p}/attn/out", att) + h, atom)
        # feed-forward on the sum
        y = local + att
        y = y + _dense(params, f"{p}/mlp_1", act(_dense(params, f"{p}/mlp_0", y)))
        h = act(_batch_norm(params, stats, new_stats, f"{p}/norm3", y, atom))
    for i in range(hp["head_layers"]):
        h = act(_dense(params, f"head0_branch-0/dense_{i}", h))
    return _dense(params, f"head0_branch-0/dense_{hp['head_layers']}", h)[:, 0], new_stats


def initial_stats(params: dict) -> dict:
    """What a fresh model's batch norms start from: mean 0, variance 1."""
    out = {}
    for name, value in params.items():
        if "/norm" in name and name.endswith("/scale"):
            out[name[:-len("scale")] + "mean"] = np.zeros(np.shape(value), np.float32)
            out[name[:-len("scale")] + "var"] = np.ones(np.shape(value), np.float32)
    return out


def unmoved(hp: dict) -> list:
    """The leaves whose gradient is zero in exact arithmetic: the biases added
    just before a batch norm takes the mean away (the local conv's last, the
    attention's ``out``, the feed-forward's last), the value bias (the weights
    of a row sum to 1, so it is one more such constant) and the key bias (the
    same number added to every logit of a row)."""
    return [f"graph_convs_{layer}/{leaf}/bias" for layer in range(hp["layers"])
            for leaf in ("local/node_mlp/dense_1", "attn/k", "attn/v", "attn/out", "mlp_1")]


node_energy.extras = encodings
node_energy.initial_stats = initial_stats
node_energy.unmoved = unmoved
