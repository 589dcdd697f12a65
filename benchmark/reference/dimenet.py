"""Plain reference: DimeNet++ (Gasteiger, Giri, Margraf, Guennemann,
arXiv:2011.14115; layer equations of DimeNet, arXiv:2003.03123) as HydraGNN
runs it, one node energy per atom.

With edge ji = (j -> i) (sender j, receiver i), vec_ji = pos_i - pos_j +
shift_ji, d = |vec|, c the cutoff, x = d/c, s = SiLU, z_ln the n-th root of
the spherical Bessel function j_l:

    u(x)         = 1/x + a x^(p-1) + b x^p + c x^(p+1)  (x < 1, else 0),  p = exponent + 1
    rbf_n(d)     = u(x) sin(f_n x)     f_n a parameter (n pi where training starts)
    sbf_ln(d, A) = u(x) j_l(z_ln x) / |j_{l+1}(z_ln)| P_l(cos A)      l < S, n <= R
    A_(kj,ji)    = atan2(|vec_ji x vec_ki|, vec_ji . vec_ki),   vec_ki = vec_kj + vec_ji
    x_e          = s(W [h_j | h_i | s(W_rbf rbf)])
    t_(kj,ji)    = s(W_down(s(W_kj x_kj) * W_rbf2 W_rbf1 rbf_kj)) * W_sbf2 W_sbf1 sbf(d_kj, A)
    x'_ji        = s(W_ji x_ji) + s(W_up sum_{kj -> ji} t);  residual, skip, residual layers
    h'_i         = W_out MLP(W_up' sum_{ji -> i} (W_g rbf_ji) * x'_ji)

Triplets: every pair of edges (kj, ji) with receiver(kj) = sender(ji) but the
exact reverse edge (k = i and shift_kj + shift_ji = 0): a k that is a periodic
image of i is a third atom and stays (Open Catalyst's rule).

Departures HydraGNN's DIMEStack makes from the paper (each followed here,
since the program is what is compared):
  - no atom-type embedding and no edge state carried between blocks: EVERY
    conv layer maps the incoming node features with a Linear, embeds the
    edges from them (x_e above), runs one interaction block and one output
    block, and hands NODE features on; the stack applies the activation to
    them after every layer;
  - rbf has no sqrt(2/c)/d prefactor of its own (the envelope's 1/x carries
    the 1/d; the constant goes into the next linear map); its frequencies are
    one trainable leaf, ``graph_convs_0/rbf/freq``, shared by all layers; P_l
    is the plain Legendre polynomial;
  - the head is an MLP on the last node features, one energy per node.

How the triplets are made here. ``reference/mlip.py`` hands this file edge
arrays only, under jit, so they are enumerated at STATIC shape from the edge
list, sharing nothing with ``hydragnn_tpu/graphs/triplets.py``: a table
``[N, K]`` of the edges each atom SENDS (K = the configuration's
``max_neighbours``; the corpus gives every atom exactly K, its incoming
edges are not capped), built by one sort. The partners ji of an edge kj are
the row ``table[receiver(kj)]``: a dense ``[E, K]`` block with a mask for
empty slots and for the exact reverse, summed onto ji with
``jax.ops.segment_sum``. An atom that sends more than K edges would lose
triplets silently: the result is multiplied by NaN instead.

Spherical Bessel functions. By closed forms in float32, not by recurrences
run on the values: for x >= l + 1 the finite sum j_l(x) = A_l(1/x) sin x +
B_l(1/x) cos x (A, B integer polynomials, Horner), for x < l + 1 the
ascending series x^l/(2l+1)!! sum_k (-x^2/2)^k / (k! (2l+3)...(2l+2k+1))
(24 terms). The sin/cos form alone loses digits at small argument and large
l (at l = 6, x = 2 its terms are 1e5 times their sum); the series has none
to lose there. At the crossover l + 1 the sin/cos terms are at most ~0.1
against values of ~0.1, so both hold float32's 1e-7 relative to the basis'
O(1) scale; the comparison's limits (1e-5 and up) leave two digits of room.
Roots and normalisers from scipy in float64, rounded once.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ACT = {"silu": jax.nn.silu, "swish": jax.nn.silu, "relu": jax.nn.relu, "tanh": jnp.tanh, "gelu": jax.nn.gelu}
SERIES_TERMS = 24


def hyperparameters(config: dict) -> dict:
    arch = config["NeuralNetwork"]["Architecture"]
    return {
        "layers": int(arch["num_conv_layers"]),
        "activation": arch["activation_function"],
        "num_radial": int(arch["num_radial"]),
        "num_spherical": int(arch["num_spherical"]),
        "envelope_exponent": int(arch["envelope_exponent"]),
        "cutoff": float(arch["radius"]),
        "max_neighbours": int(arch["max_neighbours"]),
        "before_skip": int(arch["num_before_skip"]),
        "after_skip": int(arch["num_after_skip"]),
        "output_layers": int(arch.get("num_output_layers") or 1),
        "head_layers": int(arch["output_heads"]["node"]["num_headlayers"]),
        "angle_blind": 0,  # 1: P_l = 1 for every l, the control of benchmark/tests
        "energy_weight": float(arch.get("energy_weight", 0.0)),
        "energy_peratom_weight": float(arch.get("energy_peratom_weight", 0.0)),
        "force_weight": float(arch.get("force_weight", 0.0)),
    }


# -- the basis ---------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def bessel_tables(num_spherical: int, num_radial: int):
    """(roots [S, R], 1/|j_{l+1}(root)| [S, R]) in float64, from scipy."""
    from scipy import optimize, special

    roots = np.zeros((num_spherical, num_radial))
    # the roots of j_l interlace those of j_{l-1}: bracket each from the row above
    prev = np.arange(1, num_radial + num_spherical + 1) * math.pi
    for l in range(num_spherical):
        if l:
            prev = np.array([optimize.brentq(lambda t: special.spherical_jn(l, t), a, b)
                             for a, b in zip(prev[:-1], prev[1:])])
        roots[l] = prev[:num_radial]
    norms = np.stack([1.0 / np.abs(special.spherical_jn(l + 1, roots[l]))
                      for l in range(num_spherical)])
    return roots, norms


@functools.lru_cache(maxsize=None)
def sincos_polynomials(l_max: int):
    """j_l(x) = A_l(u) sin x + B_l(u) cos x with u = 1/x: integer coefficient
    lists (lowest power first), from j_{l+1} = (2l + 1) u j_l - j_{l-1}
    carried out on the coefficients."""
    a = [np.array([0, 1]), np.array([0, 0, 1])]      # u ; u^2
    b = [np.array([0]), np.array([0, -1])]           # 0 ; -u
    for l in range(1, l_max):
        def step(p):
            nxt = (2 * l + 1) * np.concatenate([[0], p[l]])
            nxt[: len(p[l - 1])] -= p[l - 1]
            return nxt
        a.append(step(a))
        b.append(step(b))
    return a, b


def _horner(coefficients, u):
    out = jnp.zeros_like(u)
    for c in coefficients[::-1]:
        out = out * u + float(c)
    return out


def spherical_jn(l: int, x):
    """j_l(x), x > 0, float32: the closed forms of the docstring."""
    if l == 0:
        return jnp.sin(x) / x
    a, b = sincos_polynomials(l)
    big = jnp.maximum(x, l + 1.0)
    trig = _horner(a[l], 1.0 / big) * jnp.sin(big) + _horner(b[l], 1.0 / big) * jnp.cos(big)
    small = jnp.minimum(x, l + 1.0)
    term = small ** l / float(np.prod(np.arange(2 * l + 1, 0, -2)))
    series = term
    for k in range(1, SERIES_TERMS):
        term = term * (-0.5 * small * small) / (k * (2 * l + 2 * k + 1))
        series = series + term
    return jnp.where(x >= l + 1.0, trig, series)


def legendre(l: int, c):
    """P_l(c) from its power-series coefficients (Horner)."""
    return _horner(np.polynomial.legendre.leg2poly([0] * l + [1]), c)


def envelope(x, exponent: int):
    p = exponent + 1
    a, b, c = -(p + 1) * (p + 2) / 2.0, p * (p + 2.0), -p * (p + 1) / 2.0
    return jnp.where(x < 1.0, 1.0 / x + a * x ** (p - 1) + b * x ** p + c * x ** (p + 1), 0.0)


# -- triplets at static shape ---------------------------------------------------------

def sent_table(senders, num_nodes: int, k: int):
    """``[N, K]``: the edges each atom sends, -1 in empty slots; and whether
    any atom sends more than K."""
    e = senders.shape[0]
    order = jnp.argsort(senders, stable=True)
    counts = jax.ops.segment_sum(jnp.ones((e,), jnp.int32), senders, num_nodes)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(e) - starts[senders[order]]
    table = jnp.full((num_nodes, k), -1, jnp.int32)
    table = table.at[senders[order], rank].set(order.astype(jnp.int32), mode="drop")
    return table, jnp.any(counts > k)


def triplet_block(senders, receivers, shifts, num_nodes: int, k: int):
    """For every edge kj its K candidate partners ji (``[E, K]`` edge ids, 0
    where invalid) and the mask of the real ones."""
    table, over = sent_table(senders, num_nodes, k)
    ji = table[receivers]                                   # edges that start where kj ends
    filled = ji >= 0
    ji = jnp.where(filled, ji, 0)
    tol = 1e-4 * jnp.max(jnp.abs(shifts), initial=1e-30)
    returns = receivers[ji] == senders[:, None]             # k == i ...
    closes = jnp.max(jnp.abs(shifts[:, None, :] + shifts[ji]), axis=-1) <= tol  # ... same image
    return ji, filled & ~(returns & closes), over


# -- the model ------------------------------------------------------------------------

def _dense_with(params, name, x, bias=True, matmul=jnp.matmul):
    y = matmul(x, params[f"{name}/kernel"])
    return y + params[f"{name}/bias"] if bias else y


def node_energy(params, hp, x, pos, senders, receivers, shifts, matmul=jnp.matmul):
    act = ACT[hp["activation"]]
    silu = jax.nn.silu
    n, e, k = x.shape[0], senders.shape[0], hp["max_neighbours"]
    S, R, c = hp["num_spherical"], hp["num_radial"], hp["cutoff"]

    vec = pos[receivers] - pos[senders] + shifts
    d = jnp.sqrt(jnp.sum(vec * vec, axis=-1))
    xs = d / c
    env = envelope(xs, hp["envelope_exponent"])
    rbf = env[:, None] * jnp.sin(params["graph_convs_0/rbf/freq"] * xs[:, None])
    roots, norms = bessel_tables(S, R)
    radial = [env[:, None] * spherical_jn(l, jnp.float32(roots[l]) * xs[:, None])
              * jnp.float32(norms[l]) for l in range(S)]    # S x [E, R], on the edge kj

    ji, real, over = triplet_block(senders, receivers, shifts, n, k)
    vec_ji = vec[ji]                                        # [E, K, 3]
    vec_ki = vec[:, None, :] + vec_ji
    dot = jnp.where(real, jnp.sum(vec_ji * vec_ki, axis=-1), 1.0)
    cross = jnp.cross(vec_ji, vec_ki)
    # exactly collinear pairs exist (an atom, its own image and that image's
    # image): |cross| = 0 there, where sqrt has no derivative although P_l(cos A)
    # has one (0, since sin A = 0): the floor hands back that 0 instead of NaN
    norm = jnp.sqrt(jnp.maximum(jnp.where(real, jnp.sum(cross * cross, axis=-1), 1.0), 1e-18))
    cos = jnp.cos(jnp.arctan2(jnp.where(real, norm, 0.0), dot))  # [E, K]
    angular = [jnp.ones_like(cos) if hp["angle_blind"] else legendre(l, cos) for l in range(S)]
    sbf = jnp.concatenate(
        [radial[l][:, None, :] * angular[l][:, :, None] for l in range(S)], axis=-1)  # [E, K, S R]
    target = jnp.where(real, ji, e).reshape(-1)             # dropped rows go to segment E

    dense = functools.partial(_dense_with, params, matmul=matmul)
    h = x
    for layer in range(hp["layers"]):
        p = f"graph_convs_{layer}"
        q = f"{p}/interaction"
        # embedding block
        hn = dense(f"{p}/lin_node", h)
        x_e = silu(dense(f"{p}/emb_lin", jnp.concatenate(
            [hn[senders], hn[receivers], silu(dense(f"{p}/emb_lin_rbf", rbf))], axis=-1)))
        # interaction block
        rbf_e = dense(f"{q}/lin_rbf2", dense(f"{q}/lin_rbf1", rbf, bias=False), bias=False)
        x_ji = silu(dense(f"{q}/lin_ji", x_e))
        x_kj = silu(dense(f"{q}/lin_down", silu(dense(f"{q}/lin_kj", x_e)) * rbf_e))
        sbf_e = dense(f"{q}/lin_sbf2", dense(f"{q}/lin_sbf1", sbf, bias=False), bias=False)
        t = x_kj[:, None, :] * sbf_e * real[:, :, None]     # [E, K, I], row kj, slot ji
        summed = jax.ops.segment_sum(t.reshape(e * k, -1), target, e + 1)[:e]
        m = x_ji + silu(dense(f"{q}/lin_up", summed))
        for i in range(hp["before_skip"]):
            r = silu(dense(f"{q}/res_before_{i}/lin1", m))
            m = m + silu(dense(f"{q}/res_before_{i}/lin2", r))
        m = silu(dense(f"{q}/lin", m)) + x_e
        for i in range(hp["after_skip"]):
            r = silu(dense(f"{q}/res_after_{i}/lin1", m))
            m = m + silu(dense(f"{q}/res_after_{i}/lin2", r))
        # output block
        gated = dense(f"{p}/out_lin_rbf", rbf, bias=False) * m
        out = dense(f"{p}/out_lin_up", jax.ops.segment_sum(gated, receivers, n), bias=False)
        for i in range(hp["output_layers"]):
            out = silu(dense(f"{p}/out_lin_{i}", out))
        h = act(dense(f"{p}/out_lin", out, bias=False))     # the stack's activation
    for i in range(hp["head_layers"]):
        h = act(dense(f"head0_branch-0/dense_{i}", h))
    energy = dense(f"head0_branch-0/dense_{hp['head_layers']}", h)[:, 0]
    return jnp.where(over, jnp.nan, energy)
