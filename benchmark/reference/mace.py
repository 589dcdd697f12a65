"""Plain reference: MACE (Batatia, Kovacs, Simm, Ortner, Csanyi,
arXiv:2206.07697) at the sizes of MACE-MP-0 (arXiv:2401.00096) as HydraGNN
runs it, one node energy per atom. Imports nothing of the program; float32
``jax.numpy``, rank-3 ``[rows, (l m), channel]`` tensors, no padding, no
kernels. The paths of one sender irrep l1 share one einsum (their couplings
side by side): ten einsums, one a path, compiled twice as long for each of
the shapes a run compares.

With C channels, Y_lm real spherical harmonics (component normalisation,
m = -l..l, Y_1 = sqrt(3) (y, z, x)), j = sender, i = receiver of an edge:

    h_i^(0)   = W_embed[z_i]
    layer t:  h~ = Linear_up(h)                               per l, [C, C]
              m_ij[l3 m3, c] = R_p,c(r_ij) sum_{m1 m2} C^{l3}_{l1 l2}[m1, m2, m3]
                               h~_j[l1 m1, c] Y_{l2 m2}(r^_ij)  paths p = (l1, l2, l3),
                               l1 + l2 + l3 even, in (l1, l2, l3) order
              A_i[l3]  = Linear_l3 over (paths to l3 x C) of sum_j m_ij / avg_num_neighbors
              sc_i     = W_skip[z_i] h_i                       per l, [C, C] by species
              B_i[LM, c] = sum_nu sum_eta W[z_i, (L, nu, eta), c]
                           sum U^(nu)[eta, M, i1..i_nu] prod_xi A_i[i_xi, c]
              h_i^(t+1) = Linear(B_i) + sc_i     L <= node_max_ell, L = 0 in the last layer
    E_i = sum_{t < T} w_t . h_i^(t)[0e] + MLP(h_i^(T)[0e])     bias-free readouts

R = bias-free MLP (SiLU) on sqrt(2/r_c) sin(n pi r / r_c) / r times the
polynomial cutoff with p = 5. The program's departures (no E0, no scale and
shift, tables with a row for every Z <= 118) are followed.

Couplings, built here a second way. C^{l3}_{l1 l2} is the one tensor that
every rotation leaves unchanged (null vector of D1 x D2 x D3 - 1, the Wigner
matrices fitted to this file's own harmonics), of unit Frobenius norm, its
first entry above 1e-6 positive. U^(nu) for a target L: the left-nested chains
((l_1 l_2) l' l_3) -> L in the order (l', l_nu, earlier chains), symmetrised
over the copies, scaled to Frobenius norm sqrt(2L+1), each kept where it is
independent of those kept before. ``symmetric_rank`` counts the same subspace
without any coupling: the rotation- and parity-invariant part of
V_L x Sym^nu(V), found on the monomials.
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

ACT = {"silu": jax.nn.silu, "relu": jax.nn.relu, "tanh": jnp.tanh, "gelu": jax.nn.gelu}


def hyperparameters(config: dict) -> dict:
    arch = config["NeuralNetwork"]["Architecture"]
    head = arch["output_heads"]["node"]
    return {
        "layers": int(arch["num_conv_layers"]),
        "channels": int(arch["hidden_dim"]),
        "max_ell": int(arch["max_ell"]),
        "node_max_ell": int(arch["node_max_ell"]),
        "correlation": int(arch["correlation"]),
        "radius": float(arch["radius"]),
        "num_radial": int(arch["num_radial"]),
        "radial_layers": 3,  # 64-64-64, MACE's default at every size
        "avg_num_neighbors": float(arch["avg_num_neighbors"]),
        "activation": arch["activation_function"],
        "readout_layers": int(head["num_headlayers"]),
        "energy_weight": float(arch.get("energy_weight", 0.0)),
        "energy_peratom_weight": float(arch.get("energy_peratom_weight", 0.0)),
        "force_weight": float(arch.get("force_weight", 0.0)),
    }


# -- harmonics and couplings (float64 numpy at build; jnp in the model) ---------------

def harmonics(x, y, z, l_max: int, xp=np) -> list:
    """[Y_0 .. Y_lmax], each [..., 2l+1], of a unit vector: associated
    Legendre recurrence with sin^m(theta) carried by Re/Im (x + iy)^m, so
    every entry is a polynomial in (x, y, z). No Condon-Shortley phase."""
    re, im = [xp.ones_like(x)], [xp.zeros_like(x)]
    for m in range(1, l_max + 1):
        re.append(x * re[m - 1] - y * im[m - 1])
        im.append(x * im[m - 1] + y * re[m - 1])
    leg = {}
    for m in range(l_max + 1):
        leg[m, m] = float(np.prod(np.arange(1, 2 * m, 2))) * xp.ones_like(x)
        if m < l_max:
            leg[m + 1, m] = (2 * m + 1) * z * leg[m, m]
        for l in range(m + 2, l_max + 1):
            leg[l, m] = ((2 * l - 1) * z * leg[l - 1, m] - (l + m - 1) * leg[l - 2, m]) / (l - m)
    out = []
    for l in range(l_max + 1):
        comps = []
        for m in range(-l, l + 1):
            a = abs(m)
            norm = math.sqrt((2 * l + 1) * (2.0 if m else 1.0)
                             * math.factorial(l - a) / math.factorial(l + a))
            comps.append(norm * leg[l, a] * (im[a] if m < 0 else re[a]))
        out.append(xp.stack(comps, axis=-1))
    return out


def _unit(v):
    return v / np.linalg.norm(v, axis=-1, keepdims=True)


@functools.lru_cache(maxsize=None)
def _rotations(l_max: int, count: int = 3) -> tuple:
    """The Wigner matrices {l: D^l(R)} of ``count`` fixed rotations R, fitted
    so that Y_l(R v) = D^l Y_l(v) on sample directions."""
    rng = np.random.default_rng(20240101)
    pts = _unit(rng.normal(size=(4 * (2 * l_max + 1) ** 2, 3)))
    wigner = []
    for _ in range(count):
        q, _ = np.linalg.qr(rng.normal(size=(3, 3)))
        q = q * np.sign(np.linalg.det(q))
        y0 = harmonics(pts[:, 0], pts[:, 1], pts[:, 2], l_max)
        moved = pts @ q.T
        y1 = harmonics(moved[:, 0], moved[:, 1], moved[:, 2], l_max)
        wigner.append({l: np.linalg.lstsq(y0[l], y1[l], rcond=None)[0].T
                       for l in range(l_max + 1)})
    return tuple(wigner)


@functools.lru_cache(maxsize=None)
def coupling(l1: int, l2: int, l3: int) -> np.ndarray:
    """C[m1, m2, m3], l1 + l2 + l3 even."""
    assert (l1 + l2 + l3) % 2 == 0 and abs(l1 - l2) <= l3 <= l1 + l2
    wigner = _rotations(max(l1, l2, l3))
    size = (2 * l1 + 1) * (2 * l2 + 1) * (2 * l3 + 1)
    rows = [np.kron(np.kron(d[l1], d[l2]), d[l3]) - np.eye(size) for d in wigner]
    _, s, vt = np.linalg.svd(np.concatenate(rows, axis=0))
    assert s[-1] < 1e-9 and (size == 1 or s[-2] > 1e-3), (l1, l2, l3, s[-2:])  # one-dimensional
    c = vt[-1].reshape(2 * l1 + 1, 2 * l2 + 1, 2 * l3 + 1)
    c = c / np.linalg.norm(c)
    first = c.ravel()[np.flatnonzero(np.abs(c.ravel()) > 1e-6)[0]]
    return c if first > 0 else -c


@functools.lru_cache(maxsize=None)
def _chains(l_max: int, nu: int, L: int) -> tuple:
    dim = (l_max + 1) ** 2
    if nu == 1:
        if L > l_max:
            return ()
        t = np.zeros((2 * L + 1, dim))
        for m in range(2 * L + 1):
            t[m, L * L + m] = 1.0
        return (t,)
    out = []
    for lp in range((nu - 1) * l_max + 1):
        for ln in range(l_max + 1):
            if not abs(lp - ln) <= L <= lp + ln or (lp + ln + L) % 2:
                continue
            for t in _chains(l_max, nu - 1, lp):
                new = np.zeros((2 * L + 1,) + t.shape[1:] + (dim,))
                c = coupling(lp, ln, L)
                for a in range(2 * lp + 1):
                    for b in range(2 * ln + 1):
                        for m in range(2 * L + 1):
                            new[m, ..., ln * ln + b] += c[a, b, m] * t[a]
                out.append(new)
    return tuple(out)


@functools.lru_cache(maxsize=None)
def symmetric_couplings(l_max: int, nu: int, L: int) -> np.ndarray:
    """U[eta, M, i_1, ..., i_nu], symmetric in the i."""
    kept, ortho = [], []
    perms = list(itertools.permutations(range(1, nu + 1)))
    for t in _chains(l_max, nu, L):
        s = sum(np.transpose(t, (0,) + p) for p in perms) / len(perms)
        norm = np.linalg.norm(s)
        if norm < 1e-9:
            continue
        s = s * (math.sqrt(2 * L + 1) / norm)
        r = s.ravel().copy()
        for q in ortho:
            r -= (q @ r) * q
        if np.linalg.norm(r) > 1e-6 * np.linalg.norm(s):
            kept.append(s)
            ortho.append(r / np.linalg.norm(r))
    dim = (l_max + 1) ** 2
    return np.stack(kept) if kept else np.zeros((0, 2 * L + 1) + (dim,) * nu)


def symmetric_rank(l_max: int, nu: int, L: int) -> int:
    """How many independent equivariant polynomials of degree ``nu`` in the
    components l <= l_max (natural parity) transform as L: the dimension of
    the part of V_L x Sym^nu(V) that two rotations and the inversion leave
    unchanged. No coupling coefficient enters."""
    dim = (l_max + 1) ** 2
    monos = list(itertools.combinations_with_replacement(range(dim), nu))
    rng = np.random.default_rng(7)
    pts = rng.normal(size=(len(monos) + 64, dim))
    values = lambda x: np.stack([np.prod(x[:, list(m)], axis=1) for m in monos], axis=1)
    wigner = _rotations(max(l_max, L), 2)
    rows = []
    for d in wigner:
        full = np.zeros((dim, dim))
        for l in range(l_max + 1):
            full[l * l:(l + 1) ** 2, l * l:(l + 1) ** 2] = d[l]
        # polynomial p -> p(D x), on the monomial basis
        act = np.linalg.lstsq(values(pts), values(pts @ full.T), rcond=None)[0]
        rows.append(np.kron(d[L], act.T) - np.eye((2 * L + 1) * len(monos)))
    parity = np.concatenate([np.full(2 * l + 1, (-1.0) ** l) for l in range(l_max + 1)])
    flip = np.array([np.prod(parity[list(m)]) for m in monos]) * (-1.0) ** L
    rows.append(np.diag(np.tile(flip, 2 * L + 1) - 1.0))
    s = np.linalg.svd(np.concatenate(rows, axis=0), compute_uv=False)
    return int(np.sum(s < 1e-8 * s[0]))


def paths(l_in: int, max_ell: int) -> list:
    return [(l1, l2, l3) for l1 in range(l_in + 1) for l2 in range(max_ell + 1)
            for l3 in range(abs(l1 - l2), min(l1 + l2, max_ell) + 1) if (l1 + l2 + l3) % 2 == 0]


# -- the model ---------------------------------------------------------------------

def _blocks(t, l_max: int) -> dict:
    """[rows, (l m), C] -> {l: [rows, 2l+1, C]}"""
    return {l: t[:, l * l:(l + 1) ** 2, :] for l in range(l_max + 1)}


def node_energy(params, hp, x, pos, senders, receivers, shifts, matmul=jnp.matmul):
    act = ACT[hp["activation"]]
    n, C, max_ell = x.shape[0], hp["channels"], hp["max_ell"]
    z = jnp.clip(jnp.round(x[:, 0]).astype(jnp.int32), 0, 118)
    vec = pos[receivers] - pos[senders] + shifts
    dist = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + 1e-18)
    unit = vec / dist[:, None]
    Y = harmonics(unit[:, 0], unit[:, 1], unit[:, 2], max_ell, jnp)
    r_c = hp["radius"]
    k = jnp.arange(1, hp["num_radial"] + 1, dtype=jnp.float32)
    bessel = math.sqrt(2.0 / r_c) * jnp.sin(k * math.pi * dist[:, None] / r_c) / dist[:, None]
    u = dist / r_c
    cutoff = jnp.where(u < 1.0, 1.0 - 21.0 * u ** 5 + 35.0 * u ** 6 - 15.0 * u ** 7, 0.0)
    radial_in = bessel * cutoff[:, None]

    energy = jnp.zeros((n,), jnp.float32)
    h = params["graph_convs_0/node_embedding/embedding"][z][:, None, :]  # [N, 1, C]
    l_in = 0
    for t in range(hp["layers"]):
        p = f"graph_convs_{t}"
        last = t == hp["layers"] - 1
        out_ell = 0 if last else hp["node_max_ell"]
        # radial weights, one a path a channel
        r = radial_in
        for i in range(hp["radial_layers"]):
            r = act(matmul(r, params[f"{p}/radial/dense_{i}/kernel"]))
        pth = paths(l_in, max_ell)
        r = matmul(r, params[f"{p}/radial/dense_out"]).reshape(-1, len(pth), C)
        # interaction
        up = jnp.concatenate(
            [matmul(blk, params[f"{p}/interaction/linear/up_w{l}"])
             for l, blk in _blocks(h, l_in).items()], axis=1)
        sent = _blocks(up[senders], l_in)
        # one product a sender irrep l1: its paths' couplings side by side, [m1, (l2 m2), (path, m3)]
        per_l3 = {l3: [] for l3 in range(max_ell + 1)}
        Y_all = jnp.concatenate(Y, axis=-1)
        for l1 in range(l_in + 1):
            mine = [(i, path) for i, path in enumerate(pth) if path[0] == l1]
            c = np.zeros((2 * l1 + 1, Y_all.shape[1], sum(2 * l3 + 1 for _, (_, _, l3) in mine)))
            at = 0
            for _, (_, l2, l3) in mine:
                c[:, l2 * l2:(l2 + 1) ** 2, at:at + 2 * l3 + 1] = coupling(l1, l2, l3)
                at += 2 * l3 + 1
            m = jnp.einsum("abq,eac,eb->eqc", jnp.asarray(c, jnp.float32), sent[l1], Y_all)
            at = 0
            for i, (_, _, l3) in mine:
                per_l3[l3].append((i, m[:, at:at + 2 * l3 + 1, :] * r[:, i, None, :]))
                at += 2 * l3 + 1
        A = jnp.concatenate(
            [matmul(jax.ops.segment_sum(
                jnp.concatenate([m for _, m in sorted(per_l3[l3], key=lambda im: im[0])], axis=-1),
                receivers, n) / hp["avg_num_neighbors"], params[f"{p}/interaction/linear/mix_w{l3}"])
             for l3 in range(max_ell + 1)], axis=1)  # [N, (max_ell+1)^2, C]
        skip = {l: jnp.einsum("nmc,ncd->nmd", blk, params[f"{p}/interaction/linear/skip_w{l}"][z])
                for l, blk in _blocks(h, min(l_in, out_ell)).items()}
        # product basis
        w = params[f"{p}/product_basis/contraction/weights"][z]  # [N, weights, C]
        new, at = [], 0
        for L in range(out_ell + 1):
            B = jnp.zeros((n, 2 * L + 1, C), jnp.float32)
            for nu in range(1, hp["correlation"] + 1):
                U = jnp.asarray(symmetric_couplings(max_ell, nu, L), jnp.float32)
                if nu == 1:
                    poly = jnp.einsum("eMk,nkc->neMc", U, A)
                else:  # the product of nu - 1 copies first, the last copy last
                    outer = A
                    for _ in range(nu - 2):
                        outer = jnp.einsum("n...c,njc->n...jc", outer, A)
                    q = jnp.einsum("eM...k,n...c->neMkc", U, outer)
                    poly = jnp.einsum("neMkc,nkc->neMc", q, A)
                eta = U.shape[0]
                B = B + jnp.einsum("nec,neMc->nMc", w[:, at:at + eta, :], poly)
                at += eta
            mixed = matmul(B, params[f"{p}/product_basis/linear/w{L}"])
            new.append(mixed + skip[L] if L in skip else mixed)
        h = jnp.concatenate(new, axis=1)
        l_in = out_ell
        # readout of this layer's scalars
        s = h[:, 0, :]
        head = "head0_branch-0"
        if last:
            for j in range(hp["readout_layers"]):
                s = act(matmul(s, params[f"{head}/readout_{t}_dense_{j}/kernel"]))
        energy = energy + matmul(s, params[f"{head}/readout_{t}/kernel"])[:, 0]
    return energy
