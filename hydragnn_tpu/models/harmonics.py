"""Real spherical harmonics + Gaunt (real-CG) couplings — the
hand-rolled replacement for e3nn that MACE needs.

Reference: ``hydragnn/models/MACEStack.py`` uses ``e3nn.o3.SphericalHarmonics``
and tensor products whose Clebsch-Gordan contractions come from
``utils/model/mace_utils/tools/cg.py:94`` (``U_matrix_real``). Here:

* ``spherical_harmonics(vec, l_max)`` — explicit Cartesian polynomial
  formulas up to l=3 (differentiable jnp, component normalization: the l=0
  value is 1 and each block has ||Y_l||^2 = 2l+1 on the unit sphere);
* Gaunt coefficients G^{l3}_{l1 l2}[m1, m2, m3] = ∫ Y_{l1 m1} Y_{l2 m2}
  Y_{l3 m3} dΩ computed ONCE on host by *exact* Gauss-Legendre x uniform-phi
  quadrature (the integrand is a polynomial on the sphere) — this makes the
  coupling self-consistent with our harmonics convention by construction, no
  sympy table matching needed;
* ``coupling_tensor`` — the Gaunt tensor of an even (l1, l2, l3) as a unit
  coupling with a fixed sign; ``symmetric_basis`` — the couplings of nu copies
  of the irreps l <= l_max to L that are symmetric under permuting the
  copies, reduced to a basis (MACE's U, ``models/mace.py``).

Equivariance of the whole pipeline is asserted by rotation tests at the model
level (MACE scalar outputs invariant, forces equivariant).
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Real spherical harmonics (component normalization), m ordered -l..l
# ---------------------------------------------------------------------------


def _sh_blocks(x, y, z, l_max: int, xp):
    """Shared implementation for jnp (device) and numpy (host quadrature)."""
    out = [xp.stack([xp.ones_like(x)], axis=-1)]  # l=0: [.., 1]
    if l_max >= 1:
        c1 = math.sqrt(3.0)
        out.append(xp.stack([c1 * y, c1 * z, c1 * x], axis=-1))
    if l_max >= 2:
        c = math.sqrt(15.0)
        c20 = math.sqrt(5.0)
        out.append(
            xp.stack(
                [
                    c * x * y,
                    c * y * z,
                    c20 * 0.5 * (3.0 * z * z - 1.0),
                    c * x * z,
                    c * 0.5 * (x * x - y * y),
                ],
                axis=-1,
            )
        )
    if l_max >= 3:
        out.append(
            xp.stack(
                [
                    math.sqrt(35.0 / 8.0) * y * (3.0 * x * x - y * y),
                    math.sqrt(105.0) * x * y * z,
                    math.sqrt(21.0 / 8.0) * y * (5.0 * z * z - 1.0),
                    math.sqrt(7.0) * 0.5 * z * (5.0 * z * z - 3.0),
                    math.sqrt(21.0 / 8.0) * x * (5.0 * z * z - 1.0),
                    math.sqrt(105.0) * 0.5 * z * (x * x - y * y),
                    math.sqrt(35.0 / 8.0) * x * (x * x - 3.0 * y * y),
                ],
                axis=-1,
            )
        )
    if l_max >= 4:
        out.extend(_sh_recurrence(x, y, z, 4, l_max, xp))
    return out


def _sh_recurrence(x, y, z, l_from: int, l_max: int, xp):
    """General real spherical harmonics for l >= 4 by recurrence, same
    convention as the explicit blocks (m ordered -l..l, e3nn axis roles,
    component normalization ||Y_l||^2 = 2l+1 on the unit sphere).

    Uses A_m = Re (x+iy)^m, B_m = Im (x+iy)^m and associated Legendre
    polynomials with the sin^m(theta) factor divided out (it lives in
    A_m/B_m), so everything is polynomial in (x, y, z) — differentiable and
    pole-safe."""
    one = xp.ones_like(x)
    A = [one, x]
    B = [xp.zeros_like(x), y]
    for m in range(2, l_max + 1):
        A.append(x * A[m - 1] - y * B[m - 1])
        B.append(x * B[m - 1] + y * A[m - 1])

    # Q[(l, m)]: P_l^m(z) / sin^m(theta), via the standard l-recurrence
    Q = {}
    for m in range(l_max + 1):
        Q[(m, m)] = float(math.prod(range(1, 2 * m, 2))) * one  # (2m-1)!!
        if m + 1 <= l_max:
            Q[(m + 1, m)] = (2 * m + 1) * z * Q[(m, m)]
        for l in range(m + 2, l_max + 1):
            Q[(l, m)] = (
                (2 * l - 1) * z * Q[(l - 1, m)] - (l - 1 + m) * Q[(l - 2, m)]
            ) / (l - m)

    blocks = []
    for l in range(l_from, l_max + 1):
        comps = []
        for m in range(-l, l + 1):
            am = abs(m)
            c = math.sqrt(
                (2 * l + 1)
                * (2.0 if m != 0 else 1.0)
                * math.factorial(l - am)
                / math.factorial(l + am)
            )
            base = c * Q[(l, am)]
            if m < 0:
                comps.append(base * B[am])
            elif m > 0:
                comps.append(base * A[am])
            else:
                comps.append(base)
        blocks.append(xp.stack(comps, axis=-1))
    return blocks


def spherical_harmonics(vec: jax.Array, l_max: int, eps: float = 1e-6) -> list:
    """Unit-normalize ``vec`` [E, 3] and return [Y_0, ..., Y_lmax], each
    [E, 2l+1]. Zero vectors (padding) are substituted with the +z pole BEFORE
    the norm so gradients stay finite (sqrt at 0 has a NaN derivative and
    0 * NaN defeats downstream masking)."""
    n2 = jnp.sum(vec * vec, axis=-1, keepdims=True)
    is_zero = n2 < eps * eps
    safe_vec = jnp.where(is_zero, jnp.array([0.0, 0.0, 1.0]), vec)
    n = jnp.sqrt(jnp.sum(safe_vec * safe_vec, axis=-1, keepdims=True))
    unit = safe_vec / n
    return _sh_blocks(unit[..., 0], unit[..., 1], unit[..., 2], l_max, jnp)


# ---------------------------------------------------------------------------
# Gaunt coefficients by exact quadrature (host, cached)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _quadrature(l_max_total: int):
    """Gauss-Legendre in cos(theta) x uniform phi — exact for spherical
    polynomials up to the triple-product degree."""
    n_theta = 2 * l_max_total + 4
    n_phi = 4 * l_max_total + 5
    ct, wt = np.polynomial.legendre.leggauss(n_theta)
    phi = np.arange(n_phi) * (2.0 * np.pi / n_phi)
    st = np.sqrt(1.0 - ct**2)
    x = st[:, None] * np.cos(phi)[None, :]
    y = st[:, None] * np.sin(phi)[None, :]
    z = np.broadcast_to(ct[:, None], x.shape)
    w = np.broadcast_to(wt[:, None], x.shape) * (2.0 * np.pi / n_phi)
    return x.ravel(), y.ravel(), z.ravel(), w.ravel()


@functools.lru_cache(maxsize=None)
def gaunt(l1: int, l2: int, l3: int) -> tuple:
    """G[m1, m2, m3] = (1/4pi) ∫ Y_{l1 m1} Y_{l2 m2} Y_{l3 m3} dΩ in the
    component-normalized basis above. Zero unless |l1-l2| <= l3 <= l1+l2 and
    l1+l2+l3 even. Returned as a nested tuple (hashable, cached)."""
    x, y, z, w = _quadrature(l1 + l2 + l3)
    blocks = _sh_blocks(x, y, z, max(l1, l2, l3), np)
    Y1, Y2, Y3 = blocks[l1], blocks[l2], blocks[l3]  # [Q, 2l+1]
    G = np.einsum("q,qa,qb,qc->abc", w / (4.0 * np.pi), Y1, Y2, Y3)
    G[np.abs(G) < 1e-12] = 0.0
    return tuple(map(lambda m: tuple(map(tuple, m)), G))


def gaunt_array(l1: int, l2: int, l3: int) -> np.ndarray:
    return np.asarray(gaunt(l1, l2, l3))


def coupling_paths(l_in1: int, l_in2: int, l_out_max: int) -> list:
    """All (l1, l2, l3) with nonzero Gaunt coupling within the given maxima."""
    paths = []
    for l1 in range(l_in1 + 1):
        for l2 in range(l_in2 + 1):
            for l3 in range(abs(l1 - l2), min(l1 + l2, l_out_max) + 1):
                if (l1 + l2 + l3) % 2 == 0:
                    paths.append((l1, l2, l3))
    return paths


def coupling_tensor(l1: int, l2: int, l3: int) -> np.ndarray:
    """The real coupling C[m1, m2, m3] of (l1, l2) to l3 for l1 + l2 + l3 even
    (the one the symmetric contraction and the interaction use): the Gaunt
    tensor scaled to unit Frobenius norm, with the sign that makes its first
    entry above 1e-6 (C order) positive. A convention a second construction
    can meet without this file (``benchmark/reference/mace.py`` finds the
    same tensor as the null vector of the rotation constraint)."""
    G = gaunt_array(l1, l2, l3)
    norm = np.linalg.norm(G)
    if norm == 0.0:
        raise ValueError(f"no even coupling of l = ({l1}, {l2}) to {l3}")
    G = G / norm
    first = G.ravel()[np.flatnonzero(np.abs(G.ravel()) > 1e-6)[0]]
    return G if first > 0 else -G


@functools.lru_cache(maxsize=None)
def _coupling_chains(l_max: int, nu: int, L: int) -> tuple:
    """Every left-nested chain ((l_1 l_2) l' l_3) ... -> L of ``nu`` copies of
    the irreps l <= l_max through even couplings, as dense tensors
    [2L+1, D, ..., D] over the flat (l, m) index (D = (l_max+1)^2), in the
    fixed order (l', l_nu, earlier chains)."""
    D = irreps_dim(l_max)
    if nu == 1:
        if L > l_max:
            return ()
        T = np.zeros((2 * L + 1, D))
        T[:, L * L : (L + 1) ** 2] = np.eye(2 * L + 1)
        return (T,)
    out = []
    for lp in range((nu - 1) * l_max + 1):
        for ln in range(l_max + 1):
            if not (abs(lp - ln) <= L <= lp + ln) or (lp + ln + L) % 2:
                continue
            C = coupling_tensor(lp, ln, L)
            for T in _coupling_chains(l_max, nu - 1, lp):
                new = np.zeros((2 * L + 1,) + T.shape[1:] + (D,))
                new[..., ln * ln : (ln + 1) ** 2] = np.einsum("a...,abM->M...b", T, C)
                out.append(new)
    return tuple(out)


@functools.lru_cache(maxsize=None)
def symmetric_basis(l_max: int, nu: int, L: int) -> np.ndarray:
    """A basis U[eta, M, i_1, ..., i_nu] of the couplings of ``nu`` copies of
    the irreps l <= l_max (natural parity; i the flat (l, m) index) to L that
    are symmetric under permuting the copies: sum_i U[eta, M, i_1..i_nu]
    prod_xi x[i_xi] is the eta-th equivariant polynomial of degree nu. Built
    from the coupling chains, symmetrised, each scaled to Frobenius norm
    sqrt(2L+1), kept in order where it is linearly independent of those kept
    before (Gram-Schmidt residual over 1e-6). The count of eta is the rank of
    the symmetric subspace (l_max 3: 1, 4, 8 for L = 0 and 1, 3, 12 for L = 1
    at nu = 1, 2, 3)."""
    perms = list(itertools.permutations(range(1, nu + 1)))
    kept, ortho = [], []
    for T in _coupling_chains(l_max, nu, L):
        S = sum(np.transpose(T, (0,) + p) for p in perms) / len(perms)
        norm = np.linalg.norm(S)
        if norm < 1e-9:
            continue
        S = S * (math.sqrt(2 * L + 1) / norm)
        r = S.ravel().copy()
        for q in ortho:
            r -= (q @ r) * q
        if np.linalg.norm(r) > 1e-6 * np.linalg.norm(S):
            kept.append(S)
            ortho.append(r / np.linalg.norm(r))
    return np.stack(kept) if kept else np.zeros((0, 2 * L + 1) + (irreps_dim(l_max),) * nu)


# ---------------------------------------------------------------------------
# Misc irreps helpers
# ---------------------------------------------------------------------------


def irreps_dim(l_max: int) -> int:
    return (l_max + 1) ** 2
