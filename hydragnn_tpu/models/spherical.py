"""Spherical Bessel / harmonic basis for DimeNet's directional messages.

Reference: PyG ``SphericalBasisLayer`` (used by ``DIMEStack.py:70-73``), which
sympy-generates j_l and Y_l^0 formulas. Here: spherical Bessel functions via
the standard upward recurrence, their roots precomputed with scipy at module
*build* time (host numpy, cached), and m=0 real spherical harmonics as
Legendre polynomials — all plain jnp elementwise math that XLA fuses.

    sbf[t, l*num_radial + n] = envelope(d/c) * j_l(z_{l,n} d/c) * P_l(cos(angle))

with d the length of the triplet's edge kj: the radial factor is a function
of the EDGE (``radial_on_edges``, evaluated on ``[E]`` and gathered by
``idx_kj``), the angular one of the triplet (``angular_on_triplets``).

matching DimeNet's normalization (each radial slice scaled by
1/|j_{l+1}(z_{l,n})|, angular part sqrt((2l+1)/4pi) folded into learned
weights downstream — we keep plain P_l like PyG's generated code does for l=0
normalization consistency).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=None)
def spherical_bessel_roots(num_spherical: int, num_radial: int) -> tuple:
    """First ``num_radial`` positive roots of j_l for l < num_spherical."""
    from scipy import optimize, special

    roots = np.zeros((num_spherical, num_radial))
    # j_0 roots are n*pi; use them as brackets that shift with l
    for l in range(num_spherical):
        found = []
        x = 1e-6
        step = 0.1
        prev = special.spherical_jn(l, x)
        while len(found) < num_radial:
            x2 = x + step
            cur = special.spherical_jn(l, x2)
            if prev == 0.0:
                prev = cur
                x = x2
                continue
            if np.sign(prev) != np.sign(cur):
                r = optimize.brentq(lambda t: special.spherical_jn(l, t), x, x2)
                if r > 1e-4:
                    found.append(r)
            prev = cur
            x = x2
        roots[l] = found[:num_radial]
    return tuple(map(tuple, roots))


@functools.lru_cache(maxsize=None)
def _normalizers(num_spherical: int, num_radial: int) -> tuple:
    from scipy import special

    roots = np.asarray(spherical_bessel_roots(num_spherical, num_radial))
    norm = np.zeros_like(roots)
    for l in range(num_spherical):
        norm[l] = 1.0 / np.abs(special.spherical_jn(l + 1, roots[l]))
    return tuple(map(tuple, norm))


@functools.partial(jax.custom_jvp, nondiff_argnums=(0,))
def _sph_jn_stack(l_max: int, x: jnp.ndarray) -> jnp.ndarray:
    """Stacked [l_max+1, ...] spherical Bessel values with an *analytic*
    derivative (``j_l' = j_{l-1} - (l+1)/x j_l``).

    The custom JVP is load-bearing: the primal blends upward and Miller
    recurrences whose intermediate values overflow float32 outside their
    stability regions; autodiff through the unselected ``where`` branch then
    produces 0 * inf = NaN cotangents (this killed DimeNet force training).
    The analytic derivative only touches the final, finite values, and takes
    them from THIS function one order up, so the rule is closed under
    differentiation of any order (force training differentiates twice): no
    pass ever differentiates the recurrences themselves.
    """
    return jnp.stack(_spherical_jn_primal(l_max, x))


@_sph_jn_stack.defjvp
def _sph_jn_jvp(l_max, primals, tangents):
    (x,), (dx,) = primals, tangents
    inv = 1.0 / jnp.maximum(x, 0.05)
    # j_l' needs j_{l-1} and j_l (j_0' = -j_1): the stack itself, to order
    # max(l_max, 1). Every order of differentiation therefore asks for the
    # SAME values of the same argument, which the compiler computes once
    j = _sph_jn_stack(max(l_max, 1), x)
    derivs = [-j[1]]  # j_0' = -j_1
    for l in range(1, l_max + 1):
        derivs.append(j[l - 1] - (l + 1) * inv * j[l])
    # clamp region (x < 0.05): zero derivative, matching jnp.maximum's choice
    grad = jnp.stack(derivs) * jnp.where(x >= 0.05, 1.0, 0.0)
    return j[: l_max + 1], grad * dx


def _spherical_jn(l_max: int, x: jnp.ndarray) -> list:
    stacked = _sph_jn_stack(l_max, x)
    return [stacked[l] for l in range(l_max + 1)]


def _spherical_jn_primal(l_max: int, x: jnp.ndarray) -> list:
    """j_0..j_{l_max}, stable over the full argument range.

    Upward recurrence from the analytic j_0/j_1 is stable only for x > l (it
    amplifies the irregular solution y_l below that; padded edges with x ~ 0
    overflow it to inf). Miller's downward recurrence is stable for x < l but
    its truncated start loses accuracy for x >> l. So: compute both and select
    per (l, x). Downward is normalized against whichever of j_0/j_1 is larger
    in magnitude at each x (normalizing only by j_0 breaks at its zeros).
    x is clamped to >= 0.05; callers mask padded (x ~ 0) entries.
    """
    safe = jnp.maximum(x, 0.05)
    inv = 1.0 / safe  # one division an evaluation: every step below multiplies
    j0 = jnp.sin(safe) * inv
    j1 = (j0 - jnp.cos(safe)) * inv

    # upward recurrence (stable region x > l)
    up = [j0, j1]
    for l in range(2, l_max + 1):
        up.append((2 * l - 1) * inv * up[l - 1] - up[l - 2])

    # Miller downward recurrence
    L = l_max + 8
    jp1 = jnp.zeros_like(safe)
    j = jnp.full_like(safe, 1e-18)
    store: dict[int, jnp.ndarray] = {}
    for l in range(L, 0, -1):
        jm1 = (2 * l + 1) * inv * j - jp1
        jp1 = j
        j = jm1
        if l - 1 <= max(l_max, 1):
            store[l - 1] = j
    use_j0 = jnp.abs(j0) >= jnp.abs(j1)
    num = jnp.where(use_j0, j0, j1)
    den = jnp.where(use_j0, store[0], store[1])
    scale = num / jnp.where(den == 0, 1.0, den)
    down = [store[l] * scale if l in store else up[l] for l in range(l_max + 1)]

    out = [j0]
    for l in range(1, l_max + 1):
        out.append(jnp.where(safe > l, up[l], down[l]))
    return out


def _legendre(l_max: int, x: jnp.ndarray) -> list:
    p = [jnp.ones_like(x)]
    if l_max >= 1:
        p.append(x)
    for l in range(2, l_max + 1):
        p.append(((2 * l - 1) * x * p[l - 1] - (l - 1) * p[l - 2]) / l)
    return p


def radial_on_edges(
    x: jnp.ndarray, num_spherical: int, num_radial: int, envelope_exponent: int = 5
) -> jnp.ndarray:
    """The radial part of the spherical basis, on EDGES: ``[E]`` scaled
    lengths x = d/c -> ``[E, num_spherical * num_radial]`` with column
    ``l * num_radial + n`` = u(x) j_l(z_ln x) / |j_{l+1}(z_ln)|. It depends on
    the edge kj alone, so it is evaluated once an edge and gathered to the
    triplets (49 of them an edge at 50 neighbours), as the published layer
    does. One call of the Bessel stack on ``[E, S R]`` arguments; order l is
    read off its own columns. A padded edge is handed x = 1, where the envelope
    and its derivatives vanish: exact zeros in every pass."""
    from .radial import polynomial_envelope

    R = num_radial
    roots = jnp.asarray(spherical_bessel_roots(num_spherical, R), x.dtype).reshape(1, -1)
    norms = jnp.asarray(_normalizers(num_spherical, R), x.dtype).reshape(1, -1)
    stack = _sph_jn_stack(num_spherical - 1, roots * x[:, None])  # [S, E, S R]
    jl = jnp.concatenate(
        [stack[l, :, l * R:(l + 1) * R] for l in range(num_spherical)], axis=-1)  # [E, S R]
    return polynomial_envelope(x, envelope_exponent)[:, None] * jl * norms


def angular_on_triplets(cos_angle: jnp.ndarray, num_spherical: int, num_radial: int) -> jnp.ndarray:
    """``[T]`` cosines of the triplets' angles -> ``[T, num_spherical *
    num_radial]``: P_l(cos(angle)) in every column of order l (the columns of
    :func:`radial_on_edges`)."""
    legendre = jnp.stack(_legendre(num_spherical - 1, cos_angle), axis=-1)  # [T, S]
    return jnp.repeat(legendre, num_radial, axis=-1)
