"""MD17-shaped frames of one molecule from a seed.

MD17 is one molecule sampled along a trajectory: every frame has the same
atoms, a few hundredths of an angstrom apart from the next. The template
here is aspirin-shaped (C9H8O4, 21 atoms: a benzene ring, a carboxyl group
and an acetoxy group tilted out of the ring's plane), built from bond
lengths, not taken from the data set. A frame is the template plus clipped
Gaussian displacements from the seed.

Edges are a neighbour list with a skin, as MD codes keep one: every directed
pair within ``radius + skin`` on the TEMPLATE. The model's cosine cutoff
gives a pair beyond ``radius`` the weight zero, so the list is exact for
every frame while its length is the same for every frame and every seed —
one padded shape, no recompile.

Targets are seeded random numbers. Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

_C, _H, _O = 6, 1, 8


def _unit(deg: float) -> np.ndarray:
    a = np.deg2rad(deg)
    return np.array([np.cos(a), np.sin(a), 0.0])


def aspirin_template() -> tuple[np.ndarray, np.ndarray]:
    """(z [21], pos [21, 3]) of an aspirin-shaped molecule."""
    up = np.array([0.0, 0.0, 1.0])
    z, pos = [], []

    def add(zi, p):
        z.append(zi)
        pos.append(np.asarray(p, np.float64))
        return pos[-1]

    ring = [add(_C, 1.39 * _unit(60.0 * i)) for i in range(6)]
    for i in (2, 3, 4, 5):  # ring hydrogens
        add(_H, 2.48 * _unit(60.0 * i))
    # carboxyl on ring carbon 0, in the ring's plane
    c7 = add(_C, ring[0] + 1.48 * _unit(0.0))
    add(_O, c7 + 1.21 * _unit(-60.0))
    o9 = add(_O, c7 + 1.34 * _unit(60.0))
    add(_H, o9 + 0.97 * _unit(0.0))
    # acetoxy on ring carbon 1, tilted out of the plane
    o10 = add(_O, ring[1] + 1.36 * (0.55 * _unit(60.0) + 0.835 * up))
    c11 = add(_C, o10 + 1.36 * (0.70 * _unit(120.0) + 0.714 * up))
    add(_O, c11 + 1.20 * (0.80 * _unit(200.0) + 0.60 * up))
    c13 = add(_C, c11 + 1.50 * (0.60 * _unit(90.0) + 0.80 * up))
    for deg in (30.0, 150.0, 270.0):  # methyl hydrogens
        add(_H, c13 + 1.09 * (0.94 * _unit(deg) + 0.34 * up))
    return np.asarray(z, np.int32), np.stack(pos)


def topology(params: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """(z, template positions, senders, receivers) — seed-independent."""
    z, tmpl = aspirin_template()
    d = np.linalg.norm(tmpl[:, None, :] - tmpl[None, :, :], axis=-1)
    reach = float(params["radius"]) + float(params["skin"])
    s, r = np.nonzero((d <= reach) & ~np.eye(len(z), dtype=bool))
    return z, tmpl, s.astype(np.int32), r.astype(np.int32)


def sizes(params: dict) -> np.ndarray:
    return np.full(int(params["count"]), len(aspirin_template()[0]), np.int64)


def generate(params: dict, seed: int) -> list[dict]:
    """The corpus: one dict of arrays per frame (z, pos, cell, senders,
    receivers, shifts, energy, forces); ``cell`` is None (no periodicity)."""
    z, tmpl, senders, receivers = topology(params)
    count, n = int(params["count"]), len(z)
    rng = np.random.default_rng([int(seed), 0x6d643137])
    sigma, clip = float(params["displacement_sigma"]), float(params["displacement_clip"])
    disp = np.clip(rng.normal(scale=sigma, size=(count, n, 3)), -clip, clip)
    pos = (tmpl[None] + disp).astype(np.float32)
    energy = rng.normal(size=count).astype(np.float32)
    forces = rng.normal(size=(count, n, 3)).astype(np.float32)
    shifts = np.zeros((len(senders), 3), np.float32)
    return [
        {"z": z, "pos": pos[i], "cell": None, "senders": senders,
         "receivers": receivers, "shifts": shifts, "energy": energy[i],
         "forces": forces[i]}
        for i in range(count)
    ]
