"""MPtrj-shaped periodic crystals from a seed.

The traffic file fixes the SIZES (atoms per structure, in corpus order) so
that every seed pads to the same bucket table and the same sequence of
shapes; the seed moves species, coordinates, targets.

A structure: n atoms in a cubic cell at ``volume_per_atom`` A^3 per atom,
placed on a jittered grid (no two atoms closer than about half a grid
spacing), species drawn from ``n_species`` elements. Neighbours: each atom's
``max_neighbours`` nearest images inside ``radius`` (minimum-image shifts to
as many cells as the radius reaches, so a 2-atom cell has its full shell).
Every atom gets exactly ``max_neighbours`` edges: a structure whose sparsest
atom has fewer inside the radius is compressed uniformly until it has (the
neighbour sets do not change under a uniform scaling). That keeps edges =
``max_neighbours`` x atoms for every seed, which is what makes the padded
shapes seed-independent; real MPtrj at a cap of 32 within 5 A is the same to
within a few percent of atoms.

Targets are seeded random numbers (energy per structure, force per atom):
the benchmark measures a training step, not what is learned.

Nothing here imports the program under test.
"""

from __future__ import annotations

import numpy as np


def sizes(params: dict) -> np.ndarray:
    """Atoms per structure, in corpus order, from the traffic file alone."""
    spec = params["sizes"]
    rng = np.random.default_rng(int(spec["seed"]))
    n = rng.lognormal(np.log(float(spec["median"])), float(spec["sigma"]),
                      int(params["count"]))
    n = np.clip(np.rint(n), int(spec["min"]), int(spec["max"])).astype(np.int64)
    # the heavy tail's end is always present: it decides the worst-case bucket
    n[int(spec.get("max_at", 0))] = int(spec["max"])
    return n


def _structure(n: int, rng: np.random.Generator, params: dict) -> dict:
    radius = float(params["radius"])
    k = int(params["max_neighbours"])
    side = (float(params["volume_per_atom"]) * n) ** (1.0 / 3.0)
    m = int(np.ceil(n ** (1.0 / 3.0) - 1e-9))
    sites = rng.permutation(m ** 3)[:n]
    grid = np.stack(np.unravel_index(sites, (m, m, m)), axis=1).astype(np.float64)
    spacing = side / m
    pos = (grid + 0.5 + rng.uniform(-0.25, 0.25, size=(n, 3))) * spacing

    # k nearest images of every atom, searched far enough to hold them
    reach = radius * 1.1
    while True:
        r = int(np.ceil(reach / side))
        offs = np.stack(np.meshgrid(*[np.arange(-r, r + 1)] * 3, indexing="ij"),
                        axis=-1).reshape(-1, 3).astype(np.float64)
        cand = (pos[None, :, :] + offs[:, None, :] * side).reshape(-1, 3)
        d2 = ((pos ** 2).sum(1)[:, None] + (cand ** 2).sum(1)[None, :]
              - 2.0 * pos @ cand.T)  # [n, K*n]
        np.maximum(d2, 0.0, out=d2)
        zero = int(np.flatnonzero((offs == 0).all(1))[0])
        d2[np.arange(n), zero * n + np.arange(n)] = np.inf  # not itself
        if d2.shape[1] - 1 < k:
            reach *= 1.5
            continue
        nearest = np.argpartition(d2, k - 1, axis=1)[:, :k]
        dk = np.sqrt(np.take_along_axis(d2, nearest, axis=1))
        if dk.max() <= reach:
            break
        reach *= 1.5
    order = np.argsort(dk, axis=1, kind="stable")
    nearest = np.take_along_axis(nearest, order, axis=1)
    scale = min(1.0, 0.99 * radius / float(dk.max()))
    senders = np.repeat(np.arange(n, dtype=np.int32), k)
    receivers = (nearest % n).astype(np.int32).reshape(-1)
    shifts = offs[nearest // n].reshape(-1, 3) * side
    species = np.sort(rng.choice(np.arange(1, int(params["n_species"]) + 1),
                                 size=int(rng.integers(1, 6)), replace=False))
    z = rng.choice(species, size=n)
    return {
        "z": z.astype(np.int32),
        "pos": (pos * scale).astype(np.float32),
        "cell": (np.eye(3) * side * scale).astype(np.float32),
        "senders": senders,
        "receivers": receivers,
        "shifts": (shifts * scale).astype(np.float32),
        "energy": np.float32(rng.normal() * 0.1 * n),
        "forces": rng.normal(size=(n, 3)).astype(np.float32),
    }


def generate(params: dict, seed: int) -> list[dict]:
    """The corpus: one dict of arrays per structure (z, pos, cell, senders,
    receivers, shifts, energy, forces)."""
    rng = np.random.default_rng([int(seed), 0x6372797374])
    return [_structure(int(n), rng, params) for n in sizes(params)]
