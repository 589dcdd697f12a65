"""Triplet (angle) indexing for directional message passing — DimeNet.

Reference: ``hydragnn/models/DIMEStack.py:233-281`` (``triplets()`` adapted
from PyG) and, for periodic structures, the Open Catalyst Project's
``dimenet_plus_plus.py::triplets``. For every directed edge ji = (j -> i)
the partners are the edges kj = (k -> j) that END where ji STARTS; the
interaction block mixes edge embeddings along the (kj) -> (ji) pairs,
weighted by the spherical basis of the angle between them.

The rule that drops a pair. A partner is dropped only where it is the EXACT
REVERSE of ji: k = i AND the two edges' periodic shifts cancel
(``shift_kj + shift_ji = 0``), so that the path k -> j -> i returns to the
atom it left. A k that is a periodic IMAGE of i (same index, another cell)
is a real third atom and is kept: Open Catalyst's rule. In a cell 8-12 A wide
at a 6 A cutoff many neighbours are such images (in a 2-atom cell most), and
PyG's non-periodic rule ``k != i`` would lose their angles. Without shifts
(molecules) every shift is zero and the rule reads ``k != i``: PyG's.

Where it runs. Host-side numpy, never inside jit: vectorised (one sort by
receiver, counts, a ``repeat``/``arange`` expansion; no Python loop over
edges), ordered by ji so that ``idx_ji`` never decreases (the sum onto ji is
then a sum over consecutive rows). ``graphs.batching.collate`` calls it per
sample on the thread that collates, inside a ``triplets`` span, unless the
sample already carries ``extras["idx_kj"]`` (``attach_triplets``: the
serving tier's requests, and datasets with no cap on an atom's edges, whose
pad buckets are sized from the attached counts). The angle itself is
computed on the device from the padded edge vectors (it depends on
positions, which change under force training).

The size. T = sum_j in(j) out(j) - (reversed pairs). Where one side of every
atom's edges is capped at K (``max_neighbours``: the radius graph caps the
incoming side, a k-nearest corpus the outgoing one), T <= K x E for every
graph, which is the pad rule of ``graphs.batching.compute_pad_spec``.
"""

from __future__ import annotations

import numpy as np

from .graph import GraphSample

_EMPTY = np.zeros((0,), np.int32)


def build_triplets(senders: np.ndarray, receivers: np.ndarray,
                   shifts: np.ndarray | None = None) -> tuple[np.ndarray, np.ndarray]:
    """Edge-index pairs (idx_kj, idx_ji): every pair of edges with
    ``receivers[kj] == senders[ji]`` but the exact reverse of ji. ``shifts``
    ``[E, 3]`` are the edges' periodic shift vectors (None = all zero).
    Sorted by ji, then by the receiver-sorted order of kj."""
    senders = np.asarray(senders, np.int64)
    receivers = np.asarray(receivers, np.int64)
    E = senders.shape[0]
    if E == 0:
        return _EMPTY, _EMPTY
    n = int(max(senders.max(), receivers.max())) + 1
    order = np.argsort(receivers, kind="stable")          # edges grouped by where they end
    counts = np.bincount(receivers, minlength=n)
    starts = np.cumsum(counts) - counts
    partners = counts[senders]                             # edges ending at ji's start
    total = int(partners.sum())
    if total == 0:
        return _EMPTY, _EMPTY
    idx_ji = np.repeat(np.arange(E), partners)
    first = np.cumsum(partners) - partners                 # ji's first row in the expansion
    within = np.arange(total) - np.repeat(first, partners)
    idx_kj = order[np.repeat(starts[senders], partners) + within]
    back = senders[idx_kj] == receivers[idx_ji]            # k == i
    if shifts is not None and len(shifts):
        shifts = np.asarray(shifts, np.float64)
        # two shift sums that do not cancel differ by a lattice vector: far
        # above rounding of sums of float32 lattice vectors
        tol = 1e-4 * max(float(np.abs(shifts).max()), 1e-30)
        rows = np.flatnonzero(back)
        closes = np.abs(shifts[idx_kj[rows]] + shifts[idx_ji[rows]]).max(axis=1) <= tol
        back[rows[~closes]] = False
    keep = ~back
    return idx_kj[keep].astype(np.int32), idx_ji[keep].astype(np.int32)


def degree_cap(samples) -> int:
    """The K of ``T <= K x E`` that these samples bear out: for each sample
    the smaller of its largest in-degree and its largest out-degree
    (T = sum_j in(j) out(j) <= min(max in, max out) x E), the largest over
    the samples. O(E) a sample; no triplet is enumerated."""
    cap = 0
    for s in samples:
        if s.num_edges:
            cap = max(cap, min(int(np.bincount(s.senders).max()),
                               int(np.bincount(s.receivers).max())))
    return cap


def attach_triplets(sample: GraphSample) -> GraphSample:
    """Compute and keep triplet indices on a sample (``extras``); collate
    takes them from there instead of enumerating."""
    idx_kj, idx_ji = build_triplets(sample.senders, sample.receivers, sample.edge_shifts)
    sample.extras["idx_kj"] = idx_kj
    sample.extras["idx_ji"] = idx_ji
    return sample
