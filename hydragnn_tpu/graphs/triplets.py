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
    return max((min(sends, receives) for sends, receives in _max_degrees(samples)), default=0)


def _max_degrees(samples):
    """(largest out-degree, largest in-degree) of every sample with edges."""
    return [(int(np.bincount(s.senders).max()), int(np.bincount(s.receivers).max()))
            for s in samples if s.num_edges]


def block_rows(samples, cap: int) -> str | None:
    """Which side of a triplet is the ROW of the dense ``[E, K]`` layout, for a
    whole corpus under the cap K: ``"kj"`` where no atom SENDS more than K
    edges (every edge kj then has at most K partners ji, the edges its
    receiver sends), ``"ji"`` where none RECEIVES more than K (a radius
    graph's cap: the partners kj of ji are the edges its sender receives),
    ``None`` where neither holds for every sample (``degree_cap`` is borne
    out sample by sample, by either side): such a corpus keeps the flat
    list. O(E) a sample."""
    if not cap:
        return None
    sends, receives = map(max, zip(*_max_degrees(samples) or [(0, 0)]))
    if sends <= cap:
        return "kj"
    return "ji" if receives <= cap else None


def block_triplets(senders: np.ndarray, receivers: np.ndarray, shifts: np.ndarray | None,
                   num_nodes: int, k: int, rows: str) -> tuple[np.ndarray, np.ndarray]:
    """The triplets of :func:`build_triplets` as a dense block: ``table``
    ``[num_nodes, k]``, the edge ids each atom sends (``rows == "kj"``) or
    receives (``"ji"``) in edge order, -1 where it has fewer, and ``mask``
    ``[E, k]``: slot ``(r, s)`` pairs the row edge ``r`` with its s-th partner
    ``table[node_of_row[r], s]`` (``node_of_row`` = receivers for rows kj,
    senders for rows ji: the atom j both edges share), and is real unless the
    partner is missing or the exact reverse of the row (the rule above). One
    ``argsort``; the partners of all rows that share an atom are one table row."""
    senders = np.asarray(senders, np.int64)
    receivers = np.asarray(receivers, np.int64)
    E = senders.shape[0]
    table = np.full((num_nodes, k), -1, np.int64)
    if E == 0:
        return table.astype(np.int32), np.zeros((0, k), bool)
    owner, node_of_row = (senders, receivers) if rows == "kj" else (receivers, senders)
    counts = np.bincount(owner, minlength=num_nodes)
    if counts.max() > k:
        raise ValueError(
            f"an atom {'sends' if rows == 'kj' else 'receives'} {int(counts.max())} edges, "
            f"the block holds {k}: rows {rows!r} is not this corpus's capped side")
    order = np.argsort(owner, kind="stable")
    starts = np.cumsum(counts) - counts
    table[owner[order], np.arange(E) - starts[owner[order]]] = order
    partner = table[node_of_row]                           # [E, k]
    mask = partner >= 0
    # k == i: the far ends of row and partner are one atom. The row's far end
    # is its ``owner`` entry (senders of kj, receivers of ji), the partner's its
    # ``node_of_row`` entry, whichever of the two sides the row is
    r, s = np.nonzero(mask & (node_of_row[partner] == owner[:, None]))
    if shifts is not None and len(shifts) and r.size:
        shifts = np.asarray(shifts, np.float64)
        tol = 1e-4 * max(float(np.abs(shifts).max()), 1e-30)
        closes = np.abs(shifts[r] + shifts[partner[r, s]]).max(axis=1) <= tol
        r, s = r[closes], s[closes]
    mask[r, s] = False
    return table.astype(np.int32), mask


def attach_triplets(sample: GraphSample) -> GraphSample:
    """Compute and keep triplet indices on a sample (``extras``); collate
    takes them from there instead of enumerating."""
    idx_kj, idx_ji = build_triplets(sample.senders, sample.receivers, sample.edge_shifts)
    sample.extras["idx_kj"] = idx_kj
    sample.extras["idx_ji"] = idx_ji
    return sample
