"""Positional encodings for GPS global attention (host-side preprocessing).

Reference: ``hydragnn/preprocess/serialized_dataset_loader.py:90,183-189`` —
PyG ``AddLaplacianEigenvectorPE(k=pe_dim)`` per sample plus relative edge
encodings ``rel_pe = |pe_src - pe_dst|``. numpy implementation: eigenvectors
of the symmetric-normalized graph Laplacian, skipping the trivial constant
eigenvector, sign-fixed by one stated rule (``fix_signs``), zero-padded when
the graph has fewer than k+1 nodes.
"""

from __future__ import annotations

import numpy as np

from ..graphs.graph import GraphSample


def laplacian_pe(senders, receivers, num_nodes: int, k: int) -> np.ndarray:
    """k smallest non-trivial eigenvectors of the normalized Laplacian."""
    A = np.zeros((num_nodes, num_nodes))
    A[senders, receivers] = 1.0
    A = np.maximum(A, A.T)  # symmetrize
    deg = A.sum(axis=1)
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1e-12))
    L = np.eye(num_nodes) - (dinv[:, None] * A * dinv[None, :])
    vals, vecs = np.linalg.eigh(L)
    order = np.argsort(vals)
    pe = vecs[:, order[1 : k + 1]]  # skip the trivial eigenvector
    if pe.shape[1] < k:
        pe = np.pad(pe, ((0, 0), (0, k - pe.shape[1])))
    return fix_signs(pe).astype(np.float32)


SIGN_TIE = 1e-6  # entries this close (relative) to the largest magnitude tie with it


def fix_signs(pe: np.ndarray) -> np.ndarray:
    """An eigenvector is defined up to its sign; the rule that fixes it: of
    the entries whose magnitude is within ``SIGN_TIE`` (relative) of the
    vector's largest, the one of the LOWEST node index is made positive. The
    tolerance is what makes the rule a function of the structure: a symmetric
    structure has entries of equal magnitude and opposite sign, and a plain
    ``argmax`` picks between them by the last bit of the eigensolver's
    rounding. An all-zero (padding) column is left as it is. The benchmark's
    plain reference (``benchmark/reference/gps.py``) states the same rule in
    its own words; ``tests/test_gps_reference.py`` holds the two together."""
    pe = np.array(pe, copy=True)
    mag = np.abs(pe)
    top = mag.max(axis=0, initial=0.0)
    for j in range(pe.shape[1]):
        if top[j] > 0.0:
            i = int(np.argmax(mag[:, j] >= top[j] * (1.0 - SIGN_TIE)))
            if pe[i, j] < 0:
                pe[:, j] = -pe[:, j]
    return pe


def attach_lap_pe(sample: GraphSample, k: int) -> GraphSample:
    """Compute and cache pe/rel_pe on a sample (idempotent)."""
    if "pe" in sample.extras and sample.extras["pe"].shape[1] == k:
        return sample
    pe = laplacian_pe(sample.senders, sample.receivers, sample.num_nodes, k)
    sample.extras["pe"] = pe
    sample.extras["rel_pe"] = np.abs(pe[sample.senders] - pe[sample.receivers])
    return sample
