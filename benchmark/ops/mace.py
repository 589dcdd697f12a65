"""Needed work of one MACE energy-and-force training step, from shapes.

Counted over REAL atoms and edges from the configuration's own sizes (C
channels, harmonics to ``max_ell``, hidden irreps to ``node_max_ell``,
correlation nu_max, 2 layers at MACE-MP-0), as ``benchmark/ops/egnn.py``
counts: every dense map reads its input rows and writes its output rows once
(rows x (in + out) elements of 4 B), every sum over a node's edges reads
E x width and writes N x width, multiply-adds as the equations read. Per layer,
with M_in = (l_in + 1)^2 components in (1, then (node_max_ell + 1)^2), P paths
(l1, l2, l3) of even sum and S = sum_paths (2 l3 + 1) path outputs a channel
(4 and 16, then 10 and 40):

  radial       E x (n_b w + w w + w w + w P C) multiply-adds
  up / mix / skip / product linear: rows x C x C each; the skip also reads its
               [C, C] matrix a node (it is gathered by species)
  tensor product (its own count, ``tensor_product``): per edge and channel
               sum_paths (2 l3 + 1)(2 l1 + 1 + 1) multiply-adds and 16 n_K for the
               couplings' contraction with Y; reads M_in C + P C + 16, writes S C;
               the sum at the receivers reads E S C and writes N S C
  contraction (``contraction``): per node and channel the monomials of degree
               2..nu_max (one multiply each) and, for every target component
               (L, M), degree nu and eta, one multiply-add a monomial of that
               degree, plus one for its weight; reads A (D C) and the weights'
               row (n_w C), writes B
  readouts     N x C (layer 1), N x (C h + h) (last layer)

The dense count over monomials is what the symmetric contraction needs when
nothing is known of U's zeros; an implementation that uses them needs less.
A step is 9 x the forward pass (forward, force gradient, parameter gradient
of both), the benchmark's convention (PERF.md section 3).
"""

from __future__ import annotations

import math

STEP_OVER_FORWARD = 9.0
ETA = {  # symmetric couplings of nu copies of l <= 3 to L (benchmark/reference/mace.py::symmetric_rank)
    3: {0: (1, 4, 8), 1: (1, 3, 12)},
}


def sizes(config: dict) -> dict:
    arch = config["NeuralNetwork"]["Architecture"]
    head = arch["output_heads"]["node"]
    if int(arch["max_ell"]) not in ETA or int(arch["node_max_ell"]) > 1:
        raise NotImplementedError("eta is tabulated for max_ell 3 and L <= 1")
    return {
        "C": int(arch["hidden_dim"]), "layers": int(arch["num_conv_layers"]),
        "max_ell": int(arch["max_ell"]), "node_ell": int(arch["node_max_ell"]),
        "nu": int(arch["correlation"]), "n_b": int(arch["num_radial"]),
        "radial": [64, 64, 64],  # MACE's default at every size
        "head": [int(d) for d in head["dim_headlayers"][: int(head["num_headlayers"])]] + [1],
    }


def paths(l_in: int, max_ell: int) -> list:
    return [(l1, l2, l3) for l1 in range(l_in + 1) for l2 in range(max_ell + 1)
            for l3 in range(abs(l1 - l2), min(l1 + l2, max_ell) + 1) if (l1 + l2 + l3) % 2 == 0]


def layers(s: dict):
    """(l_in, out_ell, paths) of each layer."""
    for t in range(s["layers"]):
        l_in = 0 if t == 0 else s["node_ell"]
        yield l_in, (0 if t == s["layers"] - 1 else s["node_ell"]), paths(l_in, s["max_ell"])


def tensor_product_forward(s: dict, nodes: float, edges: float) -> tuple[float, float]:
    """(multiply-adds, elements moved) of the interaction's tensor product and
    its sum at the receivers, all layers, one forward pass."""
    C, D = s["C"], (s["max_ell"] + 1) ** 2
    macs = elems = 0.0
    for l_in, _, pth in layers(s):
        m_in, slab = (l_in + 1) ** 2, sum(2 * l3 + 1 for _, _, l3 in pth)
        n_k = sum((2 * l1 + 1) * (2 * l3 + 1) for l1, _, l3 in pth)
        macs += edges * (D * n_k + C * sum((2 * l3 + 1) * (2 * l1 + 2) for l1, _, l3 in pth))
        elems += edges * (m_in * C + len(pth) * C + D + slab * C) + edges * slab * C + nodes * slab * C
    return macs, elems


def contraction_forward(s: dict, nodes: float) -> tuple[float, float]:
    """The same for the symmetric contraction."""
    C, D = s["C"], (s["max_ell"] + 1) ** 2
    monos = [math.comb(D + nu - 1, nu) for nu in range(1, s["nu"] + 1)]
    macs = elems = 0.0
    for _, out_ell, _ in layers(s):
        per = sum(monos[1:])
        n_w = 0
        for L in range(out_ell + 1):
            eta = ETA[s["max_ell"]][L][: s["nu"]]
            per += (2 * L + 1) * sum(e * (m + 1) for e, m in zip(eta, monos))
            n_w += sum(eta)
        macs += nodes * C * per
        elems += nodes * C * (D + n_w + (out_ell + 1) ** 2)
    return macs, elems


def forward(s: dict, nodes: float, edges: float) -> tuple[float, float]:
    """(multiply-adds, elements moved) of one forward pass."""
    C, D = s["C"], (s["max_ell"] + 1) ** 2
    macs, elems = tensor_product_forward(s, nodes, edges)
    m2, e2 = contraction_forward(s, nodes)
    macs, elems = macs + m2, elems + e2
    elems += nodes * C + edges * (9 + D + 1)  # embedding rows; edge vectors -> length, Y
    for t, (l_in, out_ell, pth) in enumerate(layers(s)):
        prev = s["n_b"]
        for w in s["radial"] + [len(pth) * C]:
            macs += edges * prev * w
            elems += edges * (prev + w)
            prev = w
        m_in, m_out = (l_in + 1) ** 2, (out_ell + 1) ** 2
        m_skip = (min(l_in, out_ell) + 1) ** 2
        slab = sum(2 * l3 + 1 for _, _, l3 in pth)
        macs += nodes * C * C * (m_in + slab + m_skip + m_out)
        elems += nodes * C * (2 * m_in + slab + D + 2 * m_skip + 2 * m_out)
        elems += nodes * C * C * (min(l_in, out_ell) + 1)  # the skip's matrix a node
        prev = C
        for d in (s["head"] if t == s["layers"] - 1 else [1]):
            macs += nodes * prev * d
            elems += nodes * (prev + d)
            prev = d
    return macs, elems


def _step(macs: float, elems: float) -> tuple[float, float]:
    return STEP_OVER_FORWARD * 2.0 * macs, STEP_OVER_FORWARD * 4.0 * elems


def needed(config: dict, nodes: float, edges: float, graphs: float) -> tuple[float, float]:
    """(FLOP, bytes) one training step needs for this many real atoms, edges
    and graphs."""
    return _step(*forward(sizes(config), float(nodes), float(edges)))


def tensor_product(config: dict, nodes: float, edges: float) -> tuple[float, float]:
    """(FLOP, bytes) of the scope ``interaction/tensor_product`` in a step."""
    return _step(*tensor_product_forward(sizes(config), float(nodes), float(edges)))


def contraction(config: dict, nodes: float, edges: float) -> tuple[float, float]:
    """(FLOP, bytes) of the scope ``product_basis/contraction`` in a step."""
    return _step(*contraction_forward(sizes(config), float(nodes)))
