"""Needed work of one DimeNet++ energy-and-force training step, from shapes.

Counted over REAL atoms (N), edges (E) and triplets (T) from the
configuration's own sizes (hidden H, output embedding O, triplet embedding I,
basis embedding B, S x R spherical basis, R radial), as the other files of
this directory count: every dense map reads its input rows and writes its
output rows once (rows x (in + out) elements of 4 B), every gather reads the
rows it gathers, every sum reads its rows and writes the summed ones,
multiply-adds as the equations read.

Once a model call (the layers share them):
  geometry   E x (pos_j, pos_i, shift -> vec, d) and T x (vec_ji, vec_kj ->
             angle): 2 gathered 3-vectors read and 1 angle written a triplet
  basis      rbf [E, R]; the radial part of sbf on the EDGES [E, S R]
             (~20 multiply-adds a value for the Bessel function); then a
             triplet reads the gathered S R row, makes S Legendre values and
             writes the S R row
Per conv layer:
  embedding  node Linear N x F x H; rbf Linear E x R x H; two gathered H rows
             an edge; E x 3H x H
  interaction/dense   lin_rbf1/2 (R -> B -> H), lin_ji, lin_kj, the product
             with the rbf filter, 2 x (before + after) residual linears, lin:
             all E x H x H
  interaction/triplets (its own count, ``triplets``): lin_down E x H x I; a
             triplet reads its S R basis row and one gathered I row, makes
             the two small products (S R x B, B x I) and the Hadamard product,
             and its I row is read once more by the sum onto ji, which writes
             E x I; lin_up E x I x H. The [T, B] and [T, I] intermediates are
             not counted: a pass over the triplets needs neither in memory
  output     rbf gate E x R x H, the gated sum E x H -> N x H, N x H x O,
             ``num_output_layers`` x N x O x O, N x O x H
and the node head once. A step is 9 x the forward pass (forward, force
gradient, parameter gradient of both), the benchmark's convention (PERF.md
section 3).
"""

from __future__ import annotations

STEP_OVER_FORWARD = 9.0
BESSEL_MACS = 20.0  # a spherical Bessel value: sin, cos and a degree-l polynomial in 1/x


def sizes(config: dict) -> dict:
    arch = config["NeuralNetwork"]["Architecture"]
    head = arch["output_heads"]["node"]
    return {
        "F": len(config["NeuralNetwork"]["Variables_of_interest"]["input_node_features"]),
        "H": int(arch["hidden_dim"]), "O": int(arch["out_emb_size"]),
        "I": int(arch["int_emb_size"]), "B": int(arch["basis_emb_size"]),
        "S": int(arch["num_spherical"]), "R": int(arch["num_radial"]),
        "layers": int(arch["num_conv_layers"]),
        "residual": int(arch["num_before_skip"]) + int(arch["num_after_skip"]),
        "output_layers": int(arch.get("num_output_layers") or 1),
        "head": [int(d) for d in head["dim_headlayers"][: int(head["num_headlayers"])]] + [1],
    }


def triplets_forward(s: dict, edges: float, triplets: float) -> tuple[float, float]:
    """(multiply-adds, elements moved) under ``interaction/triplets``, all
    layers, one forward pass."""
    H, I, B, SR = s["H"], s["I"], s["B"], s["S"] * s["R"]
    macs = edges * 2 * H * I + triplets * (SR * B + B * I + I)
    elems = edges * 2 * (H + I) + triplets * (SR + 2 * I) + edges * I
    return s["layers"] * macs, s["layers"] * elems


def basis_forward(s: dict, edges: float, triplets: float) -> tuple[float, float]:
    """The same for ``geometry`` + ``basis``, once a call."""
    R, SR = s["R"], s["S"] * s["R"]
    macs = edges * (5 + 4 * R + BESSEL_MACS * SR) + triplets * (20 + 2 * s["S"] + SR)
    elems = edges * (9 + 4 + 1 + R + SR) + triplets * (6 + 1 + 2 * SR)
    return macs, elems


def forward(s: dict, nodes: float, edges: float, triplets: float) -> tuple[float, float]:
    """(multiply-adds, elements moved) of one forward pass."""
    F, H, O, R, B = s["F"], s["H"], s["O"], s["R"], s["B"]
    macs, elems = basis_forward(s, edges, triplets)
    m2, e2 = triplets_forward(s, edges, triplets)
    macs, elems = macs + m2, elems + e2
    for layer in range(s["layers"]):
        f_in = F if layer == 0 else H
        # embedding
        macs += nodes * f_in * H + edges * (R * H + 3 * H * H)
        elems += nodes * (f_in + H) + edges * (R + H) + edges * 2 * H + edges * 4 * H
        # interaction/dense
        dense = 3 + 2 * s["residual"]
        macs += edges * (R * B + B * H + dense * H * H + H)
        elems += edges * (R + B + B + H) + edges * dense * 2 * H + edges * 3 * H
        # output
        macs += edges * (R * H + H) + nodes * (H * O + s["output_layers"] * O * O + O * H)
        elems += edges * (R + 3 * H) + edges * H + nodes * H \
            + nodes * (H + O + s["output_layers"] * 2 * O + O + H)
    prev = H
    for d in s["head"]:
        macs += nodes * prev * d
        elems += nodes * (prev + d)
        prev = d
    return macs, elems


def _step(macs: float, elems: float) -> tuple[float, float]:
    return STEP_OVER_FORWARD * 2.0 * macs, STEP_OVER_FORWARD * 4.0 * elems


def reckoned_triplets(nodes: float, edges: float) -> float:
    """Triplets from atoms and edges alone, for callers that are handed no
    count (``metrics/step_roofline_share.py``, ``step_mfu.py``): in a corpus
    whose every atom sends ``edges / nodes`` edges, each edge ji meets the
    edges ending at j, on average ``edges / nodes`` of them, less its exact
    reverse where that edge exists: ``edges x (edges / nodes - 1)``, the real
    count less at most one triplet an edge. A lower bound, within 2% at 50
    neighbours."""
    return edges * max(edges / max(nodes, 1.0) - 1.0, 0.0)


def needed(config: dict, nodes: float, edges: float, graphs: float) -> tuple[float, float]:
    """(FLOP, bytes) one training step needs for this many real atoms, edges
    and graphs; triplets by ``reckoned_triplets``."""
    nodes, edges = float(nodes), float(edges)
    return _step(*forward(sizes(config), nodes, edges, reckoned_triplets(nodes, edges)))


def triplets(config: dict, nodes: float, edges: float, triplets: float) -> tuple[float, float]:
    """(FLOP, bytes) of the scope ``interaction/triplets`` in a step, for the
    real triplet count."""
    return _step(*triplets_forward(sizes(config), float(edges), float(triplets)))
