"""Needed work of one EGNN energy-and-force training step, from shapes.

Counted over REAL atoms and edges (padding is the program's choice, not the
algorithm's need), from the configuration's own widths:

  forward multiply-adds, per layer l (w_l = input width: the node feature's
  for l = 0, else H):
      phi_e   E x ((2 w_l + 1) H + H H)
      phi_x   E x (H H + H)                    layers with a coordinate update
      phi_h   N x ((w_l + H) H + H H)
  head        N x (H h_1 + h_1 h_2 + ... + h_k)
  forward bytes, 4 B an element: every dense layer reads its input rows and
  writes its output rows once (rows x (in + out)); every sum over a node's
  edges reads E x width and writes N x width.

A step is forward + the force gradient (2 x forward) + the parameter gradient
of both (2 x again): 9 x the forward pass, in operations and in bytes. That is
the convention of this benchmark; recomputed work does not count.
"""

from __future__ import annotations

STEP_OVER_FORWARD = 9.0


def widths(config: dict) -> dict:
    arch = config["NeuralNetwork"]["Architecture"]
    head = arch["output_heads"]["node"]
    return {
        "hidden": int(arch["hidden_dim"]),
        "layers": int(arch["num_conv_layers"]),
        "input": len(config["NeuralNetwork"]["Variables_of_interest"]["input_node_features"]),
        "coordinate_updates": bool(arch.get("equivariance")),
        "head": [int(d) for d in head["dim_headlayers"][: int(head["num_headlayers"])]] + [1],
    }


def forward(w: dict, nodes: float, edges: float) -> tuple[float, float]:
    """(multiply-adds, elements moved) of one forward pass."""
    h = w["hidden"]
    macs = elems = 0.0
    for layer in range(w["layers"]):
        wl = w["input"] if layer == 0 else h
        macs += edges * ((2 * wl + 1) * h + h * h)
        elems += edges * ((2 * wl + 1 + h) + (h + h))
        if w["coordinate_updates"] and layer < w["layers"] - 1:
            macs += edges * (h * h + h)
            elems += edges * ((h + h) + (h + 1)) + edges * 3 + nodes * 3
        macs += nodes * ((wl + h) * h + h * h)
        elems += nodes * ((wl + h + h) + (h + h))
        elems += edges * h + nodes * h  # sum of messages at the sender
    prev = h
    for d in w["head"]:
        macs += nodes * prev * d
        elems += nodes * (prev + d)
        prev = d
    return macs, elems


def needed(config: dict, nodes: float, edges: float, graphs: float) -> tuple[float, float]:
    """(FLOP, bytes) one training step needs for this many real atoms, edges
    and graphs."""
    macs, elems = forward(widths(config), float(nodes), float(edges))
    return STEP_OVER_FORWARD * 2.0 * macs, STEP_OVER_FORWARD * 4.0 * elems
