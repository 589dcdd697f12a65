"""Needed work of one PaiNN energy-and-force training step, from shapes.

Counted over REAL atoms and edges from the configuration's own widths. A
block l works at width w_l (the node feature's for l = 0, as HydraGNN runs it,
else F) and leaves width F; R radial functions.

  forward multiply-adds per block:
      filter      E x R 3w                     W(r)
      phi         N x (w w + w 3w)
      messages    E x 3 w x 4 / 2              the [E, 3, w] products and sums
      U, V        N x 3 x 2 w w
      a           N x (2w w + w m w)           m = 3, or 2 in the last block
      lift        N x (w F + F F) + N x 3 w F  (no vector lift in the last)
  head            N x (F h_1 + ... + h_k)
  forward bytes, 4 B an element: a dense layer reads its input rows and
  writes its output rows once; the gathers read E x 3w (phi) and E x 3 w (v),
  the vector messages are written and read once (2 E 3 w) and summed into
  N x 3 w, the scalar messages E x w into N x w.

A step is 9 x the forward pass (forward, force gradient, parameter gradient of
both), as in ``ops/egnn.py``.
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
        "radial": int(arch["num_radial"]),
        "head": [int(d) for d in head["dim_headlayers"][: int(head["num_headlayers"])]] + [1],
    }


def forward(w: dict, nodes: float, edges: float) -> tuple[float, float]:
    f, r = w["hidden"], w["radial"]
    macs = elems = 0.0
    for layer in range(w["layers"]):
        wl = w["input"] if layer == 0 else f
        last = layer == w["layers"] - 1
        m = 2 if last else 3
        macs += edges * r * 3 * wl
        elems += edges * (r + 3 * wl)
        macs += nodes * (wl * wl + wl * 3 * wl)
        elems += nodes * ((wl + wl) + (wl + 3 * wl))
        macs += edges * 3 * wl * 2
        elems += edges * (3 * wl + 3 * wl) + 2 * edges * 3 * wl + nodes * 3 * wl \
            + edges * wl + nodes * wl
        macs += nodes * 3 * 2 * wl * wl
        elems += nodes * 3 * 2 * (wl + wl)
        macs += nodes * (2 * wl * wl + wl * m * wl)
        elems += nodes * ((2 * wl + wl) + (wl + m * wl))
        macs += nodes * (wl * f + f * f)
        elems += nodes * ((wl + f) + (f + f))
        if not last:
            macs += nodes * 3 * wl * f
            elems += nodes * 3 * (wl + f)
    prev = f
    for d in w["head"]:
        macs += nodes * prev * d
        elems += nodes * (prev + d)
        prev = d
    return macs, elems


def needed(config: dict, nodes: float, edges: float, graphs: float) -> tuple[float, float]:
    macs, elems = forward(widths(config), float(nodes), float(edges))
    return STEP_OVER_FORWARD * 2.0 * macs, STEP_OVER_FORWARD * 4.0 * elems
