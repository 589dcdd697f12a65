"""Needed work of one SchNet energy-and-force training step, from shapes.

Counted over REAL atoms (N) and edges (E) from the configuration's own widths
(hidden H, filters F, Gaussians G), as the other files of this directory
count: a dense layer reads its input rows and writes its output rows once
(rows x (in + out) elements of 4 B) and costs rows x in x out multiply-adds;
a gather reads the rows it gathers; a sum writes the summed rows. Elementwise
work (the Gaussians' exponentials, ssp, the cutoff window) is counted by the
elements it moves, not as multiply-adds. A layer l reads node features of
width w_l (the raw feature's, 1, for l = 0, as HydraGNN runs it, else H).

  scope       multiply-adds                     elements moved
  geometry    E x 9                             E x (2 x 3 gathered + 3 + 1 + 1)
  smearing    E x 2 G                           E x (1 + G)          once a call
  filter      E x (G F + F F)                   E x ((G + F) + (F + F))
              filter1, ssp, filter2 with the window folded into its write:
              116,736 multiply-adds and 968 elements an edge a layer at the
              published 200 / 256
  aggregate   N x w_l F + E x F                 N x (w_l + F) + E x 2 F + N x F
              lin1; then the [E, F] filter rows and the gathered [E, F] sender
              rows read once and [N, F] written: the same work whatever
              implements it, the fused kernel or XLA's gather, multiply and
              segment_sum (which also writes and re-reads the [E, F] messages)
  update      N x F H                           N x (F + H)
  head        N x (H h_1 + ... + h_k)           N x (H + h_1) + ...

At the published widths and 50 edges an atom the filter network is 5.84M of a
layer's 6.37M multiply-adds an atom: 92% of the model's arithmetic is two dense
layers on E rows. A step is 9 x the forward pass (forward, force gradient,
parameter gradient of both), the benchmark's convention (``ops/egnn.py``).
"""

from __future__ import annotations

STEP_OVER_FORWARD = 9.0


def widths(config: dict) -> dict:
    arch = config["NeuralNetwork"]["Architecture"]
    head = arch["output_heads"]["node"]
    return {
        "hidden": int(arch["hidden_dim"]),
        "filters": int(arch["num_filters"]),
        "gaussians": int(arch["num_gaussians"]),
        "layers": int(arch["num_conv_layers"]),
        "input": len(config["NeuralNetwork"]["Variables_of_interest"]["input_node_features"]),
        "head": [int(d) for d in head["dim_headlayers"][: int(head["num_headlayers"])]] + [1],
    }


def filter_forward(w: dict, edges: float) -> tuple[float, float]:
    """(multiply-adds, elements moved) under ``filter``, all layers, one
    forward pass."""
    f, g = w["filters"], w["gaussians"]
    return w["layers"] * edges * (g * f + f * f), w["layers"] * edges * (g + 3 * f)


def aggregate_forward(w: dict, nodes: float, edges: float) -> tuple[float, float]:
    """The same for ``aggregate``."""
    f = w["filters"]
    macs = elems = 0.0
    for layer in range(w["layers"]):
        wl = w["input"] if layer == 0 else w["hidden"]
        macs += nodes * wl * f + edges * f
        elems += nodes * (wl + f) + edges * 2 * f + nodes * f
    return macs, elems


def forward(w: dict, nodes: float, edges: float) -> tuple[float, float]:
    """(multiply-adds, elements moved) of one forward pass."""
    h, f, g = w["hidden"], w["filters"], w["gaussians"]
    macs, elems = edges * (9 + 2 * g), edges * (11 + 1 + g)
    for part in (filter_forward(w, edges), aggregate_forward(w, nodes, edges)):
        macs, elems = macs + part[0], elems + part[1]
    macs += w["layers"] * nodes * f * h
    elems += w["layers"] * nodes * (f + h)
    prev = h
    for d in w["head"]:
        macs += nodes * prev * d
        elems += nodes * (prev + d)
        prev = d
    return macs, elems


def _step(macs: float, elems: float) -> tuple[float, float]:
    return STEP_OVER_FORWARD * 2.0 * macs, STEP_OVER_FORWARD * 4.0 * elems


def needed(config: dict, nodes: float, edges: float, graphs: float) -> tuple[float, float]:
    """(FLOP, bytes) one training step needs for this many real atoms, edges
    and graphs."""
    return _step(*forward(widths(config), float(nodes), float(edges)))


def filter(config: dict, nodes: float, edges: float) -> tuple[float, float]:
    """(FLOP, bytes) of the scope ``filter`` in a step."""
    return _step(*filter_forward(widths(config), float(edges)))


def aggregate(config: dict, nodes: float, edges: float) -> tuple[float, float]:
    """(FLOP, bytes) of the scope ``aggregate`` in a step."""
    return _step(*aggregate_forward(widths(config), float(nodes), float(edges)))
