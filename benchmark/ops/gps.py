"""Needed work of one GPS-round-EGNN energy-and-force training step, from
shapes.

Counted over REAL atoms (N), edges (E) and attention pairs (P = sum over the
step's graphs of n_g squared: every atom attends to every atom of its own
structure), from the configuration's own widths (hidden H, heads h, encodings
k), whatever implements them: dense per-graph blocks of the corpus's largest
structure's width run 8 x these pairs, and that is the program's choice, not
the algorithm's need. As the other files of this directory count: a dense
layer reads its input rows and writes its output rows once (rows x (in + out)
elements of 4 B) and costs rows x in x out multiply-adds; a sum over a node's
edges reads E x width and writes N x width; elementwise work (a batch norm,
the softmax) is counted by the elements it moves.

  forward, per layer:
  rel_pos_emb   E x k H                           E x (k + H)
  local (EGNN)  phi_e  E x ((3H + 1) H + H H)     E x ((3H + 1 + H) + 2H)
                phi_x  E x (H H + H)              as ``ops/egnn.py``   (layers but the last)
                phi_h  N x (2H H + H H)           N x (3H + 2H), sum of messages E x H + N x H
  attention     q, k, v, out: N x 4 H H           N x 4 x 2H
                scores and weighted sum: 2 P H    q, k, v read and the result written: N x 4H
                softmax (the scope of that name): h P logits read, h P weights written
  norm          three a layer: N x H read and written each
  feed_forward  N x (H 2H + 2H H)                 N x (3H + 3H)
  embedding     N x (w H + k H + 2H H) once;  head as ``ops/egnn.py``

A step is 9 x the forward pass (forward, force gradient, parameter gradient of
both), the benchmark's convention (``ops/egnn.py``). ``needed`` is handed
atoms, edges and graphs only (``metrics/step_roofline_share.py``), so it takes
P at its lower bound N squared / G (equal structures; the corpus's log-normal
sizes make the true P about 1.2 x that, and attention's pair terms are under
1% of the step's multiply-adds). The scope functions are handed the counted P
(``graphs/batching.py`` notes it on the ``collate`` span).
"""

from __future__ import annotations

import glob
import os

STEP_OVER_FORWARD = 9.0

# What the cell asks of the program: the configuration key that runs the conv
# stack as one scanned body, ``Training.scan_conv_layers``. A program whose
# configuration schema does not know the key ignores it (``update_config``
# rejects no ``Training`` key) and builds the ten layers unrolled: a step
# program of 171-208 MB that no compile cache holds, 830 s of set-up in EVERY
# run (PERF.md section 6, "GPS, three attempts"), which no run limit admits.
# The driver tries a new cell on the parent commit with these benchmark files
# laid over it, and a commit cannot be given a validation after the fact: so
# the cell's own files say it here, where ``lib/cells.py`` loads them, before
# jax starts. It reads the schema's text for the key, whatever module
# implements it; it imports nothing of the program.
_SCHEMA = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
                       "hydragnn_tpu", "config", "*.py")
if not any("scan_conv_layers" in open(path).read() for path in glob.glob(_SCHEMA)):
    raise SystemExit(
        "gps_egnn_mlip_oc20: this program's configuration schema (hydragnn_tpu/config) has no "
        "Training.scan_conv_layers; unrolled, the cell's step program is larger than the "
        "compile cache and is compiled anew in every run, 830 s of set-up")


def widths(config: dict) -> dict:
    arch = config["NeuralNetwork"]["Architecture"]
    head = arch["output_heads"]["node"]
    return {
        "hidden": int(arch["hidden_dim"]),
        "heads": int(arch["global_attn_heads"]),
        "encodings": int(arch["pe_dim"]),
        "layers": int(arch["num_conv_layers"]),
        "input": len(config["NeuralNetwork"]["Variables_of_interest"]["input_node_features"]),
        "coordinate_updates": bool(arch.get("equivariance")),
        "head": [int(d) for d in head["dim_headlayers"][: int(head["num_headlayers"])]] + [1],
    }


def softmax_forward(w: dict, pairs: float) -> tuple[float, float]:
    """(multiply-adds, elements moved) under ``softmax``, all layers, one
    forward pass."""
    return 0.0, w["layers"] * 2.0 * w["heads"] * pairs


def attention_forward(w: dict, nodes: float, pairs: float) -> tuple[float, float]:
    """The same for ``attention`` (projections, scores, softmax, weighted sum)."""
    h = w["hidden"]
    macs = w["layers"] * (nodes * 4 * h * h + 2.0 * pairs * h)
    elems = w["layers"] * (nodes * 4 * 2 * h + nodes * 4 * h)
    return macs, elems + softmax_forward(w, pairs)[1]


def local_forward(w: dict, nodes: float, edges: float) -> tuple[float, float]:
    """The same for ``local``: the EGNN conv with an ``[E, H]`` edge attribute."""
    h = w["hidden"]
    macs = elems = 0.0
    for layer in range(w["layers"]):
        macs += edges * ((3 * h + 1) * h + h * h)
        elems += edges * ((3 * h + 1 + h) + (h + h))
        if w["coordinate_updates"] and layer < w["layers"] - 1:
            macs += edges * (h * h + h)
            elems += edges * ((h + h) + (h + 1)) + edges * 3 + nodes * 3
        macs += nodes * (2 * h * h + h * h)
        elems += nodes * ((2 * h + h) + (h + h))
        elems += edges * h + nodes * h  # sum of messages at the sender
    return macs, elems


def forward(w: dict, nodes: float, edges: float, pairs: float) -> tuple[float, float]:
    """(multiply-adds, elements moved) of one forward pass."""
    h, k, layers = w["hidden"], w["encodings"], w["layers"]
    macs = nodes * (w["input"] * h + k * h + 2 * h * h)
    elems = nodes * ((w["input"] + h) + (k + h) + (2 * h + h))
    for part in (local_forward(w, nodes, edges), attention_forward(w, nodes, pairs)):
        macs, elems = macs + part[0], elems + part[1]
    macs += layers * (edges * k * h + nodes * 4 * h * h)  # rel_pos_emb, feed_forward
    elems += layers * (edges * (k + h) + nodes * 6 * h + 3 * nodes * 2 * h)  # and the norms
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
    and graphs; attention pairs at their lower bound (module docstring)."""
    nodes = float(nodes)
    pairs = nodes * nodes / max(float(graphs), 1.0)
    return _step(*forward(widths(config), nodes, float(edges), pairs))


def attention(config: dict, nodes: float, pairs: float) -> tuple[float, float]:
    """(FLOP, bytes) of the scope ``attention`` in a step."""
    return _step(*attention_forward(widths(config), float(nodes), float(pairs)))


def softmax(config: dict, pairs: float) -> tuple[float, float]:
    """(FLOP, bytes) of the scope ``softmax`` in a step."""
    return _step(*softmax_forward(widths(config), float(pairs)))

