"""Each bucketed benchmark cell's pad-bucket table and what it pads, replayed
on the CPU from the traffic file alone: no graph is generated, no JAX touched.

    python3 run-scripts/replay_pad_buckets.py [ROOT [CELL ...]]

``ROOT`` is a checkout of this repository (default: the one this file is in),
so the same file replays a parent checkout's table rule and the working
tree's. For every cell of ``BENCHMARK.json`` whose configuration sets
``Training.pad_buckets`` above 1: the corpus's sizes
(``benchmark/generators/<generator>.py::sizes``; every atom sends exactly
``max_neighbours`` edges, so a structure's edges are that many times its
atoms) -> ``split_dataset`` -> ``create_dataloaders`` (the table:
``graphs/batching.py::compute_pad_buckets``) -> the train loader's
``batch_plan()`` for each of the window's ``distinct_epochs`` shuffles. The
samples are stand-ins that carry sizes and a degree-``max_neighbours`` edge
list (what ``triplet_cap`` reads), nothing else.

Prints a cell: the table, the steps and the share of steps a bucket, the mean
edge slots a step, and the padded share of the edge slots, which is what a
run's ``padded_edge_share`` / ``loader_padded_edge_share`` reads when its
window holds whole rounds of those epochs (under a triplet cap the triplet
slots are ``cap x n_edge``, so their ratio between two tables is the same).
On the quantile rule of PR 39 and before it gives 72.198 / 37.004 / 45.936%
for MACE / EGNN / DimeNet++ (the ledger's figures) and 30.6% for SchNet.
Last line: a JSON object of the same numbers, a key a cell.
"""

import json
import os
import sys

import numpy as np

root = os.path.abspath(sys.argv[1] if len(sys.argv) > 1
                       else os.path.join(os.path.dirname(__file__), ".."))
sys.path[:0] = [root, os.path.join(root, "benchmark")]

from lib.cells import Cell  # noqa: E402

from hydragnn_tpu.preprocess.load_data import create_dataloaders, split_dataset  # noqa: E402


class Sized:
    """What the table and the plan read of a sample: its sizes, and an edge
    list in which every atom sends and receives ``k`` edges."""

    def __init__(self, atoms: int, k: int):
        self.num_nodes, self.num_edges, self.extras = atoms, atoms * k, {}
        self.senders = self.receivers = np.repeat(np.arange(atoms), k)


def replay(name: str) -> dict | None:
    cell = Cell(name)
    training = dict(cell.config["NeuralNetwork"]["Training"])
    training.update(cell.traffic.get("training", {}))
    n_buckets = int(training.get("pad_buckets", 0) or 0)
    if n_buckets <= 1:
        return None
    params = cell.traffic["params"]
    k = int(params["max_neighbours"])
    samples = [Sized(int(n), k) for n in cell.generator.sizes(params)]
    arch = cell.config["NeuralNetwork"]["Architecture"]
    cap = min(int(arch.get("max_neighbours") or 0), k) if arch.get("mpnn_type") == "DimeNet" else 0
    train, val, test = split_dataset(samples, float(training.get("perc_train", 0.7)))
    loader, _, _ = create_dataloaders(train, val, test, int(training["batch_size"]),
                                      buckets=n_buckets, triplet_cap=cap)
    steps = {b.as_tuple(): 0 for b in loader.buckets}
    real = 0
    for epoch in range(int(cell.traffic["distinct_epochs"])):
        loader.set_epoch(epoch)
        for chunk, pad in loader.batch_plan():
            steps[pad.as_tuple()] += 1
            real += sum(train[i].num_edges for i in chunk)
    n_steps = sum(steps.values())
    slots = sum(key[1] * n for key, n in steps.items())
    out = {"table": [list(key) for key in steps], "steps": list(steps.values()),
           "mean_edge_slots": slots / n_steps, "padded_edge_share": 100.0 * (1.0 - real / slots)}
    print(f"{name}: batch {loader.batch_size}, {len(train)} train samples, "
          f"{n_steps} steps in {cell.traffic['distinct_epochs']} epochs")
    for key, n in steps.items():
        print(f"  (nodes, edges, graphs, triplets) {key}: {n} steps, {100.0 * n / n_steps:.2f}%")
    print(f"  mean edge slots a step {out['mean_edge_slots']:.1f}, "
          f"padded_edge_share {out['padded_edge_share']:.3f}%")
    return out


def main() -> None:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    print(json.dumps({name: out for name in sys.argv[2:] or names if (out := replay(name))}))


if __name__ == "__main__":
    main()
