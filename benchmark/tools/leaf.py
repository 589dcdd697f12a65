"""Look into ONE leaf of a cell's comparison, entry by entry, on the chip.

    python3 benchmark/tools/leaf.py [--rehearse] <cell> <seed> [leaf | -] [out.json]

The steps a run compares, through the program and through the plain
reference, as ``tools/limits.py`` drives them, but keeping the trees: for the
leaf named (default: the one whose ``change_norm`` gap is the worst) every
entry's change after the last step and its gradient at each step, on both
sides (the program's gradients from its first moments: g_t = (mu_t - b1
mu_(t-1)) / (1 - b1)). Says which entries make the gap, and what their
gradients were: under Adam an entry whose gradient is nought to rounding at
a step moves a full learning rate in the direction its round-off points.
``--rehearse`` takes the tiny sizes, to try the tool on the CPU. Not part of
a benchmark run.
"""

from __future__ import annotations

import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]


def main(argv):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from lib import check, weights
    from lib.cells import Cell, load_module
    from lib.program import Program
    from run import check_entries, signatures

    rehearse = "--rehearse" in argv
    argv = [a for a in argv if a != "--rehearse"]
    cell, seed = Cell(argv[0], rehearse=rehearse), int(argv[1])
    leaf = argv[2] if len(argv) > 2 and argv[2] != "-" else None
    jax.config.update("jax_default_matmul_precision", cell.config["precision"]["matmul"])
    hp = cell.reference.hyperparameters(cell.config)
    opt = dict(cell.config["optimizer_reference"], learning_rate=float(
        cell.config["NeuralNetwork"]["Training"]["Optimizer"]["learning_rate"]))
    b1 = opt["b1"]
    graphs = cell.generator.generate(cell.traffic["params"], seed)
    prog = Program(cell.config, cell.traffic, graphs,
                   lambda sh: weights.make_weights(sh, seed, cell.config["weights"]))
    params0 = weights.flat_dict(prog.params0)
    checked = check_entries(prog, signatures(prog, int(cell.traffic["distinct_epochs"])),
                            int(cell.traffic["check_steps"]))
    prog.step.capture = len(checked)
    prog.steps(checked)
    captured = jax.device_get(prog.step.captured)
    index = prog.corpus_index
    prog.release()
    del prog
    gc.collect()

    def mu(state):
        return {k: np.asarray(v, np.float64) for k, v in weights.flat_dict(
            optax.tree_utils.tree_get(state, "mu")).items()}

    mus = [mu(c[1]) for c in captured]
    got_grads = [{k: (m[k] - (b1 * mus[t - 1][k] if t else 0.0)) / (1.0 - b1) for k in m}
                 for t, m in enumerate(mus)]
    last = weights.flat_dict(captured[-1][0])
    got_change = {k: np.asarray(last[k], np.float64) - np.asarray(params0[k], np.float64)
                  for k in last}

    # the reference, as reference/<objective>.py::follow, keeping the trees
    ref = load_module("reference", cell.config["objective"])
    steps = [[[graphs[j] for j in index[chunk]]] for chunk, _ in checked]
    scale = float(cell.config["input_scale"])
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params0.items()}
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    want_grads, want_params = [], []
    for t, sub_batches in enumerate(steps, start=1):
        _, grad = ref.step_loss_and_grad(cell.reference.node_energy, hp, params, sub_batches, scale)
        want_grads.append({k: np.asarray(g, np.float64) for k, g in grad.items()})
        params, m, v = ref.adamw_update(params, grad, m, v, t, opt["learning_rate"], b1,
                                        opt["b2"], opt["eps"], opt["weight_decay"])
        want_params.append({k: np.asarray(x, np.float64) for k, x in params.items()})
    want_change = {k: np.asarray(params[k], np.float64) - np.asarray(params0[k], np.float64)
                   for k in params}

    # where the two sides part: entries of ANY leaf whose parameter differs by over a thousandth
    # of a learning rate after a step, the largest first, with that step's gradients
    lr = opt["learning_rate"]
    for t in range(len(captured)):
        got_params = {k: np.asarray(x, np.float64)
                      for k, x in weights.flat_dict(captured[t][0]).items()}
        apart = []
        for k, x in got_params.items():
            d = np.abs(x - want_params[t][k]).ravel()
            for j in np.flatnonzero(d > lr / 1000.0):
                apart.append((d[j], k, int(j)))
        apart.sort(reverse=True)
        print(f"after step {t + 1}: {len(apart)} entries of all leaves lie over lr / 1000 apart, "
              f"{sum(1 for a in apart if a[0] > lr / 20.0)} over lr / 20"
              + (": the largest" if apart else ""))
        for d, k, j in apart[:6]:
            size = float(np.median(np.abs(want_grads[t][k])))
            print(f"  {k}[{j}]: parameters {d / lr:.3f} lr apart; this step's gradient reference "
                  f"{want_grads[t][k].ravel()[j]:+.3e} program {got_grads[t][k].ravel()[j]:+.3e} "
                  f"(the leaf's median |gradient| {size:.3e})")

    def norms(tree):
        return {k: float(np.sqrt(np.sum(np.square(x)))) for k, x in tree.items()}

    gap, worst = check.worst_leaf_gap(norms(got_change), norms(want_change))
    leaf = leaf or worst
    floor = float(np.median(list(norms(want_change).values())))
    grad_floor = float(np.median(list(norms(want_grads[0]).values())))
    print(f"{cell.name} seed {seed}: change_norm {gap:.4e} at {worst}; grad_norm "
          f"{check.worst_leaf_gap(norms(got_grads[0]), norms(want_grads[0]))}")
    cp, cr = got_change[leaf].ravel(), want_change[leaf].ravel()
    gp = [g[leaf].ravel() for g in got_grads]
    gr = [g[leaf].ravel() for g in want_grads]

    def leaf_gap(keep):
        return abs(np.linalg.norm(cp[keep]) - np.linalg.norm(cr[keep])) / max(
            np.linalg.norm(cr[keep]), floor)

    everything = np.ones(cp.shape, bool)
    med = [float(np.median(np.abs(g))) for g in gr]
    small = np.zeros(cp.shape, bool)
    for g, scale_t in zip(gr, med):
        small |= np.abs(g) < 1e-3 * scale_t
    diff2 = np.square(cp - cr)
    order = np.argsort(-diff2)
    print(f"leaf {leaf}: {cp.size} entries, |change| reference {np.linalg.norm(cr):.6e} program "
          f"{np.linalg.norm(cp):.6e}, gap as compared {leaf_gap(everything):.4e}; the leaf's first "
          f"gradient is {np.linalg.norm(gr[0]) / grad_floor:.3g} x the median leaf's")
    print(f"  median |reference gradient| of the leaf by step: {med}")
    print(f"  entries whose reference gradient is under a thousandth of that at some step: "
          f"{int(small.sum())}; gap without them {leaf_gap(~small):.4e}; "
          f"gap without the one entry that differs most "
          f"{leaf_gap(np.arange(cp.size) != order[0]):.4e}")
    print(f"  share of |program - reference|^2 in the 1 / 3 / 10 entries that differ most: "
          + " / ".join(f"{diff2[order[:k]].sum() / diff2.sum():.3f}" for k in (1, 3, 10)))
    rows = []
    for j in order[:8]:
        rows.append({"entry": int(j), "change_reference": cr[j], "change_program": cp[j],
                     "grad_reference": [g[j] for g in gr], "grad_program": [g[j] for g in gp]})
        print(f"  entry {j}: change ref {cr[j]:+.4e} prog {cp[j]:+.4e}; gradients by step ref "
              + " ".join(f"{g[j]:+.3e}" for g in gr) + " prog "
              + " ".join(f"{g[j]:+.3e}" for g in gp))
    if len(argv) > 3:
        os.makedirs(os.path.dirname(os.path.abspath(argv[3])), exist_ok=True)
        with open(argv[3], "w") as f:
            json.dump({"cell": cell.name, "seed": seed, "leaf": leaf, "gap": leaf_gap(everything),
                       "gap_without_small": leaf_gap(~small), "small_entries": int(small.sum()),
                       "median_abs_grad": med, "rows": rows,
                       "change_reference": cr.tolist(), "change_program": cp.tolist(),
                       "grad_reference": [g.tolist() for g in gr],
                       "grad_program": [g.tolist() for g in gp]}, f)


if __name__ == "__main__":
    main(sys.argv[1:])
