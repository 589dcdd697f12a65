"""Read the numbers a cell's limits are set from, on the chip, in one process.

    python3 benchmark/tools/limits.py <cell> <seed,seed,...> [control seeds [high,default]]

For each seed: the cell's data and weights, the steps a run compares (one
batch of every padded shape, ``run.check_entries``) through ``train_epoch``
(the window's own call and feed, the cell's own batch), the plain reference at the configuration's precision, and the three
gaps of ``lib/check.py``. For each control seed also the CONTROL: the same
reference computed one precision step down (``high``, three bf16 passes, for
float32 at ``highest``; and the TPU's default single pass, which is what the
program's dense layers do when nothing sets a precision), put in the
program's place. A limit goes above the sound runs' largest gap and below
the control's smallest. Not part of a benchmark run.
"""

from __future__ import annotations

import gc
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

LOWER = {"highest": ("high", "default"), "high": ("default",), "default": ()}


def main(argv):
    import jax
    import optax

    from lib import check, weights
    from lib.cells import Cell
    from lib.program import Program
    from run import check_entries, signatures

    cell = Cell(argv[0])
    seeds = [int(s) for s in argv[1].split(",")]
    control_seeds = {int(s) for s in argv[2].split(",")} if len(argv) > 2 else set()
    stated = cell.config["precision"]["matmul"]
    lowers = argv[3].split(",") if len(argv) > 3 else LOWER[stated]
    jax.config.update("jax_default_matmul_precision", stated)
    hp = cell.reference.hyperparameters(cell.config)
    opt = dict(cell.config["optimizer_reference"], learning_rate=float(
        cell.config["NeuralNetwork"]["Training"]["Optimizer"]["learning_rate"]))
    none = {"loss": float("inf"), "grad_norm": float("inf"), "change_norm": float("inf")}
    for seed in seeds:
        t0 = time.perf_counter()
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
        got = check.program_numbers(
            captured, params0, weights.flat_dict,
            lambda s: optax.tree_utils.tree_get(s, "mu"), opt["b1"])
        ref_steps = [[[graphs[j] for j in index[chunk]]] for chunk, _ in checked]
        scale = float(cell.config["input_scale"])
        want = cell.follow(cell.reference.node_energy, hp, opt, params0, ref_steps, scale)
        _, rows = check.compare(got, want, none)
        print(f"seed {seed} sound   " + "  ".join(
            f"{r['name']}={r['value']:.3e}@{r['where']}" for r in rows)
            + f"  losses {got['losses']} vs {want['losses']}  ({time.perf_counter() - t0:.0f} s)",
            flush=True)
        if seed in control_seeds:
            for lower in lowers:
                with jax.default_matmul_precision(lower):
                    ctl = cell.follow(cell.reference.node_energy, hp, opt, params0,
                                      ref_steps, scale)
                _, rows = check.compare(ctl, want, none)
                print(f"seed {seed} control[{lower}] " + "  ".join(
                    f"{r['name']}={r['value']:.3e}@{r['where']}" for r in rows), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
