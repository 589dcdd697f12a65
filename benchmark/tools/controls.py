"""Read a cell's model-level controls on the chip: the plain reference with
one of its own hyperparameters changed, put in the program's place.

    python3 benchmark/tools/controls.py <cell> <seed,seed,...> key=value [key=value ...]

For each seed: the cell's data and weights, the steps a run compares
(``run.check_entries``), the plain reference as the configuration states it,
and for each ``key=value`` the same reference with that one entry of its
``hyperparameters`` changed (``angle_blind=1`` for DimeNet++: the Legendre
part of the spherical basis set to 1; ``emulate="high"``: the products of
``reference/mlip.py::MATMUL``, for where no TPU is), or, for ``leave_out=n``,
the same reference on steps that lack their last ``n`` graphs, or, for
``precision="high"`` / ``"default"``, under that ``jax.default_matmul_precision``. Each control is put
through ``lib/check.py::compare`` with the CELL'S OWN LIMITS, as a run's
program is, and the verdict is printed: a control has to come out
``not correct``. The program itself is not run (``tools/limits.py`` reads the
sound runs). Not part of a benchmark run.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]


def main(argv):
    import jax

    from lib import check, weights
    from lib.cells import Cell
    from lib.program import Program
    from run import check_entries, signatures

    cell = Cell(argv[0])
    seeds = [int(s) for s in argv[1].split(",")]
    controls = [kv.split("=", 1) for kv in argv[2:]]
    jax.config.update("jax_default_matmul_precision", cell.config["precision"]["matmul"])
    hp = cell.reference.hyperparameters(cell.config)
    opt = dict(cell.config["optimizer_reference"], learning_rate=float(
        cell.config["NeuralNetwork"]["Training"]["Optimizer"]["learning_rate"]))
    limits = cell.config["limits"]
    scale = float(cell.config["input_scale"])
    for seed in seeds:
        graphs = cell.generator.generate(cell.traffic["params"], seed)
        prog = Program(cell.config, cell.traffic, graphs,
                       lambda sh: weights.make_weights(sh, seed, cell.config["weights"]))
        params0 = weights.flat_dict(prog.params0)
        checked = check_entries(prog, signatures(prog, int(cell.traffic["distinct_epochs"])),
                                int(cell.traffic["check_steps"]))
        steps = [[[graphs[j] for j in prog.corpus_index[chunk]]] for chunk, _ in checked]
        prog.release()
        del prog
        want = cell.follow(cell.reference.node_energy, hp, opt, params0, steps, scale)
        for key, value in controls:
            if key == "leave_out":
                ctl = cell.follow(cell.reference.node_energy, hp, opt, params0,
                                  [[sb[:-int(value)] for sb in step] for step in steps], scale)
            elif key == "precision":  # the chip's own lower product, as tools/limits.py
                with jax.default_matmul_precision(json.loads(value)):
                    ctl = cell.follow(cell.reference.node_energy, hp, opt, params0, steps, scale)
            else:
                ctl = cell.follow(cell.reference.node_energy, dict(hp, **{key: json.loads(value)}),
                                  opt, params0, steps, scale)
            ok, rows = check.compare(ctl, want, limits)
            print(f"seed {seed} control[{key}={value}] {'correct: NOT CAUGHT' if ok else 'not correct'}: "
                  + "  ".join(f"{r['name']}={r['value']:.3e} (limit {r['limit']:.0e}, "
                              f"{'ok' if r['ok'] else 'OVER'})@{r['where']}" for r in rows)
                  + f"  losses {ctl['losses']} vs {want['losses']}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
