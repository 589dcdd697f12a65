"""The comparison that decides ``correct``.

The program's first steps (taken through the window's own call and feed, on
the cell's own batches) against the plain reference following the same
steps from the same seeded weights. Three kinds of number, each with a limit
of its own from the configuration file (``limits``):

  loss         worst relative gap of a step's loss
  grad_norm    worst leaf: gap between the program's norm of the first
               gradient (as the optimizer got it: its first moment after one
               step, divided by 1 - b1) and the reference's, against the
               reference's norm of that leaf or of the median leaf,
               whichever is larger
  change_norm  the same for the norm of the parameters' change after the
               last of the steps

and, after the window, every step's loss finite. Where the model keeps batch
statistics (``stats_norm`` in both sides' numbers) a fourth, under a limit of
its own:

  stats_norm   the same for the norm of each statistic after the last of the
               steps: the running means and variances move with every batch,
               outside the gradient, so no other number sees them
"""

from __future__ import annotations

import numpy as np


def _norms(tree: dict) -> dict:
    return {k: float(np.sqrt(np.sum(np.square(np.asarray(v, np.float64))))) for k, v in tree.items()}


def program_numbers(captured, params0: dict, flat_dict, first_moment, b1: float) -> dict:
    """From the state copies the step wrapper kept:
    [(params, opt_state, batch_stats, loss)]."""
    losses = [float(np.asarray(c[-1])) for c in captured]
    mu = flat_dict(first_moment(captured[0][1]))
    grad = {k: np.asarray(v, np.float64) / (1.0 - b1) for k, v in mu.items()}
    last = flat_dict(captured[-1][0])
    change = {k: np.asarray(last[k], np.float64) - np.asarray(params0[k], np.float64) for k in last}
    out = {"losses": losses, "grad_norm": _norms(grad), "change_norm": _norms(change)}
    stats = flat_dict(captured[-1][2])
    if stats:
        out["stats_norm"] = _norms(stats)
    return out


def worst_leaf_gap(got: dict, want: dict) -> tuple[float, str]:
    floor = float(np.median(list(want.values())))
    worst, where = 0.0, ""
    for k, w in want.items():
        # a leaf the program does not hand over reads as not finite
        gap = abs(got.get(k, float("nan")) - w) / max(w, floor, 1e-30)
        if not np.isfinite(gap):
            return float("inf"), k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def compare(got: dict, want: dict, limits: dict) -> tuple[bool, list[dict]]:
    """[{name, value, limit, ok, where}] for each number compared."""
    rows = []
    gaps = [abs(g - w) / max(abs(w), 1e-30) for g, w in zip(got["losses"], want["losses"])]
    worst = max(gaps) if gaps and all(np.isfinite(gaps)) else float("inf")
    rows.append({"name": "loss", "value": worst, "limit": limits["loss"],
                 "where": f"step {int(np.argmax(gaps)) + 1}" if gaps else ""})
    names = ("grad_norm", "change_norm") + (("stats_norm",) if "stats_norm" in want else ())
    for name in names:
        value, where = worst_leaf_gap(got.get(name, {}), want[name])
        rows.append({"name": name, "value": value, "limit": limits[name], "where": where})
    for r in rows:
        r["ok"] = bool(r["value"] <= r["limit"])
    return all(r["ok"] for r in rows), rows
