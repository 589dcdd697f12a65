"""One HPO trial in its own OS process: ``python trial_worker.py config.json
out.json``. The subprocess side of the ProcessPoolEvaluator pattern
(reference ``examples/multidataset_hpo/gfm_deephyper_multi.py:127-170``) —
each trial gets a fresh interpreter and JAX runtime, so concurrent trials
never share compilation caches, device state, or global config.

Data: regenerates the same synthetic QM9-style molecules as the driver
(``QM9_HPO_SAMPLES`` sets the count) — a real corpus would load from the
config's Dataset section instead.
"""

from __future__ import annotations

import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(_HERE)))
sys.path.insert(0, os.path.join(_HERE, "..", "qm9"))


def main() -> None:
    cfg_path, out_path = sys.argv[1], sys.argv[2]
    with open(cfg_path) as f:
        cfg = json.load(f)

    from qm9 import synthetic_molecules

    import hydragnn_tpu
    from hydragnn_tpu.run_prediction import run_prediction

    samples = synthetic_molecules(int(os.environ.get("QM9_HPO_SAMPLES", "120")))
    state, model, full_cfg = hydragnn_tpu.run_training(cfg, samples)
    error, _, _, _ = run_prediction(full_cfg, state, model, samples=samples)
    with open(out_path, "w") as f:
        json.dump({"objective": float(error)}, f)


if __name__ == "__main__":
    main()
