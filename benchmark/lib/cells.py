"""Cells, found by name: BENCHMARK.json -> configuration, traffic mix,
generator, reference, shape functions and per-layer readers, each a file of
its own under ``benchmark/``. Adding a cell, a configuration, a mix or a
metric adds files and one entry in BENCHMARK.json; nothing here is edited.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    def __init__(self, workload: str, rehearse: bool = False):
        bench = _json(ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no cell {workload!r} in BENCHMARK.json; known: {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config = _json(ROOT, cfg_entry["file"])
        self.traffic = _json(BENCH_DIR, "traffic", f"{self.entry['traffic']}.json")
        if rehearse:  # tiny sizes for the CPU; never a measurement
            self.traffic = _merge(self.traffic, self.traffic.get("rehearse", {}))
            self.config = _merge(self.config, self.config.get("rehearse", {}))
        self.generator = load_module("generators", self.traffic["generator"])
        self.reference = load_module("reference", self.config["arch"])
        self.follow = load_module("reference", self.config["objective"]).follow
        self.ops = load_module("ops", self.config["arch"])
        self.end_to_end = [m for m in bench["end_to_end"] if _applies(m, workload)]
        self.per_layer = [m for m in bench["per_layer"] if _applies(m, workload)]


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def peaks(device_kind: str) -> dict:
    table = _json(BENCH_DIR, "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"device_kind {device_kind!r} is not in benchmark/peaks.json")
    return table[device_kind]
