"""Compiled-program cost observatory: what the AOT executables we serve
actually COST.

Wall-clock alone is weak evidence for compiled-program claims. XLA's own
compiled-artifact introspection is not: ``Compiled.cost_analysis()``
(flops, bytes accessed) and
``memory_analysis()`` (argument/output/temp/generated-code bytes) are
exact properties of the artifact, CPU-provable, and free to read — the
executable already exists by the time we ask. This module captures them
at every ``utils/compile_cache.aot_compile`` site (serve warm-up, quant
executables, screen engine, the flag-gated train-step probe), keyed per
``(model, bucket, backend, precision, kind)``, plus the compile
sentinel's lowering counts, and persists the lot as a schema'd
``logs/<run>/ledger.json``.

The REGRESSION SENTINEL (``python -m hydragnn_tpu.telemetry ledger
<current> --baseline <base>``) diffs two ledgers and fails loudly when
any shared entry's flops / bytes-accessed / peak-bytes inflated beyond a
relative tolerance — the cost analog of the recompile sentinel, wired as
a bench evidence source.

Capture is on whenever the telemetry plane is (``HYDRAGNN_LEDGER=0``
opts out); a path-valued ``HYDRAGNN_LEDGER`` additionally makes warm-up
sites save the cumulative ledger there.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time

from ..utils import flags
from . import metrics

SCHEMA_VERSION = 1

# cost_analysis() metric names -> ledger field names
_COST_FIELDS = (
    ("flops", "flops"),
    ("bytes accessed", "bytes_accessed"),
    ("transcendentals", "transcendentals"),
)
# CompiledMemoryStats attributes -> ledger field names
_MEMORY_FIELDS = (
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
)
# metrics the diff sentinel compares (absent-on-this-backend keys skip)
DIFF_METRICS = ("flops", "bytes_accessed", "peak_bytes")

_FALSEY = ("0", "false", "no", "off")
_TRUTHY = ("1", "true", "yes", "on")


def capture_enabled() -> bool:
    """Ledger capture rides the telemetry plane; ``HYDRAGNN_LEDGER=0``
    opts out without touching the rest of the plane."""
    if not metrics.enabled():
        return False
    raw = flags.get(flags.LEDGER)
    return raw is None or str(raw) not in _FALSEY


def save_path() -> str | None:
    """An explicit save target from ``HYDRAGNN_LEDGER``: a path value is
    the target; a bare truthy value means the default ``./logs/
    ledger.json``; unset/falsey means the caller decides (runs with a
    journal still persist next to it)."""
    raw = flags.get(flags.LEDGER)
    if raw is None or str(raw) in _FALSEY:
        return None
    raw = str(raw)
    if raw in _TRUTHY:
        return os.path.join(".", "logs", "ledger.json")
    return raw


def _backend_name() -> str:
    try:
        import jax

        return jax.default_backend()
    except Exception:
        return "unknown"


def _lowering_counts() -> dict:
    try:
        from ..analysis.sentinel import compile_counts

        return dict(compile_counts())
    except Exception:
        return {}


def cost_dict(compiled) -> dict:
    """Guarded ``cost_analysis()`` read: tolerate missing keys (per-backend
    — CPU omits some) and backends that refuse the call entirely."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return {}
    if not isinstance(cost, dict):
        return {}
    out = {}
    for src, dst in _COST_FIELDS:
        value = cost.get(src)
        if isinstance(value, (int, float)):
            out[dst] = float(value)
    return out


def memory_dict(compiled) -> dict:
    """Guarded ``memory_analysis()`` read; ``peak_bytes`` is derived as
    the sum of the populated resident parts (arguments + outputs + temps
    + generated code) so the field exists even on backends that report
    no single peak figure (CPU included)."""
    try:
        stats = compiled.memory_analysis()
    except Exception:
        return {}
    if stats is None:
        return {}
    out = {}
    for attr, dst in _MEMORY_FIELDS:
        value = getattr(stats, attr, None)
        if isinstance(value, (int, float)):
            out[dst] = int(value)
    parts = [out.get(k) for k in (
        "argument_bytes", "output_bytes", "temp_bytes",
        "generated_code_bytes")]
    present = [p for p in parts if p is not None]
    if present:
        out["peak_bytes"] = int(sum(present))
    return out


def entry_key(entry: dict) -> str:
    """The identity a diff matches entries on."""
    return "|".join(str(entry.get(k, "?")) for k in (
        "model", "bucket", "backend", "precision", "kind"))


class CostLedger:
    """In-memory accumulator of per-executable cost entries
    (thread-safe; warm-ups record from dispatcher threads)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: dict[str, dict] = {}  # guarded-by: _lock

    def record(self, compiled, *, model: str = "?", bucket=None,
               kind: str = "aot", precision: str | None = None,
               compile_s: float | None = None, extra: dict | None = None,
               ) -> dict | None:
        """Capture one compiled executable's cost entry (no-op and None
        when capture is off). Re-recording the same key overwrites — a
        re-warm measures the same artifact."""
        if not capture_enabled():
            return None
        entry = {
            "model": str(model),
            "bucket": list(bucket) if isinstance(bucket, (tuple, list))
            else (bucket if bucket is None else str(bucket)),
            "backend": _backend_name(),
            "precision": str(precision) if precision is not None else "default",
            "kind": str(kind),
            "t_wall": time.time(),
        }
        entry.update(cost_dict(compiled))
        entry.update(memory_dict(compiled))
        if compile_s is not None:
            entry["compile_s"] = round(float(compile_s), 4)
        lowerings = _lowering_counts().get("lowerings")
        if lowerings is not None:
            entry["lowerings_at_capture"] = int(lowerings)
        if extra:
            entry.update(extra)
        key = entry_key(entry)
        with self._lock:
            self._entries[key] = entry
        return dict(entry)

    def entries(self) -> list[dict]:
        with self._lock:
            return [dict(self._entries[k]) for k in sorted(self._entries)]

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()

    def document(self) -> dict:
        """The schema'd ledger document (what ``save`` writes)."""
        return {
            "schema": SCHEMA_VERSION,
            "created_unix": time.time(),
            "backend": _backend_name(),
            "lowerings": _lowering_counts(),
            "entries": self.entries(),
        }

    def save(self, path: str) -> str | None:
        """Atomically persist the ledger document; empty ledgers write
        nothing (no entries, no file — absence is unambiguous)."""
        doc = self.document()
        if not doc["entries"]:
            return None
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, path)
        return path


def load(path: str) -> dict:
    """Read a ledger document back; raises on unreadable/unschema'd input
    (the diff sentinel wants loud failure, not a silent pass)."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "entries" not in doc:
        raise ValueError(f"not a ledger document: {path}")
    return doc


def diff(baseline: dict, current: dict, tolerance: float = 0.02) -> dict:
    """Compare two ledger documents entry-by-entry. An entry REGRESSES
    when any :data:`DIFF_METRICS` value grew beyond ``tolerance``
    (relative); shrinkage is reported as an improvement, never a failure.
    Entries present on one side only are listed but do not fail — a new
    bucket is news, not a regression."""
    base_by = {entry_key(e): e for e in baseline.get("entries", [])}
    cur_by = {entry_key(e): e for e in current.get("entries", [])}
    regressions, improvements, compared = [], [], 0
    for key in sorted(set(base_by) & set(cur_by)):
        b, c = base_by[key], cur_by[key]
        compared += 1
        for metric in DIFF_METRICS:
            bv, cv = b.get(metric), c.get(metric)
            if not isinstance(bv, (int, float)) or not isinstance(cv, (int, float)):
                continue
            if bv <= 0:
                continue
            ratio = cv / bv
            delta = {"key": key, "metric": metric, "baseline": bv,
                     "current": cv, "ratio": round(ratio, 6)}
            if ratio > 1.0 + tolerance:
                regressions.append(delta)
            elif ratio < 1.0 - tolerance:
                improvements.append(delta)
    return {
        "tolerance": tolerance,
        "compared": compared,
        "only_in_baseline": sorted(set(base_by) - set(cur_by)),
        "only_in_current": sorted(set(cur_by) - set(base_by)),
        "regressions": regressions,
        "improvements": improvements,
        "ok": not regressions,
    }


# -- the process ledger -------------------------------------------------------

LEDGER = CostLedger()


def record(compiled, **kwargs) -> dict | None:
    return LEDGER.record(compiled, **kwargs)


def entries() -> list[dict]:
    return LEDGER.entries()


def reset_ledger() -> None:
    LEDGER.reset()


def save(path: str) -> str | None:
    return LEDGER.save(path)


def maybe_save(default_path: str | None = None) -> str | None:
    """Persist the process ledger to the flag-armed path, else to the
    caller's default (a run's log dir); a no-op when neither names a
    target or the ledger is empty."""
    path = save_path() or default_path
    if path is None:
        return None
    return LEDGER.save(path)


@contextlib.contextmanager
def isolated_ledger():
    """Swap the process ``LEDGER`` for a fresh instance for the duration
    of the scope (same single-rebind pattern as
    ``metrics.isolated_registry``)."""
    global LEDGER
    fresh = CostLedger()
    prev, LEDGER = LEDGER, fresh
    try:
        yield fresh
    finally:
        LEDGER = prev


__all__ = [
    "DIFF_METRICS",
    "CostLedger",
    "LEDGER",
    "SCHEMA_VERSION",
    "capture_enabled",
    "cost_dict",
    "diff",
    "entries",
    "entry_key",
    "isolated_ledger",
    "load",
    "maybe_save",
    "memory_dict",
    "record",
    "reset_ledger",
    "save",
    "save_path",
]
