"""From the profiler's trace to numbers — the yardstick, kept with the benchmark.

``extract`` reads an ``.xplane.pb`` with nothing but JAX into a plain dict:

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...], ...},
     "modules": {"/device:TPU:0": [[name, start_ns, dur_ns], ...], ...},
     "host": [[name, start_ns, dur_ns], ...]}

device events are those of each device plane's ``XLA Ops`` line (one entry
per executed HLO operation), modules those of its ``XLA Modules`` line (one
entry per executed program: ``jit_train_step(<fingerprint>)``); host events
are the benchmark's own ``TraceAnnotation`` spans (``bench_*``). ``reduce``
turns that dict into the numbers the per-layer readers use; a reader that
needs another number takes it from the extracted dict, which a run hands it
too (``ctx["events"]``).
``benchmark/tests`` holds a small recorded dict and the values ``reduce``
must give on it.
"""

from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench_"
# how the host spans are named in the breakdown
HOST_KINDS = {"bench_dataload": "dataload", "bench_dispatch": "dispatch", "bench_sync": "sync"}
# a device event's name is its HLO instruction: "%fusion.3 = f32[..] fusion(..)"
MOSAIC = 'custom_call_target="tpu_custom_call"'
NAME_CHARS = 160  # of an instruction, in the breakdown


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def extract(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    out = {"devices": {}, "modules": {}, "host": [], "lines": {}}
    for plane in data.planes:
        is_device = plane.name.startswith("/device:") and "TPU" in plane.name
        for line in plane.lines:
            if is_device:
                out["lines"].setdefault(plane.name, []).append(line.name)
                if line.name in (OPS_LINE, MODULES_LINE):
                    out["devices" if line.name == OPS_LINE else "modules"][plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events]
            else:
                out["host"].extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events if e.name.startswith(HOST_PREFIX))
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _spans(events):
    return [(s, s + d) for _, s, d in events if d > 0]


def reduce(trace: dict, is_mosaic=None) -> dict:
    """Numbers from an extracted trace. Times in seconds.

    ``is_mosaic(name) -> bool`` says which device operations are Mosaic
    kernels; the default takes an instruction whose text holds
    ``custom_call_target="tpu_custom_call"`` (a fusion that merely reads a
    custom call's result does not).
    """
    is_mosaic = is_mosaic or (lambda name: MOSAIC in name)
    devices = sorted(trace["devices"])
    if not devices:
        return {"devices": 0}
    busy, mosaic, by_op = [], [], {}
    for dev in devices:
        events = trace["devices"][dev]
        busy.append(length(union(_spans(events))))
        mosaic.append(sum(d for n, _, d in events if is_mosaic(n)))
        for n, _, d in events:
            by_op[n] = by_op.get(n, 0.0) + d
    first = trace["devices"][devices[0]]
    merged = union(_spans(first))
    # idle gaps on the first device, and what the host was doing in each
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])), reverse=True)[:5]
    host = [(HOST_KINDS.get(n, n), s, s + d) for n, s, d in trace["host"]]
    idle_gaps = []
    for dur, g0, g1 in gaps:
        share = {}
        for kind, s, e in host:
            ov = min(e, g1) - max(s, g0)
            if ov > 0:
                share[kind] = share.get(kind, 0.0) + ov
        kind = max(share, key=share.get) if share and max(share.values()) > 0.5 * dur else "other"
        idle_gaps.append([kind, dur * 1e-9])
    n = float(len(devices))
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": len(devices),
        "busy_s": sum(busy) / n * 1e-9,
        "mosaic_s": sum(mosaic) / n * 1e-9,
        "device_ops": [[name[:NAME_CHARS], d / n * 1e-9] for name, d in top],
        "idle_gaps": idle_gaps,
    }


def step_ends(trace: dict, functions: tuple) -> list[float]:
    """When each execution of the step program ended on the first device, ns
    on the device's clock, in order: the ends of the ``XLA Modules`` events
    whose program is named for one of ``functions``."""
    modules = trace.get("modules") or {}
    if not modules:
        return []
    events = modules[sorted(modules)[0]]
    return sorted(s + d for n, s, d in events if any(f in n for f in functions))


def stamp_disagreement(finishes: list, ends_ns: list) -> dict | None:
    """The host's finish stamps (seconds, the host's clock) against the
    device's own step ends (ns, the device's clock), step by step. The two
    clocks share no zero, so: ``interval`` = |host interval - device interval|
    between consecutive steps, and ``late`` = how much later than the
    promptest stamp of the run each stamp came after its step's end. Seconds,
    one entry a step. None where the two do not count the same steps."""
    if len(finishes) != len(ends_ns) or len(finishes) < 2:
        return None
    ends = [e * 1e-9 for e in ends_ns]
    lag = [f - e for f, e in zip(finishes, ends)]
    soonest = min(lag)
    return {
        "interval": [abs((f1 - f0) - (e1 - e0)) for f0, f1, e0, e1 in
                     zip(finishes, finishes[1:], ends, ends[1:])],
        "device_interval": [e1 - e0 for e0, e1 in zip(ends, ends[1:])],
        "late": [x - soonest for x in lag],
    }
