"""One cell traced, then the host threads' account of it, a line a batch:

    python3 run-scripts/host_batches.py CELL [SEED [SECONDS [ROWS]]]
    python3 run-scripts/host_batches.py            # the last traced run's profile as it lies

The first form runs ``benchmark/run.py --workload CELL --trace 1`` as a child
(its lines pass through; a chip belongs to one process, so this one touches
JAX only after the child has ended), then reads the profile it left under
``.bench_out/trace``. A line a batch, in the order the loader collated them:
``collate``'s length and its phases (``fetch_us`` / ``fill_us`` / the
``triplets`` child spans / ``certify_us`` / what they leave), what ``transfer``
moved (leaves, bytes, ms), the producer's wait for a queue slot (``handoff``),
how long the finished batch then waited for its ``dispatch`` (lead), and how
many finished batches the loop's ``dataload`` found (``ready``). The first
ROWS (default 40) batches, then the five longest of each of ``collate``,
``handoff`` and lead, then every ``gc`` span. The metrics over the same spans
are ``benchmark/metrics/collate_certify_share.py`` and its six neighbours;
``benchmark/tools/dump_spans.py`` prints the spans' counts by thread.
"""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]


def nth(events):
    """``{(batch, n): event}`` for the n-th span of each batch index, by start:
    the index recurs every epoch, its n-th collate feeds its n-th dispatch."""
    out, seen = {}, {}
    for e in sorted(events, key=lambda e: e[0]):
        batch = e[3].get("batch")
        if batch is not None:
            out[batch, seen.setdefault(batch, 0)] = e
            seen[batch] += 1
    return out


def rows(host):
    from lib import host_spans, spans

    phases = {row["at"]: row for row in host_spans.collate_phases(host)}
    by = {name: nth(spans.named(host, name))
          for name in ("transfer", "handoff", "dispatch", "dataload")}
    out = []
    for key, c in nth(spans.named(host, "collate")).items():
        t, h, d, w = (by[name].get(key) for name in ("transfer", "handoff", "dispatch", "dataload"))
        done = (t or c)[1]
        out.append({
            "at": c[0], "batch": key[0], "collate_ms": 1e-6 * (c[1] - c[0]),
            "phases": phases.get(c[0]), "leaves": t[3].get("leaves") if t else None,
            "bytes": t[3].get("bytes") if t else None,
            "transfer_ms": 1e-6 * (t[1] - t[0]) if t else None,
            "handoff_ms": 1e-6 * (h[1] - h[0]) if h else None,
            "lead_ms": 1e-6 * (d[0] - done) if d else None,
            "ready": w[3].get("ready") if w else None})
    return sorted(out, key=lambda r: r["at"])


def line(r, t0):
    def f(v, spec):
        return format(v, spec) if v is not None else "-".rjust(int(spec.split(".")[0]))

    p = r["phases"]
    split = " ".join(f(p[k] if p else None, spec) for k, spec in (
        ("fetch", "8.0f"), ("fill", "7.0f"), ("triplets", "7.0f"), ("certify", "7.0f"),
        ("rest", "7.0f")))
    return (f"{1e-9 * (r['at'] - t0):9.4f} {r['batch']:5d} {r['collate_ms']:10.3f} {split} "
            f"{f(r['leaves'], '6d')} {f(r['bytes'], '10d')} {f(r['transfer_ms'], '8.3f')} "
            f"{f(r['handoff_ms'], '10.3f')} {f(r['lead_ms'], '9.3f')} {f(r['ready'], '5d')}")


def main(argv):
    if argv:
        cell, seed, seconds = argv[0], (argv[1:2] or ["7"])[0], (argv[2:3] or ["30"])[0]
        code = subprocess.call(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", cell,
             "--seed", seed, "--seconds", seconds, "--trace", "1"], cwd=ROOT)
        if code:
            return code
    keep = int(argv[3]) if len(argv) > 3 else 40
    from lib import host_spans, spans, trace

    host = spans.host_spans(trace.find_xplane(spans.TRACE_DIR))
    table = rows(host)
    if not table:
        print("the profile holds no collate span with a batch index")
        return 1
    t0 = table[0]["at"]
    head = " ".join(name.rjust(width) for name, width in (
        ("at_s", 9), ("batch", 5), ("collate_ms", 10), ("fetch_us", 8), ("fill_us", 7),
        ("trip_us", 7), ("cert_us", 7), ("rest_us", 7), ("leaves", 6), ("bytes", 10),
        ("xfer_ms", 8), ("handoff_ms", 10), ("lead_ms", 9), ("ready", 5)))
    print(head)
    for r in table[:keep]:
        print(line(r, t0))
    for key in ("collate_ms", "handoff_ms", "lead_ms"):
        print(f"-- the five longest by {key}")
        for r in sorted((r for r in table if r[key] is not None), key=lambda r: -r[key])[:5]:
            print(line(r, t0))
    pauses = host_spans.gc_pauses(host)
    print(f"-- {len(pauses)} gc span(s), longest first")
    for start, end, generation, collected, thread, inside in pauses[:keep]:
        print(f"{1e-9 * (start - t0):9.4f} generation {generation} {1e-6 * (end - start):9.3f} ms "
              f"{collected} collected, inside {inside} on {thread}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
