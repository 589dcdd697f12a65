"""Write a trimmed copy of the last traced run's events as JSON.

    python benchmark/tools/dump_trace.py <out.json> [events per device]

For looking at a trace by hand and for recording the small trace that
``benchmark/tests`` checks the reduction on.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE)]


def main(argv):
    from lib import trace

    out, keep = argv[0], int(argv[1]) if len(argv) > 1 else 400
    root = os.path.dirname(os.path.dirname(HERE))
    data = trace.extract(trace.find_xplane(os.path.join(root, ".bench_out", "trace")))
    t0 = min(e[1] for ev in data["devices"].values() for e in ev[:1])
    t1 = max(e[1] for ev in data["devices"].values() for e in ev[:keep])
    trimmed = {
        "lines": data["lines"],
        "devices": {d: ev[:keep] for d, ev in data["devices"].items()},
        "host": [e for e in data["host"] if t0 - 5e7 <= e[1] <= t1],
    }
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(trimmed, f)
    names = {}
    for ev in data["devices"].values():
        for n, _, d in ev:
            names[n] = names.get(n, 0.0) + d
    for n, d in sorted(names.items(), key=lambda kv: -kv[1])[:40]:
        print(f"{d * 1e-6:10.2f} ms  {n}")


if __name__ == "__main__":
    main(sys.argv[1:])
