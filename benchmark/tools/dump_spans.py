"""Write a small recorded dict of the last traced run's spans and scopes.

    python benchmark/tools/dump_spans.py <out.json> [device events each side]

Keeps the first chip's operations on both sides of its longest idle gap (an
epoch boundary in a training cell), the program's host spans that overlap
them, and the scope of each kept operation. Instruction texts are cut to
their name (plus the Mosaic marker), so that the file stays small; what
``benchmark/tests/test_spans.py`` checks the readers on, against values
worked out by hand from the file (a new recording needs them worked out
anew). Also prints what the trace holds: planes' threads with their span
counts, and the scopes found.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE)]


def short(text: str) -> str:
    from lib import spans

    name = text.split(" = ", 1)[0]
    return name + (" " + spans.MOSAIC if spans.MOSAIC in text else "")


def main(argv):
    from lib import spans, trace

    out, keep = argv[0], int(argv[1]) if len(argv) > 1 else 1500
    path = trace.find_xplane(spans.TRACE_DIR)
    host, scopes = spans.host_spans(path), spans.event_scopes(path)
    devices = trace.extract(path)["devices"]
    for thread, events in host.items():
        counts = {}
        for e in events:
            counts[e[2]] = counts.get(e[2], 0) + 1
        print(f"thread {thread}: {counts}")
    print(f"{len(scopes)} instructions with a scope in the device planes' event metadata")
    trimmed = {"devices": {}, "host": {}, "scopes": {}}
    if devices:
        dev = sorted(devices)[0]
        events = sorted(devices[dev], key=lambda e: e[1])
        gaps = [(b[1] - (a[1] + a[2]), i) for i, (a, b) in enumerate(zip(events, events[1:]))]
        at = max(gaps)[1] + 1
        kept = events[max(0, at - keep):at + keep]
        t0, t1 = kept[0][1] - 5e6, kept[-1][1] + kept[-1][2] + 5e6
        trimmed["devices"][dev] = [[short(n), s, d] for n, s, d in kept]
        trimmed["scopes"] = {short(n): scopes[n] for n, _, _ in kept if n in scopes}
        for thread, ev in host.items():
            inside = [e for e in ev if e[1] >= t0 and e[0] <= t1]
            # and the dispatch spans next to the range, for the epoch's turnaround
            before = [e for e in ev if e[2] == "dispatch" and e[1] < t0][-1:]
            after = [e for e in ev if e[2] == "dispatch" and e[0] > t1][:1]
            if inside:
                trimmed["host"][thread] = [list(e) for e in before + inside + after]
        trimmed["steps"] = 1
        trimmed["step_compiles"] = {
            "traces": [11, 40.0], "lowerings": [11, 9.5], "backend_compiles": [11, 30.5]}
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(trimmed, f)
    print(f"wrote {out}: {sum(len(v) for v in trimmed['devices'].values())} device events, "
          f"{sum(len(v) for v in trimmed['host'].values())} host spans, "
          f"{len(trimmed['scopes'])} scopes")


if __name__ == "__main__":
    main(sys.argv[1:])
