"""After a traced run (``benchmark/run.py --trace 1``, or
``run-scripts/host_batches.py CELL``): what the profile holds BELOW the
program's spans. A traced run has the profiler's Python tracer on, so the
profile carries an event a Python call on every Python thread, beside the
runtime's own events on its threads.

    python3 run-scripts/profile_gaps.py OUT.txt

Writes (and prints the head of): (1) every collection of the cyclic collector
on ANY Python thread, generation 0 too, as pairs of calls of
``utils/tracer.py::_on_gc`` (a thread with no span open writes no ``gc`` span;
its hook calls are still here); (2) every program span over 60 ms and 8 x its
median (not ``train`` / ``drain`` / ``dataload``) with every event over 20 ms
of any line that overlaps it: what a stall that stretches the loop's and the
producer's spans at once was, by the runtime's name for it (PERF.md section 6:
``DeferredTpuAllocator::Allocate`` under the step call, the GIL held); (3) the
loop thread's gap between ``dataload``'s end and ``stage``'s start, where the
``for`` statement drops the previous batch: the runtime's events wholly inside
the gaps, the loop's own Python calls there, what the other Python threads ran
meanwhile, and one median gap event by event.
"""
import collections
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
from jax.profiler import ProfileData  # noqa: E402

from lib import spans, trace  # noqa: E402

out = open(sys.argv[1], "w")


def say(*a):
    print(*a, file=out)


data = ProfileData.from_file(trace.find_xplane(spans.TRACE_DIR))
lines = {}
for plane in data.planes:
    if plane.name.startswith("/device:"):
        continue
    for line in plane.lines:
        evs = sorted(((float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
                      for e in line.events), key=lambda e: (e[0], -e[1]))
        if evs:
            lines[f"{line.name}#{len(lines)}"] = evs
py = {t: ev for t, ev in lines.items() if any(e[2].startswith("$") for e in ev[:2000])}
loop = next(t for t, ev in py.items() if any(e[2] == "hydragnn/dispatch" for e in ev))
trains = [e for e in lines[loop] if e[2] == "hydragnn/train"]
w0, w1 = min(e[0] for e in trains), max(e[1] for e in trains)
say(f"window {1e-9 * (w1 - w0):.3f} s; python threads:",
    {t: len(ev) for t, ev in py.items()}, "loop", loop)

# (1) collections on any thread
say("\n== collections (pairs of _on_gc calls), by thread")
for t, ev in py.items():
    calls = [e for e in ev if "_on_gc" in e[2] and w0 <= e[0] <= w1]
    durs = [(b[0] - a[1], a[0]) for a, b in zip(calls[0::2], calls[1::2])]
    if durs:
        big = sorted(durs, reverse=True)[:6]
        say(f"{t}: {len(durs)} collections, total {1e-6 * sum(d for d, _ in durs):.2f} ms, median "
            f"{1e-3 * statistics.median(d for d, _ in durs):.0f} us; longest: "
            + ", ".join(f"{1e-6 * d:.2f} ms at {1e-9 * (at - w0):.3f} s" for d, at in big))
    else:
        say(f"{t}: no _on_gc call in the window")

# (2) stretched spans
say("\n== program spans over 60 ms (not train/drain/dataload), and what overlapped them")
named = [(a, b, n[len("hydragnn/"):], t) for t, ev in py.items() for a, b, n in ev
         if n.startswith("hydragnn/") and w0 <= a <= w1]
meds = collections.defaultdict(list)
for a, b, n, t in named:
    meds[n].append(b - a)
long = [s for s in named if s[1] - s[0] > 60e6 and s[2] not in ("train", "drain", "dataload")
        and s[1] - s[0] > 8 * statistics.median(meds[s[2]])]
for a, b, n, t in sorted(long):
    say(f"-- {n} {1e-6 * (b - a):.1f} ms (median {1e-6 * statistics.median(meds[n]):.2f}) at "
        f"{1e-9 * (a - w0):.3f} s on {t}")
    for t2, ev in lines.items():
        inside = [e for e in ev if e[1] > a and e[0] < b and e[1] - e[0] > 20e6
                  and not e[2].startswith("hydragnn/train")]
        for x, y, name in inside[:12]:
            say(f"     {t2}: {name[:100]} {1e-6 * (y - x):.1f} ms from {1e-6 * (x - a):+.1f} ms")

# (3) the loop's gap between dataload and stage
say("\n== the loop thread between dataload's end and stage's start")
lp = lines[loop]
gaps = []
program = [e for e in lp if e[2].startswith("hydragnn/") and e[2] != "hydragnn/train"]
for i, e in enumerate(program[:-1]):
    if e[2] == "hydragnn/dataload" and program[i + 1][2] == "hydragnn/stage":
        gaps.append((program[i + 1][0] - e[1], e[1], program[i + 1][0]))
gaps.sort()
say(f"{len(gaps)} gaps, median {1e-3 * gaps[len(gaps) // 2][0]:.0f} us, sum "
    f"{100.0 * sum(g[0] for g in gaps) / (w1 - w0):.2f}% of the window")
inside_loop = collections.Counter()
holders = collections.Counter()
runtime = collections.Counter()
runtime_n = collections.Counter()
for g, a, b in gaps:
    for x, y, name in lp:
        if a <= x and y <= b and name.startswith("$"):
            inside_loop[name] += y - x
        elif a <= x and y <= b and not name.startswith(("hydragnn/", "bench_")):
            runtime[name] += y - x
            runtime_n[name] += 1
    for t2, ev in py.items():
        if t2 == loop:
            continue
        for x, y, name in ev:
            if y > a and x < b and name.startswith("$") and (y - x) < 50e6:
                holders[t2 + " " + name] += min(y, b) - max(x, a)
    for t2, ev in lines.items():
        if t2 in py:
            continue
        for x, y, name in ev:
            if a <= x and y <= b:
                runtime[t2.split("#")[0] + ": " + name] += y - x
                runtime_n[t2.split("#")[0] + ": " + name] += 1
say("events of every line that is not a python thread's, wholly inside the gaps (the runtime's), count and ms in all:")
for name, ns in runtime.most_common(30):
    say(f"     {runtime_n[name]:7d} {1e-6 * ns:9.2f}  {name[:110]}")
say("loop thread's own python events inside the gaps, ms in all (outermost and inner both listed):")
for name, ns in inside_loop.most_common(12):
    say(f"     {1e-6 * ns:9.2f}  {name[:110]}")
say("other python threads' events overlapping the gaps, ms in all:")
for name, ns in holders.most_common(25):
    say(f"     {1e-6 * ns:9.2f}  {name[:120]}")
mid = gaps[len(gaps) // 2]
say(f"one median gap ({1e-3 * mid[0]:.0f} us), every event of the loop thread that starts inside:")
for x, y, name in lp:
    if mid[1] - 30e3 <= x <= mid[2] + 5e3:
        say(f"     {1e-3 * (x - mid[1]):9.1f} us  {1e-3 * (y - x):9.1f} us  {name[:100]}")
out.close()
print(open(sys.argv[1]).read()[:6000])
