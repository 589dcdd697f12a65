"""The program's own spans and scopes, read back from the profiler's trace.

The program (``hydragnn_tpu/utils/tracer.py``) writes every span it opens
into the profiler's trace as a host event ``hydragnn/<span>`` with its
arguments, and flax / ``jax.named_scope`` put a scope path into every device
operation's ``op_name``. ``load`` reads both, once a traced run:

    {"host": {thread: [(start_ns, end_ns, span, args), ...]},   # sorted by start
     "scopes": {instruction text: op_name}}

Where the scope of a device operation is found. A TPU plane's ``XLA Ops``
events carry no scope of their own (their statistics are the device offset
and duration only); the plane's EVENT METADATA does, as the statistic
``tf_op`` = ``<op_name>:``, which ``jax.profiler.ProfileData`` does not hand
out. ``event_scopes`` therefore reads just that table from the file with a
few lines of protobuf wire format (the planes' lines, where the bulk is, are
skipped by length); an instruction whose text holds ``op_name="..."`` is
taken from there. A fusion carries the one name of its root instruction.

Everything below ``load`` is a pure function of plain lists, checked in
``benchmark/tests/test_spans.py`` on a recorded dict.
"""

from __future__ import annotations

import os
import re
import statistics

from lib import trace as trace_lib

PREFIX = "hydragnn/"
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".bench_out", "trace")  # as run.py::TRACE_DIR
LOOP_SPANS = ("dataload", "stage", "dispatch", "backpressure", "drain", "reduce")
WEAK = ("train",)  # a span that yields to any other span, of any thread
MOSAIC = trace_lib.MOSAIC
HELD = 5.0  # a step call over this many lower quartiles long waited for the device


# -- reading ---------------------------------------------------------------------

def _kept(ctx, key: str, make):
    """``make()``, computed once a run and kept on ``ctx``."""
    if key not in ctx:
        ctx[key] = make()
    return ctx[key]


def load(ctx) -> dict | None:
    """The run's spans and scopes; None where the run left no trace."""
    def read():
        try:
            path = trace_lib.find_xplane(TRACE_DIR)
        except FileNotFoundError:
            return None
        return {"host": host_spans(path), "scopes": event_scopes(path)}

    return _kept(ctx, "_spans", read)


def host_spans(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    threads = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            found = [
                (float(e.start_ns), float(e.start_ns + e.duration_ns),
                 e.name[len(PREFIX):], {k: _number(v) for k, v in e.stats})
                for e in line.events if e.name.startswith(PREFIX)]
            if found:  # a line is a thread; two threads may share a name
                threads[f"{line.name}#{len(threads)}"] = sorted(
                    found, key=lambda e: (e[0], -e[1]))
    return threads


def _number(value):
    try:
        return int(value)
    except (TypeError, ValueError):
        return value


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:
            i += 8 if kind == 1 else 4
            continue
        yield key >> 3, value


def event_scopes(xplane_path: str) -> dict:
    """{event name (the instruction text): op_name} from the ``tf_op``
    statistic of the device planes' event metadata (XSpace.planes=1;
    XPlane.name=2, .event_metadata=4, .stat_metadata=5; map entries key=1,
    value=2; XEventMetadata.name=2, .stats=5; XStatMetadata.name=2;
    XStat.metadata_id=1, .str_value=5, .ref_value=7)."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    scopes = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, stat_names, events = "", {}, []
        for pf, value in _fields(plane):
            if pf == 2:
                name = bytes(value).decode()
            elif pf == 5:
                entry = dict(_fields(value))
                stat_names[entry[1]] = bytes(dict(_fields(entry[2])).get(2, b"")).decode()
            elif pf == 4:
                events.append(dict(_fields(value))[2])
        if not name.startswith("/device:"):
            continue
        for meta in events:
            text, op = None, None
            for mf, value in _fields(meta):
                if mf == 2:
                    text = bytes(value).decode()
                elif mf == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        op = (bytes(stat[5]).decode() if 5 in stat
                              else stat_names.get(stat.get(7), ""))
            if text and op:
                scopes[text] = op.rstrip(":")
    return scopes


# -- host spans ------------------------------------------------------------------

def loop_thread(host: dict) -> str | None:
    """The thread that runs ``train_epoch``: the one with ``dispatch`` spans."""
    for thread, events in host.items():
        if any(e[2] == "dispatch" for e in events):
            return thread
    return None


def named(host: dict, span: str, thread: str | None = None) -> list:
    return [e for t, events in host.items() if thread in (None, t)
            for e in events if e[2] == span]


def self_times(intervals: list) -> list:
    """For ``[(start, end), ...]`` of one thread or one device line (nested,
    never crossing): each interval's length minus what the intervals inside
    it cover, in the order given."""
    order = sorted(range(len(intervals)), key=lambda i: (intervals[i][0], -intervals[i][1]))
    out = [0.0] * len(intervals)
    stack = []
    for i in order:
        start, end = intervals[i][0], intervals[i][1]
        while stack and intervals[stack[-1]][1] <= start:
            stack.pop()
        out[i] = end - start
        if stack:
            out[stack[-1]] -= min(end, intervals[stack[-1]][1]) - start
        stack.append(i)
    return out


def innermost(events: list) -> list:
    """One thread's nested spans flattened to disjoint ``(start, end, span)``
    pieces, each named by the innermost span open there."""
    pieces, stack = [], []  # stack of (end, span)

    def emit(a, b):
        if stack and b > a:
            pieces.append((a, b, stack[-1][1]))

    cursor = None
    for start, end, span, _ in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            emit(cursor, stack[-1][0])
            cursor = stack.pop()[0]
        emit(cursor, start)
        stack.append((end, span))
        cursor = start
    while stack:
        emit(cursor, stack[-1][0])
        cursor = stack.pop()[0]
    return pieces


def _overlaps(intervals: list, pieces: list):
    """Both sorted and disjoint: yields ``(a, b, piece)`` for every overlap."""
    j = 0
    for a, b in intervals:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            lo, hi = max(a, pieces[k][0]), min(b, pieces[k][1])
            if hi > lo:
                yield lo, hi, pieces[k]
            k += 1


def _minus(intervals: list, taken: list) -> list:
    """``intervals`` without the parts ``taken`` covers (both sorted, disjoint)."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(taken) and taken[j][1] <= a:
            j += 1
        k = j
        while k < len(taken) and taken[k][0] < b:
            if taken[k][0] > a:
                out.append((a, taken[k][0]))
            a = max(a, taken[k][1])
            k += 1
        if b > a:
            out.append((a, b))
    return out


def attribute(gaps: list, threads: list) -> dict:
    """Split sorted, disjoint ``gaps`` ``[(start, end), ...]`` over the spans
    that overlap them: ``{span: time}``, the rest under ``"none"``. ``threads``
    is a list of one thread's events each, the loop's first; inside a thread
    the innermost span wins, an earlier thread wins over a later one, and a
    ``WEAK`` span (``train``: the whole epoch) yields to every other."""
    flat = [innermost(events) for events in threads]
    out, left = {}, list(gaps)
    for weak in (False, True):
        for pieces in flat:
            pieces = [p for p in pieces if (p[2] in WEAK) == weak]
            taken = []
            for lo, hi, piece in _overlaps(left, pieces):
                out[piece[2]] = out.get(piece[2], 0.0) + hi - lo
                taken.append((lo, hi))
            left = _minus(left, taken)
    out["none"] = trace_lib.length(left)
    return out


def device_gaps(events: list) -> list:
    """The idle intervals between a device's operations ``[[name, start, dur]]``."""
    busy = trace_lib.union([(s, s + d) for _, s, d in events if d > 0])
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]


def idle_by_span(ctx) -> dict | None:
    """Seconds of the first chip's idle time by the program span that covers it."""
    def split():
        spans, devices = load(ctx), (ctx.get("events") or {}).get("devices")
        main = loop_thread(spans["host"]) if spans else None
        if main is None or not devices:
            return None
        host = spans["host"]
        threads = [host[main]] + [ev for t, ev in sorted(host.items()) if t != main]
        gaps = device_gaps(devices[sorted(devices)[0]])
        return {k: v * 1e-9 for k, v in attribute(gaps, threads).items()}

    return _kept(ctx, "_idle_by_span", split)


def window_ns(host: dict) -> float:
    """From the first ``train`` span's start to the last one's end."""
    trains = named(host, "train")
    return max(e[1] for e in trains) - min(e[0] for e in trains) if trains else 0.0


def producers(host: dict) -> int:
    """How many threads other than the loop's worked side by side: the most
    of them with a span open at one time (a prefetcher that starts a new
    thread every epoch counts 1)."""
    main = loop_thread(host)
    marks = []
    for thread, events in host.items():
        if thread != main:
            for a, b in trace_lib.union([(e[0], e[1]) for e in events]):
                marks += [(a, 1), (b, -1)]
    most = now = 0
    for _, step in sorted(marks):
        now += step
        most = max(most, now)
    return most


def split_held(lengths) -> tuple:
    """``(the step calls that returned at once, how many did not)``, from the
    calls' lengths. The TPU runtime holds a step call while its queue of
    executions is full (~37 deep, before the loop's own ``_MAX_IN_FLIGHT``
    engages): such a call lasts until a device step ends and says nothing of
    the host's work, and a median over both kinds flips between them with
    one call more or less on a side. Held = longer than ``HELD`` x the lower
    quartile of all calls; where over three quarters are held that quartile
    is a held call itself and all count as returned at once."""
    lengths = sorted(lengths)
    if len(lengths) < 2:
        return lengths, 0
    limit = HELD * statistics.quantiles(lengths, n=4)[0]
    fast = [d for d in lengths if d <= limit]
    return fast, len(lengths) - len(fast)


def fast_dispatches(host: dict) -> tuple:
    """``split_held`` of the program's ``dispatch`` spans, lengths in ns."""
    return split_held(e[1] - e[0] for e in named(host, "dispatch"))


def self_time_of(host: dict, spans: tuple) -> float:
    """Summed self time (ns) of the named spans over every thread."""
    total = 0.0
    for events in host.values():
        selfs = self_times([(e[0], e[1]) for e in events])
        total += sum(t for e, t in zip(events, selfs) if e[2] in spans)
    return total


# -- device scopes ---------------------------------------------------------------

_WRAPPED = re.compile(r"^((?:[a-z_]+\()+)([^()]*)\)+$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _segments(op_name: str) -> list:
    out, depth, cur = [], 0, []
    for ch in op_name:
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    out.append("".join(cur))
    return out


def parse_scope(op_name: str | None) -> dict | None:
    """``jit(train_step)/transpose(jvp(jvp(HydraModel)))/HydraModel.encode/
    HydraModel.conv_block/graph_convs_2/edge_mlp/dense_0/dot_general`` ->
    ``{"tag": "transpose(jvp(jvp(", "root": "HydraModel", "path":
    ("HydraModel.encode", ..., "dense_0"), "op": "dot_general"}``. ``tag`` is
    the differentiation nest the operation was made in ("" outside any),
    ``path`` the named scopes below it, without ``jit(..)`` wrappers. None for
    a name with no ``jit(...)`` prefix (an operation the compiler made)."""
    if not op_name:
        return None
    segs = _segments(op_name.rstrip(":"))
    if len(segs) < 2 or not segs[0].startswith("jit("):
        return None
    tag, root, path = "", "", []
    for seg in segs[1:-1]:
        m = _WRAPPED.match(seg)
        if m is None:
            path.append(seg)
        elif not tag and ("jvp(" in m.group(1) or "transpose(" in m.group(1)):
            tag, root = m.group(1), m.group(2)
    return {"tag": tag, "root": root, "path": tuple(path), "op": segs[-1]}


def pass_name(tag: str) -> str:
    """The pass of an energy-conserving force step a tag belongs to, by its
    ``transpose(`` wrappers (a ``jvp(`` more or less inside, as custom-JVP
    functions add, changes nothing): none = ``forward`` (the energy), one
    inside a ``jvp(`` = ``forces`` (d energy / d positions), one outermost =
    ``grad.forward`` (the parameter gradient through the forward pass), two =
    ``grad.forces`` (through the forces); ``-`` outside any differentiation."""
    if not tag:
        return "-"
    transposes = tag.count("transpose(")
    if transposes == 0:
        return "forward"
    if transposes == 1:
        return "grad.forward" if tag.startswith("transpose(") else "forces"
    return "grad.forces"


def force_path(tag: str) -> bool:
    """The tag holds the INNER ``transpose(jvp(``: the position gradient, or
    the parameter gradient through it."""
    return tag.find("transpose(jvp(", 1) > 0


def module(scope: dict | None, text: str = "") -> str:
    """The row of the pass x module table an operation belongs to."""
    if scope is None:
        return "unscoped"
    path = scope["path"]
    convs = [i for i, seg in enumerate(path) if re.fullmatch(r"graph_convs_\d+", seg)]
    if convs:
        i = convs[0]
        child = path[i + 1] if len(path) > i + 1 else (
            scope["op"] if MOSAIC in text else "(self)")
        return f"{path[i]}/{child}"
    if "HydraModel.decode" in path:
        return "heads"
    if "HydraModel.conv_block" in path:
        return "conv_block/(self)"
    if "HydraModel.encode" in path:
        return "embedding"
    for name in (*path, scope["root"]):
        if name:
            return name  # a step-level scope (mlip_loss, optimizer) or a kernel's name
    return "kernel:" + scope["op"] if MOSAIC in text else "unscoped"


def scope_of(text: str, scopes: dict) -> dict | None:
    op = scopes.get(text)
    if op is None:
        m = _OP_NAME.search(text)
        op = m.group(1) if m else None
    return parse_scope(op)


def device_by_scope(ctx) -> dict | None:
    """Device self time in ms per step by (pass, module), mean over the
    chips, kept on ``ctx``: ``{"table": {(pass, module): ms}, "conv": ms,
    "force_path": ms, "unscoped": ms, "total": ms}``. None where the trace
    holds no device operation or no operation with a scope."""
    return _kept(ctx, "_device_by_scope", lambda: _device_by_scope(ctx))


def _device_by_scope(ctx):
    spans, events = load(ctx), ctx.get("events")
    if not spans or not events or not events.get("devices") or not ctx.get("steps"):
        return None
    keys, sums = {}, {}
    for dev_events in events["devices"].values():
        selfs = self_times([(s, s + d) for _, s, d in dev_events])
        for (text, _, _), t in zip(dev_events, selfs):
            key = keys.get(text)
            if key is None:
                scope = scope_of(text, spans["scopes"])
                tag = scope["tag"] if scope else ""
                key = keys[text] = (
                    pass_name(tag), module(scope, text),
                    bool(scope and "HydraModel.conv_block" in scope["path"]),
                    force_path(tag))
            sums[key] = sums.get(key, 0.0) + t
    if not any(k[1] != "unscoped" for k in sums):
        return None
    per_step = 1e-6 / (len(events["devices"]) * ctx["steps"])
    table = {}
    for (tag, mod, _, _), t in sums.items():
        table[(tag, mod)] = table.get((tag, mod), 0.0) + t * per_step
    return {
        "table": table,
        "conv": sum(t for k, t in sums.items() if k[2]) * per_step,
        "force_path": sum(t for k, t in sums.items() if k[3]) * per_step,
        "unscoped": sum(t for k, t in sums.items() if k[1] == "unscoped") * per_step,
        "total": sum(sums.values()) * per_step,
    }


# -- compile seconds by program ----------------------------------------------------

STEP_FUNCTIONS = ("train_step", "guarded_step")  # models/mlip.py, resilience/guard.py


def step_compiles() -> dict | None:
    """``{counter: (count, seconds)}`` of the step function from the program's
    own record, summed over the step's names that were lowered (a guarded
    step traces ``train_step`` inside ``guarded_step``: the outer one alone
    is counted). None where the program keeps no such record."""
    from hydragnn_tpu.analysis import sentinel

    if not hasattr(sentinel, "compile_seconds"):
        return None
    by_function = {}
    for fun, record in sentinel.compile_seconds().items():
        fun = fun[4:-1] if fun.startswith("jit(") and fun.endswith(")") else fun
        if fun in STEP_FUNCTIONS:
            by_function.setdefault(fun, []).append(record)
    out = {}
    for records in by_function.values():
        if any("lowerings" in record for record in records):
            for record in records:
                for name, (n, secs) in record.items():
                    have = out.get(name, (0, 0.0))
                    out[name] = (have[0] + n, have[1] + secs)
    return out or None
