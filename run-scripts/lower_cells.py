"""Lower each benchmark cell's step program for a v5e, without a chip, and
write its StableHLO text: ``python run-scripts/lower_cells.py OUT_DIR [ROOT]``.

``ROOT`` is a checkout of this repository (default: the one this file is in),
so the same file lowers a parent checkout and the working tree; ``diff -r`` of
the two ``OUT_DIR``s then says whether a change moved any cell's device
program. The step is built as ``benchmark/lib/program.py`` builds it (same
configuration, data from the seed, precision, guard) at the first batch's
padded shape, with routing, precision and donation resolved as on the chip.
``as_text()`` carries no source locations, but a Mosaic kernel's serialized
body does (file paths and lines of its whole call stack): each body is
replaced by the SHA-256 of its location-free assembly, so a kernel is compared
by what it computes. Needs libtpu's compile-only topology (one process at a
time).

``python run-scripts/lower_cells.py --compile CELL OUT_DIR`` takes the same
step of ONE cell on to the compiler, at the first batch of EVERY bucket, and
prints a bucket: the compiler's memory analysis (arguments + outputs +
temporaries, GiB: what sizes a cell, never a time), the Mosaic calls, the
``optimization_barrier``s of the lowered text, and every gather and scatter of
the optimized HLO with the length of the index it is keyed by, those keyed by
an index as long as the triplet dimension first (ISSUE 36's check: none may be
left). Writes the optimized HLO to ``OUT_DIR/<cell>.<nodes>x<edges>.hlo.txt``.
A DimeNet++ bucket compiles for a minute or two here. Also a bucket: the
seconds the trace, the lowering and the compile took on this machine, the
serialized executable's bytes, raw and as the compile cache stores them
(compressed; the chip machines' cache holds 192 MiB in all and drops a value
over that, PERF.md section 6), and the program's ``while`` loops with the
leading dimension of what they carry (a scanned stack,
``Training.scan_conv_layers``: the layers it holds). Further
arguments ``key=json`` override ``NeuralNetwork.Training`` keys of the
configuration, for an A/B of one tree: ``scan_conv_layers=false``; the word
``first`` stops after the first bucket, and ``root=DIR`` compiles the checkout
in ``DIR`` (a parent commit) with this file.

``python run-scripts/lower_cells.py --same DIR_A DIR_B`` compares the optimized
HLO files of two such ``OUT_DIR``s: values renamed in order of appearance,
source metadata and the file tables dropped, each Mosaic body hashed without
its locations. "identical" then means the compiler was handed, and made, the
same program on both sides, whatever the lowered text looked like (PR 46: the
kernels' ``fwd`` rules save copies, ``x + 0``, which XLA drops).
"""

import os
import sys

compile_cell = sys.argv[2] if sys.argv[1] == "--compile" else None
same = sys.argv[2:4] if sys.argv[1] == "--same" else None
options = sys.argv[4:] if compile_cell else []
FIRST = "first" in options
out_dir = os.path.abspath(sys.argv[3] if compile_cell else sys.argv[1])
root = os.path.abspath(
    next((o[5:] for o in options if o.startswith("root=")), None)
    or (sys.argv[2] if len(sys.argv) > 2 and not compile_cell and not same
        else os.path.join(os.path.dirname(__file__), "..")))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["HYDRAGNN_COMPILE_CACHE"] = "0"
sys.path[:0] = [root, os.path.join(root, "benchmark")]
os.chdir(root)

import base64  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
from jax._src import compilation_cache  # noqa: E402
from jax._src.interpreters import mlir  # noqa: E402
from jax._src.lib.mlir import ir  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from lib import weights  # noqa: E402
from lib.cells import Cell  # noqa: E402
from lib.program import Program  # noqa: E402

SEED = 7
TRAINING = dict(o.split("=", 1) for o in options if "=" in o and not o.startswith("root="))


def without_kernel_locations(text: str, quote: str = r"\22", gap: str = " ") -> str:
    """``text`` with every Mosaic body replaced by a hash of its assembly
    printed without debug information (``quote`` / ``gap``: how the text
    writes the body's key, StableHLO's escapes by default)."""
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True

    def digest(match):
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return f'{quote}body{quote}:{gap}{quote}sha256:{hashlib.sha256(asm.encode()).hexdigest()}{quote}'

    q = re.escape(quote)
    return re.sub(rf'{q}body{q}:{gap}{q}([A-Za-z0-9+/=]+){q}', digest, text)


def canonical_hlo(path: str) -> str:
    """An optimized HLO file as what was compiled: no file tables, no source
    metadata, kernel bodies without locations, values named by appearance."""
    with open(path) as f:
        text = f.read()
    text = re.sub(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", "\n", text, flags=re.S)
    text = without_kernel_locations(text, quote='"', gap="")
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    names = {}
    return re.sub(r"%[\w.\-]+", lambda m: names.setdefault(m.group(0), f"%v{len(names)}"), text)


def same_hlo(dir_a: str, dir_b: str) -> bool:
    ok = True
    for name in sorted(os.listdir(dir_a)):
        if not os.path.exists(os.path.join(dir_b, name)):
            print(f"{name}: only in {dir_a}")
            ok = False
            continue
        a, b = (canonical_hlo(os.path.join(d, name)).splitlines() for d in (dir_a, dir_b))
        differ = sum(x != y for x, y in zip(a, b)) + abs(len(a) - len(b))
        print(f"{name}: {len(a)} / {len(b)} lines, "
              + ("identical" if not differ else f"{differ} lines differ"))
        ok = ok and not differ
    return ok


def indexed_ops(hlo: str) -> collections.Counter:
    """(opcode, index length, result shape) -> count, over the gathers and
    scatters of an optimized HLO text; the index is the s32 operand."""
    shapes = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", hlo))
    found = collections.Counter()
    for result, op, operands in re.findall(
            r"= (\w+\[[\d,]*\])\S* (gather|scatter)\(([^)]*)\)", hlo):
        lengths = []
        for name in re.findall(r"%([\w.\-]+)", operands):
            shape = shapes.get(name, "")
            if shape.startswith("s32["):
                lengths.append(int(shape[4:-1].split(",")[0] or 1))
        found[(op, max(lengths, default=0), result)] += 1
    return found


device = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu").devices[0]


def spec(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=SingleDeviceSharding(device)),
        tree)


@contextlib.contextmanager
def cell_step(name: str):
    """``(prog, step)``: the cell's program as the benchmark builds it and its
    step built again while the backend reads ``tpu`` (routes, donation,
    interpret=False), which it does until the block ends: lower inside it."""
    cell = Cell(name)
    cell.config["NeuralNetwork"]["Training"].update(
        {k: json.loads(v) for k, v in TRAINING.items()})
    jax.config.update("jax_default_matmul_precision", cell.config["precision"]["matmul"])
    graphs = cell.generator.generate(cell.traffic["params"], SEED)
    prog = Program(cell.config, cell.traffic, graphs,
                   lambda shapes: weights.make_weights(shapes, SEED, cell.config["weights"]))
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        from hydragnn_tpu.models.mlip import make_mlip_train_step
        from hydragnn_tpu.resilience import wrap_step_with_guard
        from hydragnn_tpu.train.step import resolve_loss_scale, resolve_training_precision

        training = prog.config["NeuralNetwork"]["Training"]
        step = make_mlip_train_step(
            prog.model, prog.optimizer,
            compute_dtype=resolve_training_precision(training),
            loss_scale=resolve_loss_scale(training))
        if prog.resilience.guard_enabled:
            step = wrap_step_with_guard(step)
        yield prog, step
    finally:
        jax.default_backend = real_backend


def lower_all():
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    for name in cells:
        with cell_step(name) as (prog, step):
            batch = next(iter(prog.inner_loader))
            text = without_kernel_locations(
                step.lower(spec(prog.state), spec(batch)).as_text())
        path = os.path.join(out_dir, f"{name}.stablehlo.txt")
        with open(path, "w") as f:
            f.write(text)
        print(f"{name}: {len(text.splitlines())} lines, "
              f"{text.count('tpu_custom_call')} Mosaic calls, "
              f"padded shape {tuple(batch.x.shape)} nodes x {tuple(batch.senders.shape)} edges "
              f"-> {path}", flush=True)


def compile_buckets(name: str):
    gib = 2.0 ** 30
    with cell_step(name) as (prog, step):
        loader = prog.inner_loader
        firsts = {}
        for chunk, pad in loader.batch_plan():
            firsts.setdefault(pad.as_tuple(), (chunk, pad))
        for key in sorted(firsts)[:1 if FIRST else None]:
            chunk, pad = firsts[key]
            batch = loader.collate_chunk(chunk, pad)
            t0 = time.perf_counter()
            lowered = step.lower(spec(prog.state), spec(batch))
            t1 = time.perf_counter()
            barriers = lowered.as_text().count("stablehlo.optimization_barrier")
            t2 = time.perf_counter()
            compiled = lowered.compile()
            t3 = time.perf_counter()
            hlo = compiled.as_text()
            blob = compiled.runtime_executable().serialize()
            # a scanned stack: each ``while`` with the leading dimension most of its carried
            # rank-3 arrays share (the layers' stacked kernels and residuals)
            loops = [collections.Counter(
                int(d) for d in re.findall(r"f32\[(\d+),\d+,\d+\]", carried)).most_common(1)[0][0]
                for carried in re.findall(r"= \(([^\n]*?)\) while\(", hlo)]
            print(f"{name} bucket {pad!r}: trace + lower {t1 - t0:.1f} s, compile {t3 - t2:.1f} s "
                  f"on this machine; serialized executable {len(blob)} B "
                  f"({len(blob) / 2 ** 20:.1f} MiB), "
                  f"{len(compilation_cache.compress_executable(blob))} B as the compile cache "
                  f"stores it; {len(loops)} while loop(s), stacked over {loops}", flush=True)
            m = compiled.memory_analysis()
            peak = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
                    - m.alias_size_in_bytes)
            path = os.path.join(out_dir, f"{name}.{pad.n_node}x{pad.n_edge}.hlo.txt")
            with open(path, "w") as f:
                f.write(hlo)
            print(f"{name} bucket {pad!r}: {peak / gib:.2f} GiB (arguments "
                  f"{m.argument_size_in_bytes / gib:.2f} + outputs "
                  f"{m.output_size_in_bytes / gib:.2f} + temporaries "
                  f"{m.temp_size_in_bytes / gib:.2f} - aliased {m.alias_size_in_bytes / gib:.2f}), "
                  f"{hlo.count('custom_call_target=\"tpu_custom_call\"')} Mosaic calls, "
                  f"{barriers} optimization barriers as lowered -> {path}", flush=True)
            ops = indexed_ops(hlo)
            long_ones = {k: v for k, v in ops.items() if pad.n_triplet and k[1] >= pad.n_triplet}
            print(f"  gathers / scatters keyed by an index of the triplet dimension's length "
                  f"({pad.n_triplet}): {sum(long_ones.values())} {dict(long_ones)}", flush=True)
            for (op, length, result), count in sorted(ops.items(), key=lambda kv: -kv[0][1]):
                print(f"  {count:3d} x {op:7s} index length {length:8d} -> {result}", flush=True)


if same:
    sys.exit(0 if same_hlo(*same) else 1)
os.makedirs(out_dir, exist_ok=True)
if compile_cell:
    compile_buckets(compile_cell)
else:
    lower_all()
