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
A DimeNet++ bucket compiles for a minute or two here.
"""

import os
import sys

compile_cell = sys.argv[2] if sys.argv[1] == "--compile" else None
out_dir = os.path.abspath(sys.argv[3] if compile_cell else sys.argv[1])
root = os.path.abspath(sys.argv[2] if len(sys.argv) > 2 and not compile_cell
                       else os.path.join(os.path.dirname(__file__), ".."))
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

import jax  # noqa: E402
from jax._src.interpreters import mlir  # noqa: E402
from jax._src.lib.mlir import ir  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from lib import weights  # noqa: E402
from lib.cells import Cell  # noqa: E402
from lib.program import Program  # noqa: E402

SEED = 7


def without_kernel_locations(text: str) -> str:
    """``text`` with every Mosaic body replaced by a hash of its assembly
    printed without debug information."""
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True

    def digest(match):
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return f'\\22body\\22: \\22sha256:{hashlib.sha256(asm.encode()).hexdigest()}\\22'

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', digest, text)


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
        for key in sorted(firsts):
            chunk, pad = firsts[key]
            batch = loader.collate_chunk(chunk, pad)
            lowered = step.lower(spec(prog.state), spec(batch))
            barriers = lowered.as_text().count("stablehlo.optimization_barrier")
            compiled = lowered.compile()
            hlo = compiled.as_text()
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


os.makedirs(out_dir, exist_ok=True)
if compile_cell:
    compile_buckets(compile_cell)
else:
    lower_all()
