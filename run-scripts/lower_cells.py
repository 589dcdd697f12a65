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
"""

import os
import sys

out_dir = os.path.abspath(sys.argv[1])
root = os.path.abspath(sys.argv[2] if len(sys.argv) > 2
                       else os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["HYDRAGNN_COMPILE_CACHE"] = "0"
sys.path[:0] = [root, os.path.join(root, "benchmark")]
os.chdir(root)

import base64  # noqa: E402
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


os.makedirs(out_dir, exist_ok=True)
device = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu").devices[0]
with open(os.path.join(root, "BENCHMARK.json")) as f:
    cells = [w["name"] for w in json.load(f)["workloads"]]

for name in cells:
    cell = Cell(name)
    jax.config.update("jax_default_matmul_precision", cell.config["precision"]["matmul"])
    graphs = cell.generator.generate(cell.traffic["params"], SEED)
    prog = Program(cell.config, cell.traffic, graphs,
                   lambda shapes: weights.make_weights(shapes, SEED, cell.config["weights"]))
    batch = next(iter(prog.inner_loader))
    spec = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=SingleDeviceSharding(device)),
        tree)
    args = (spec(prog.state), spec(batch))
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"  # routes, donation, interpret=False
    try:
        # the program's own construction again, now that the backend reads tpu
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
        text = without_kernel_locations(step.lower(*args).as_text())
    finally:
        jax.default_backend = real_backend
    path = os.path.join(out_dir, f"{name}.stablehlo.txt")
    with open(path, "w") as f:
        f.write(text)
    print(f"{name}: {len(text.splitlines())} lines, "
          f"{text.count('tpu_custom_call')} Mosaic calls, "
          f"padded shape {tuple(batch.x.shape)} nodes x {tuple(batch.senders.shape)} edges "
          f"-> {path}", flush=True)
