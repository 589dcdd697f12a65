"""Compile-only sizing of a cell for a v5e, without the chip.

    JAX_PLATFORMS=cpu python benchmark/tools/sizing.py <cell> [batch ...]

For each batch size (default: the traffic file's) it builds the cell as a run
does, on the CPU, then compiles the train step of every pad bucket for a
described ``v5e:2x2`` device and prints what the compiler says it needs:
``memory_analysis()`` bytes and Mosaic calls. Host and compiler facts, not
device numbers: nothing runs.
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]


def main(argv):
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from lib import weights
    from lib.cells import Cell
    from lib.program import Program

    cell = Cell(argv[0])
    batches = [int(b) for b in argv[1:]] or [cell.traffic["training"]["batch_size"]]
    jax.config.update("jax_default_matmul_precision", cell.config["precision"]["matmul"])
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"  # routing, donation as on the chip
    graphs = cell.generator.generate(cell.traffic["params"], 0)
    for bs in batches:
        traffic = dict(cell.traffic, training=dict(cell.traffic["training"], batch_size=bs))
        prog = Program(cell.config, traffic, graphs,
                       lambda sh: weights.make_weights(sh, 0, cell.config["weights"]))
        loader = prog.inner_loader
        buckets = loader.buckets or [loader.pad]
        one = SingleDeviceSharding(topo.devices[0])
        place = lambda tree: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
        chunk0 = prog.plan(0)[0][0]
        sizes = np.array([(s.num_nodes, s.num_edges) for s in loader.samples])
        order = np.argsort(sizes[:, 0])
        for b in buckets:
            chunk = chunk0 if sizes[chunk0].sum(0)[1] <= b.n_edge and \
                sizes[chunk0].sum(0)[0] < b.n_node else order[:bs]
            batch = prog.collate(chunk, b)
            t0 = time.perf_counter()
            compiled = prog.step.step.lower(place(prog.state), place(batch)).compile()
            dt = time.perf_counter() - t0
            m = compiled.memory_analysis()
            text = compiled.as_text()
            need = m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes \
                - m.alias_size_in_bytes
            print(f"batch {bs} bucket {b.as_tuple()}: temp {m.temp_size_in_bytes / 2**30:.2f} GiB, "
                  f"args {m.argument_size_in_bytes / 2**30:.3f}, out {m.output_size_in_bytes / 2**30:.3f}, "
                  f"alias {m.alias_size_in_bytes / 2**30:.3f}, total {need / 2**30:.2f} GiB; "
                  f"mosaic calls {text.count('custom_call_target=\"tpu_custom_call\"')}; "
                  f"compile {dt:.0f} s", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
