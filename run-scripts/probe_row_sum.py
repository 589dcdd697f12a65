"""Time the row sum ``[E, C] -> [N, C]`` and its gather/sum pair on the chip,
each arm jitted alone: ``python run-scripts/probe_row_sum.py [BE,SPAN ...]``.

Ids are the cells' own: the first batch of ``painn_mlip_md17.fill`` and the
first batch of every padded shape of ``egnn_mlip_mptrj.fill``, from the
loaders ``benchmark/lib/program.py`` builds (seed 7). fp32, median of 30
calls, ms. Arms, a shape and C:

  xla        ``jax.ops.segment_sum`` as a step without the kernel has it
  xla_sorted the same scatter told ``indices_are_sorted=True`` (receivers only)
  kernel     ``fused_segment_sum`` as routed (resident or tiled by the budget)
  tiled      the tiled form, whatever the route says (EGNN's shapes: for D2)
  chain      grad of a force loss through ONE gather/sum pair: forward, VJP and
             grad-of-grad; ``xla`` = plain indexing and XLA's sums, ``pair`` =
             ``segment.gather`` and ``segment.segment_sum``. It holds a product
             with the edge weights and a tanh a pass, XLA's on both sides
  lean_chain the pair and nothing else: ``sum(gather(x))``, its VJP, and the
             VJP's own transpose, in one program
  resident   at EGNN's shapes, the sum alone through a plain gather (as
             ``models/egnn.py`` has it): the kernel's VJP a bare take (its
             transpose is then XLA's scatter-add) against the VJP that closes on
             ``segment.gather``

``BE,SPAN`` arguments time the tiled form at other geometries too (edges a
block, accumulator rows). Needs a TPU; prints one JSON line an arm and writes
``chiprun_out/probe_row_sum.json``.
"""

import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
os.chdir(ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from hydragnn_tpu.graphs import segment  # noqa: E402
from hydragnn_tpu.ops import fused_scatter as fs  # noqa: E402
from lib.cells import Cell  # noqa: E402
from lib.program import PROGRAM_KEYS, to_samples  # noqa: E402

SEED, CALLS = 7, 30
RESULTS = []


def first_batches(cell_name: str) -> list:
    """The first batch of each padded shape of the cell's training loader."""
    import copy

    from hydragnn_tpu.preprocess.load_data import dataset_loading_and_splitting

    cell = Cell(cell_name)
    graphs = cell.generator.generate(cell.traffic["params"], SEED)
    cfg = {k: copy.deepcopy(cell.config[k]) for k in PROGRAM_KEYS if k in cell.config}
    cfg["NeuralNetwork"]["Training"].update(cell.traffic.get("training", {}))
    loader = dataset_loading_and_splitting(
        cfg, samples=to_samples(graphs, float(cell.config["input_scale"])))[0]
    seen = {}
    for batch in loader:
        seen.setdefault((batch.num_nodes, batch.senders.shape[0]), batch)
    return [seen[k] for k in sorted(seen)]


def timed(label: str, fn, *args, **facts) -> float:
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(CALLS):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - start) * 1e3)
    ms = statistics.median(times)
    RESULTS.append({"arm": label, "ms": round(ms, 4), "min_ms": round(min(times), 4), **facts})
    print(json.dumps(RESULTS[-1]), flush=True)
    return ms


def parity(kernel, data, ids, n) -> float:
    got, want = jax.jit(kernel)(data, ids), xla_sum(data, ids, n)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def xla_sum(data, ids, n, sorted_ids=False):
    return jax.ops.segment_sum(data, ids, num_segments=n, indices_are_sorted=sorted_ids)


def chain(gather, row_sum, gather_ids, sum_ids, n):
    """Gradient, with respect to the edge weights, of a force loss through
    ``row_sum(gather(x) * w)``: the passes of an MLIP step over one pair."""
    energy = lambda x, w: jnp.sum(jnp.tanh(row_sum(gather(x, gather_ids) * w, sum_ids, n)))
    force_loss = lambda x, w: jnp.sum(jax.grad(energy)(x, w) ** 2)
    return jax.grad(force_loss, argnums=1)


def lean_chain(gather, row_sum, gather_ids, sum_ids, n):
    """Forward, VJP and the VJP's transpose of ``row_sum(gather(x))`` alone."""
    pair = lambda x: row_sum(gather(x, gather_ids), sum_ids, n)

    def run(x):
        y, vjp = jax.vjp(pair, x)
        (back,) = vjp(y)
        again = jax.grad(lambda ct: jnp.vdot(jax.vjp(pair, x)[1](ct)[0], back))(y)
        return back, again

    return run


def resident_bare(certified: bool):
    """The resident kernel with the VJP it had before the pair: a bare take."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def row_sum(data, ids, n):
        return fs._scatter_or_ref(data, ids, n, 128, 256, False, certified)

    row_sum.defvjp(lambda d, i, n: (row_sum(d, i, n), i),
                   lambda n, i, g: (jnp.take(g, i, axis=0), None))
    return row_sum


def padded(row_sum):
    """``row_sum`` over rows padded to whole 256s, as ``fused_segment_sum`` pads."""
    def call(data, ids, n):
        pad = -data.shape[0] % 256
        return row_sum(jnp.pad(data, ((0, pad), (0, 0))),
                       jnp.pad(ids, (0, pad), constant_values=n - 1), n)
    return call


def probe_shape(cell: str, batch, channels, geometries) -> None:
    n, e = batch.num_nodes, batch.senders.shape[0]
    ids = {"senders": jnp.asarray(batch.senders), "receivers": jnp.asarray(batch.receivers)}
    # collate's certificates, which the resident form (and it alone) reads
    certified = {"senders": batch.meta.send_fits, "receivers": batch.meta.recv_fits}
    for c in channels:
        key = jax.random.split(jax.random.PRNGKey(SEED + c), 2)
        data = jax.random.normal(key[0], (e, c), jnp.float32)
        x = jax.random.normal(key[1], (n, c), jnp.float32)
        facts = {"cell": cell, "n": n, "e": e, "c": c}
        resident = fs.scatter_route(data, e, n, 128) is None
        for which, by in ids.items():
            f = dict(facts, ids=which)
            want = timed("xla", lambda d, i: xla_sum(d, i, n), data, by, **f)
            if which == "receivers":
                timed("xla_sorted", lambda d, i: xla_sum(d, i, n, True), data, by, **f)
            fits = certified[which]
            kernel = lambda d, i, fits=fits: fs.fused_segment_sum(d, i, n, fits)
            err = parity(kernel, data, by, n)
            ms = timed("kernel", kernel, data, by, certified=fits,
                       form="resident" if resident else "tiled", rel_err=err, **f)
            print(f"# {cell} N {n} E {e} C {c} {which}: xla {want:.3f} ms, kernel {ms:.3f} ms "
                  f"({want / ms:.2f} x), rel err {err:.1e}", flush=True)
            if resident and c % 128 == 0:
                timed("tiled", lambda d, i: fs._tiled_sum(d, i, n, False), data, by, **f)
            for block, span in geometries:
                if c % 128:
                    continue
                real = fs._tile_geometry
                fs._tile_geometry = lambda n, c: (block, min(span, n // 128 * 128))
                fs._tiled_call.clear_cache()
                try:
                    timed("tiled", lambda d, i: fs._tiled_sum(d, i, n, False), data, by,
                          geometry=[block, span], **f)
                finally:
                    fs._tile_geometry = real
                    fs._tiled_call.clear_cache()
        snd, rcv = ids["senders"], ids["receivers"]
        w = data
        index = lambda x, i: x[i]
        base = timed("chain", chain(index, xla_sum, rcv, snd, n), x, w, path="xla", **facts)
        pair = timed("chain", chain(segment.gather, segment.segment_sum, rcv, snd, n), x, w,
                     path="pair", **facts)
        print(f"# {cell} N {n} E {e} C {c} chain: xla {base:.3f} ms, pair {pair:.3f} ms "
              f"({base / pair:.2f} x)", flush=True)
        base = timed("lean_chain", lean_chain(index, xla_sum, rcv, snd, n), x, path="xla", **facts)
        pair = timed("lean_chain", lean_chain(segment.gather, segment.segment_sum, rcv, snd, n),
                     x, path="pair", **facts)
        print(f"# {cell} N {n} E {e} C {c} lean chain: xla {base:.3f} ms, pair {pair:.3f} ms "
              f"({base / pair:.2f} x)", flush=True)
        if resident and certified["senders"] is not False:
            cert = bool(certified["senders"])
            closed = lambda d, i, n: fs._fused_scatter(d, i, n, 128, 256, False, cert)
            a = timed("resident", chain(index, padded(resident_bare(cert)), rcv, snd, n), x, w,
                      vjp="bare take", **facts)
            b = timed("resident", chain(index, padded(closed), rcv, snd, n), x, w,
                      vjp="segment.gather", **facts)
            print(f"# {cell} N {n} E {e} C {c} resident VJP: bare take {a:.3f} ms, "
                  f"closed on the pair {b:.3f} ms", flush=True)


def main() -> None:
    if jax.default_backend() != "tpu":
        raise SystemExit("a time comes only from the chip: no TPU here")
    geometries = [tuple(int(v) for v in arg.split(",")) for arg in sys.argv[1:]]
    with jax.default_matmul_precision("highest"):  # the cells' own
        probe_shape("painn_mlip_md17.fill", first_batches("painn_mlip_md17.fill")[0],
                    (384, 128), geometries)
        for batch in first_batches("egnn_mlip_mptrj.fill"):
            probe_shape("egnn_mlip_mptrj.fill", batch, (128,), [])
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_row_sum.json", "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "results": RESULTS}, f, indent=1)


if __name__ == "__main__":
    main()
