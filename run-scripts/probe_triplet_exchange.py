"""Time ONE DimeNet++ layer's triplet exchange on the chip, flat list against
dense ``[E, K]`` block, each arm jitted alone: ``python
run-scripts/probe_triplet_exchange.py``.

The exchange is what ``models/dimenet.py``'s ``interaction/triplets`` scope
holds between ``lin_down`` and ``lin_up``: rows of ``x [E, 64]`` brought to the
triplets, the spherical basis ``[T, 42]`` projected ``-> 8 -> 64``, their
product under the mask, the sum onto the edges ji. Ids are the cell's own: the
first batch of each bucket of ``dimenetpp_mlip_oc20.fill`` (7,424 and 22,528
edges x 50) from the loader ``benchmark/lib/program.py`` builds, collated once
as the flat list (``PadSpec.triplet_rows = None``) and once as the block. fp32,
matmul precision ``highest`` (the cell's own), median of 20 calls, ms. Three
programs an arm, the passes of an MLIP step over the exchange:

  value   the exchange
  vjp     grad of sum(tanh(exchange)) with respect to x and the basis (forces)
  vjp2    grad, with respect to the two projection weights, of the squared norm
          of that gradient (the parameter gradient of a force loss): holds the
          other two

Arms (``form/stride``; ``row_sum`` = what sums the ``[E, K I]`` fat rows onto the
``[N, K I]`` atoms: ``routed`` = ``segment.segment_sum(fits=False)`` (the tiled
Pallas sum where the resident rule refuses, XLA's scatter below it), ``xla``,
``tiled`` (the tiled form whatever the route says)):

  flat        today's: ``segment.gather`` by ``idx_kj``, ``segment_sum`` onto
              ``idx_ji``, T-length ids, XLA's route
  block3d     triplet arrays ``[E, K, C]``; the row side a broadcast, the sum
              ``[E, K I] -> [N, K I]`` by the row's receiver, then the
              ``[N K, I]`` rows placed through the ``[N, K]`` table
  block2d     the same with triplet arrays ``[E K, C]`` (projections on 2-D rows)
  lanes       the product itself at ``[E, K I]`` (lane-dense where ``[T, 64]``
              pads its lanes 2 x): x tiled along lanes, the projected basis
              reshaped once

Stride 56 pads K = 50 to whole 8-row sublane tiles (12% more slots, every
reshape between ``[E K, C]`` and ``[E, K, C]`` a bitcast). Also, alone: the
fat-row sum and its transposed gather at C = K I = 3,200 by each ``row_sum``.

GO RULE (ISSUE 36's, written before any chip run; the probe's first three
calls came back transient, and the call that ran it also ran the cell with the
step already on the block form: PERF.md section 6): the block form's three
programs together take at most HALF the flat form's time at the worst-case
bucket, and the whole step's peak (compile-only memory analysis) stays under
13 GiB. Needs a TPU; prints one JSON line an arm and writes
``chiprun_out/probe_triplet_exchange.json``.

What it printed (my chip run, PR 36; one TPU v5 lite chip, 6 min; ms, value /
vjp / vjp2 = their sum, and its share of the flat form's):

  arm (stride, row_sum)       E 7,424, N 152                    E 22,528, N 456
  flat                        5.67 / 10.78 / 18.60 = 35.05      16.30 / 33.26 / 57.27 = 106.82
  block3d (50, routed)        1.70 /  2.81 /  5.30 =  9.80 0.28  3.22 /  7.41 / 13.97 =  24.59 0.23
  block3d (50, xla)           1.71 /  2.78 /  5.30 =  9.79 0.28  5.10 /  9.28 / 17.48 =  31.87 0.30
  block3d (50, tiled)         1.30 /  2.39 /  4.60 =  8.28 0.24  3.16 /  7.36 / 13.77 =  24.29 0.23
  block3d (56, routed)        2.17 /  3.36 /  6.43 = 11.96 0.34  3.45 /  8.09 / 15.20 =  26.74 0.25
  block2d (50, routed)        3.17 /  6.52 / 13.43 = 23.12 0.66 17.00 / 31.57 / 56.43 = 105.00 0.98
  block2d (56, routed)        3.68 /  7.29 / 14.98 = 25.95 0.74 18.53 / 34.47 / 62.23 = 115.23 1.08
  lanes   (50, routed)        3.30 /  8.49 / 15.70 = 27.48 0.78  8.44 / 36.11 / 62.21 = 106.77 1.00
  fat-row sum [E, 3200] -> N  routed (= xla) 1.17, xla 1.13,    routed (= tiled) 1.25, xla 3.11,
                              tiled 0.78; gather 0.74           tiled 1.18; gather 1.03

GO: block3d at stride 50 on the routed sums takes 0.23 of the flat form's time
at the worst-case bucket (0.28 at the small one); the step's peak is 6.90 GiB
(compile-only, ``run-scripts/lower_cells.py --compile``). Stride 56 is slower (XLA
lays ``[E, K, C]`` out edge-minor, ``{0,2,1}``: K is the MAJOR dimension and pads
nothing, so 12% more slots are 12% more work); triplet arrays as 2-D ``[E K, C]``
rows or as lane-dense ``[E, K I]`` rows cost what the flat list costs (the
row-major ``[T, 64]`` arrays, not only the scatters, are what the flat form pays
for). The tiled sum also beats XLA's fat-row scatter at 152 atom slots (0.78
against 1.17 ms), where the route keeps XLA's because the resident rule accepts
the shape and ``fits=False`` declines it: ``ops/fused_scatter.py`` is not this
PR's to edit (PERF.md section 7).
"""

import copy
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
import numpy as np  # noqa: E402

from hydragnn_tpu.graphs import segment  # noqa: E402
from hydragnn_tpu.graphs.batching import PadSpec, collate  # noqa: E402
from hydragnn_tpu.ops import fused_scatter as fs  # noqa: E402
from lib.cells import Cell  # noqa: E402
from lib.program import PROGRAM_KEYS, to_samples  # noqa: E402

CELL, SEED, CALLS = "dimenetpp_mlip_oc20.fill", 7, 20
I, S_R, B = 64, 42, 8  # triplet embedding, spherical x radial, basis embedding
RESULTS = []


def first_batches() -> list:
    """(flat batch, block batch) of the first chunk of each bucket."""
    from hydragnn_tpu.preprocess.load_data import dataset_loading_and_splitting

    cell = Cell(CELL)
    graphs = cell.generator.generate(cell.traffic["params"], SEED)
    cfg = {k: copy.deepcopy(cell.config[k]) for k in PROGRAM_KEYS if k in cell.config}
    cfg["NeuralNetwork"]["Training"].update(cell.traffic.get("training", {}))
    loader = dataset_loading_and_splitting(
        cfg, samples=to_samples(graphs, float(cell.config["input_scale"])))[0]
    seen = {}
    for chunk, pad in loader.batch_plan():
        seen.setdefault(pad.as_tuple(), (chunk, pad))
    out = []
    for key in sorted(seen):
        chunk, pad = seen[key]
        assert pad.triplet_rows == "kj", pad.triplet_rows  # the corpus caps what an atom sends
        samples = [loader.samples[i] for i in chunk]
        flat = PadSpec(*pad.as_tuple(), node_cap=pad.node_cap)
        out.append((collate(samples, flat), collate(samples, pad)))
    return out


def timed(label: str, fn, *args, **facts) -> float:
    fn = jax.jit(fn)
    start = time.perf_counter()
    jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - start
    times = []
    for _ in range(CALLS):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - start) * 1e3)
    ms = statistics.median(times)
    RESULTS.append({"arm": label, "ms": round(ms, 4), "min_ms": round(min(times), 4),
                    "first_call_s": round(compile_s, 2), **facts})
    print(json.dumps(RESULTS[-1]), flush=True)
    return ms


ROW_SUMS = {
    "routed": lambda d, i, n: segment.segment_sum(d, i, n, fits=False),
    "xla": lambda d, i, n: jax.ops.segment_sum(d, i, num_segments=n),
    "tiled": lambda d, i, n: fs._tiled_sum(d, i, n, False),
}


def flat_form(batch):
    kj, ji = jnp.asarray(batch.idx_kj), jnp.asarray(batch.idx_ji)
    mask = jnp.asarray(batch.triplet_mask)[:, None]
    e = batch.num_edges

    def exchange(x, sbf, w1, w2):
        t = segment.gather(x, kj, fits=False) * ((sbf @ w1) @ w2) * mask
        return segment.segment_sum(t, ji, e, fits=False)

    return exchange, (kj.shape[0], S_R)


def block_form(batch, form: str, stride: int, row_sum: str):
    e, n = batch.num_edges, batch.num_nodes
    k = batch.triplet_mask.shape[0] // e
    table = np.asarray(batch.idx_ji)
    mask = np.asarray(batch.triplet_mask).reshape(e, k)
    if stride > k:
        table = np.pad(table, ((0, 0), (0, stride - k)), constant_values=e - 1)
        mask = np.pad(mask, ((0, 0), (0, stride - k)))
        k = stride
    table, mask = jnp.asarray(table.reshape(-1)), jnp.asarray(mask)
    node = jnp.asarray(batch.receivers)
    fat_sum = ROW_SUMS[row_sum]

    def onto_partner(t2):  # [E, K I] -> [E, I]
        by_atom = fat_sum(t2, node, n)
        return segment.segment_sum(by_atom.reshape(n * k, -1), table, e, fits=False)

    if form == "block3d":
        def exchange(x, sbf, w1, w2):
            t = x[:, None, :] * ((sbf @ w1) @ w2) * mask[:, :, None]
            return onto_partner(t.reshape(e, -1))
        return exchange, (e, k, S_R)
    if form == "block2d":
        def exchange(x, sbf, w1, w2):
            rows = jnp.broadcast_to(x[:, None, :], (e, k, x.shape[1])).reshape(e * k, -1)
            t = rows * ((sbf @ w1) @ w2) * mask.reshape(-1, 1)
            return onto_partner(t.reshape(e, -1))
        return exchange, (e * k, S_R)
    assert form == "lanes", form

    def exchange(x, sbf, w1, w2):
        proj = ((sbf @ w1) @ w2).reshape(e, -1)
        t = jnp.tile(x, (1, k)) * proj * jnp.repeat(mask, x.shape[1], axis=1)
        return onto_partner(t)
    return exchange, (e * k, S_R)


def programs(exchange):
    energy = lambda x, sbf, w1, w2: jnp.sum(jnp.tanh(exchange(x, sbf, w1, w2)))
    forces = jax.grad(energy, argnums=(0, 1))

    def force_loss(x, sbf, w1, w2):
        gx, gs = forces(x, sbf, w1, w2)
        return jnp.sum(gx * gx) + jnp.sum(gs * gs)

    return {"value": exchange, "vjp": forces, "vjp2": jax.grad(force_loss, argnums=(2, 3))}


def probe_arm(label: str, exchange, sbf_shape, **facts) -> float:
    key = jax.random.split(jax.random.PRNGKey(SEED), 4)
    x = jax.random.normal(key[0], (facts["e"], I), jnp.float32)
    sbf = jax.random.normal(key[1], sbf_shape, jnp.float32)
    w1 = jax.random.normal(key[2], (S_R, B), jnp.float32) * 0.2
    w2 = jax.random.normal(key[3], (B, I), jnp.float32) * 0.3
    total = 0.0
    for order, fn in programs(exchange).items():
        total += timed(label, fn, x, sbf, w1, w2, order=order, **facts)
    print(f"# {label} {facts}: three orders {total:.3f} ms", flush=True)
    return total


def parity(flat_batch, block_batch) -> float:
    """The mask-only exchange (every projected basis row = 1) is independent of
    the slots' order: flat and block must agree to rounding."""
    e = flat_batch.num_edges
    x = jax.random.normal(jax.random.PRNGKey(3), (e, I), jnp.float32)
    w1, w2 = jnp.ones((S_R, B)) / S_R, jnp.ones((B, I)) / B
    fl, fshape = flat_form(flat_batch)
    bl, bshape = block_form(block_batch, "block3d", 0, "routed")
    want = jax.jit(fl)(x, jnp.ones(fshape), w1, w2)
    got = jax.jit(bl)(x, jnp.ones(bshape), w1, w2)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def probe_fat_rows(batch) -> None:
    e, n = batch.num_edges, batch.num_nodes
    c = batch.triplet_mask.shape[0] // e * I
    node = jnp.asarray(batch.receivers)
    data = jax.random.normal(jax.random.PRNGKey(5), (e, c), jnp.float32)
    rows = jax.random.normal(jax.random.PRNGKey(6), (n, c), jnp.float32)
    facts = {"n": n, "e": e, "c": c}
    want = jax.jit(ROW_SUMS["xla"], static_argnums=2)(data, node, n)
    for name, fn in ROW_SUMS.items():
        route = None
        if name == "routed":
            route = ("xla" if fs.scatter_route(data, e, n, 128) is None else
                     fs.scatter_route(data, e, n, 128, tiled=True) or "tiled")
        got = jax.jit(fn, static_argnums=2)(data, node, n)
        err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        timed("fat_row_sum", lambda d, i, fn=fn: fn(d, i, n), data, node, row_sum=name,
              route=route, rel_err=err, **facts)
    timed("fat_row_gather", lambda r, i: segment.gather(r, i, fits=False), rows, node, **facts)


def main() -> None:
    if jax.default_backend() != "tpu":
        raise SystemExit("a time comes only from the chip: no TPU here")
    arms = [("block3d", 0, "routed"), ("block3d", 0, "xla"), ("block3d", 0, "tiled"),
            ("block3d", 56, "routed"), ("block2d", 0, "routed"), ("block2d", 56, "routed"),
            ("lanes", 0, "routed")]
    summary = []
    with jax.default_matmul_precision("highest"):
        for flat_batch, block_batch in first_batches():
            e, n = flat_batch.num_edges, flat_batch.num_nodes
            k = block_batch.triplet_mask.shape[0] // e
            facts = {"n": n, "e": e, "k": k}
            print(f"# bucket N {n} E {e} K {k}: real triplets flat "
                  f"{int(flat_batch.triplet_mask.sum())} block {int(block_batch.triplet_mask.sum())}, "
                  f"mask-only parity {parity(flat_batch, block_batch):.1e}", flush=True)
            probe_fat_rows(block_batch)
            flat_ms = probe_arm("flat", *flat_form(flat_batch), **facts)
            for form, stride, row_sum in arms:
                exchange, shape = block_form(block_batch, form, stride, row_sum)
                ms = probe_arm(form, exchange, shape, stride=stride or k, row_sum=row_sum, **facts)
                summary.append({"e": e, "form": form, "stride": stride or k, "row_sum": row_sum,
                                "ms": round(ms, 3), "flat_ms": round(flat_ms, 3),
                                "share_of_flat": round(ms / flat_ms, 3)})
                print("# " + json.dumps(summary[-1]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_triplet_exchange.json", "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "summary": summary,
                   "results": RESULTS}, f, indent=1)


if __name__ == "__main__":
    main()
