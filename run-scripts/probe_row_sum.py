"""Time the row sum ``[E, C] -> [N, C]`` and its gather/sum pair on the chip,
each arm jitted alone: ``python run-scripts/probe_row_sum.py [SECTION ...] [BE,SPAN ...]``.

Sections (default ``schnet dimenet egnn_worst``, PR 38's; ``painn`` and ``egnn``
are PR 34's): ids are the cells' own, the first batch of every padded shape of
the cell's training loader as ``benchmark/lib/program.py`` builds it (seed 7).
``schnet``: the three buckets of ``schnet_mlip_oc20.fill`` at C 256;
``dimenet``: the small bucket of ``dimenetpp_mlip_oc20.fill`` at the block
exchange's 3,200-wide rows; ``egnn``: the buckets an epoch of
``egnn_mlip_mptrj.fill`` runs, at C 128 (the feature rows) and C 3 (the
position rows); ``egnn_worst``: its worst-case bucket, which no epoch reaches
since PR 41: the largest batch collated again under that bucket. fp32, median
of 30 calls, ms. Arms, a shape and C:

  xla        ``jax.ops.segment_sum`` as a step without the kernel has it
  xla_sorted the same scatter told ``indices_are_sorted=True`` (receivers only)
  kernel     ``fused_segment_sum`` as routed under collate's certificate for the
             id array (resident where it holds, tiled where it does not or past
             the budget)
  tiled      the tiled form, whatever the route says
  chain      grad of a force loss through ONE gather/sum pair: forward, VJP and
             grad-of-grad; ``xla`` = plain indexing and XLA's sums, ``pair`` =
             ``segment.gather`` and ``segment.segment_sum`` under collate's
             certificates for their id arrays, as a model that hands them
             ``hints=batch`` has them, ``pair_tiled`` (C a multiple of 128) =
             the same pair with the certificate stated as not held. It holds a
             product with the edge weights and a tanh a pass, XLA's on both sides
  lean_chain the pair and nothing else: ``sum(gather(x))``, its VJP, and the
             VJP's own transpose, in one program
  resident   at EGNN's shapes, the sum alone through a plain gather (as
             ``models/egnn.py`` has it): the kernel's VJP a bare take (its
             transpose is then XLA's scatter-add) against the VJP that closes on
             ``segment.gather``
  gs_chain   (``schnet``) SchNet's whole gather-multiply-sum with an ``[E, C]``
             filter, gradient in ``h`` and the filter of a force loss (forward,
             VJP, grad-of-grad: the step's four passes over one layer), in three
             forms: ``xla`` = ``reference_gather_scatter``, ``kernel`` =
             ``fused_gather_scatter`` with the certificate stated as held (a
             time only: where the batch's ``gs_fits`` is False its values are
             not the sum's), ``pair`` = ``pair_gather_scatter`` (the tiled sum)

After the ``schnet`` section the GO RULE of ISSUE 38 is evaluated and printed:
the tiled sum <= 1.5 ms for both id arrays at ``[225024, 256] -> [4504, 256]``
and the pair's chain >= 1.2 x XLA's there; and, a bucket, which of ``kernel``
and ``pair`` a certified batch should take. After ``egnn egnn_worst`` ISSUE
45's: at the two smallest buckets the pair's chain >= 1.15 x XLA's at C 128,
at the worst-case bucket no slower; the C 3 chains beside it (a loss there
keeps the position reads of ``models/egnn.py`` on plain indexing), and whether
the tiled pair beats the certified one at every bucket.

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


def first_batches(cell_name: str, certified: bool = False) -> list:
    """The first batch of each padded shape of the cell's training loader;
    ``certified``: the first whose sender and receiver certificates both hold,
    where the shape has one."""
    import copy

    from hydragnn_tpu.preprocess.load_data import dataset_loading_and_splitting

    cell = Cell(cell_name)
    graphs = cell.generator.generate(cell.traffic["params"], SEED)
    cfg = {k: copy.deepcopy(cell.config[k]) for k in PROGRAM_KEYS if k in cell.config}
    cfg["NeuralNetwork"]["Training"].update(cell.traffic.get("training", {}))
    loader = dataset_loading_and_splitting(
        cfg, samples=to_samples(graphs, float(cell.config["input_scale"])))[0]
    firsts, held = {}, set()
    for chunk, pad in loader.batch_plan():
        key = pad.as_tuple()
        firsts.setdefault(key, (chunk, pad))
        if certified and key not in held:
            meta = loader.collate_chunk(chunk, pad).meta
            if meta.send_fits and meta.recv_fits:
                firsts[key] = (chunk, pad)
                held.add(key)
    worst = loader.buckets[-1] if loader.buckets else None
    if worst is not None and worst.as_tuple() not in firsts:
        # no batch of the epoch reaches it: the largest one, collated under it
        firsts[worst.as_tuple()] = (firsts[max(firsts)][0], worst)
    return [loader.collate_chunk(*firsts[k]) for k in sorted(firsts)]


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


def pair_under(fits_of):
    """``segment.gather`` and ``segment.segment_sum`` with ``fits_of(ids)`` stated."""
    gather = lambda x, by: segment.gather(x, by, fits=fits_of(by))
    row_sum = lambda d, by, n: segment.segment_sum(d, by, n, fits=fits_of(by))
    return gather, row_sum


def lean_chain(gather, row_sum, gather_ids, sum_ids, n):
    """Forward, VJP and the VJP's transpose of ``row_sum(gather(x))`` alone."""
    pair = lambda x: row_sum(gather(x, gather_ids), sum_ids, n)

    def run(x):
        y, vjp = jax.vjp(pair, x)
        (back,) = vjp(y)
        again = jax.grad(lambda ct: jnp.vdot(jax.vjp(pair, x)[1](ct)[0], back))(y)
        return back, again

    return run


def gs_chain(form):
    """Gradient, in ``h`` and the ``[E, C]`` filter, of a force loss through
    ``form(h, filter)``: what one SchNet layer's aggregate costs a step."""
    energy = lambda x, w: jnp.sum(jnp.tanh(form(x, w)))
    force_loss = lambda x, w: sum(jnp.sum(g ** 2) for g in jax.grad(energy, argnums=(0, 1))(x, w))
    return jax.grad(force_loss, argnums=(0, 1))


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


def probe_shape(cell: str, batch, channels, geometries, chains=True, gs=False) -> None:
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
        if gs:
            forms = {
                "xla": lambda x, w: fs.reference_gather_scatter(x, snd, rcv, n, w),
                "kernel": lambda x, w: fs.fused_gather_scatter(
                    x, snd, rcv, n, w, fits=True, interpret=False),
                "pair": lambda x, w: fs.pair_gather_scatter(x, snd, rcv, n, w),
            }
            for depth, wrap in (("forward", lambda f: f), ("grad_of_grad", gs_chain)):
                ms = {name: timed("gs_chain", wrap(form), x, w, path=name, passes=depth,
                                  gs_fits=batch.meta.gs_fits, **facts)
                      for name, form in forms.items()}
                print(f"# {cell} N {n} E {e} C {c} gather-multiply-sum {depth}: "
                      + ", ".join(f"{k} {v:.3f} ms" for k, v in ms.items())
                      + f" (xla / pair {ms['xla'] / ms['pair']:.2f} x, kernel / pair "
                      f"{ms['kernel'] / ms['pair']:.2f} x)", flush=True)
        if not chains:
            continue
        base = timed("chain", chain(index, xla_sum, rcv, snd, n), x, w, path="xla", **facts)
        # the pair as a model with ``hints=batch`` has it: each id array's own certificate
        held = lambda by: certified["senders" if by is snd else "receivers"]
        pair = timed("chain", chain(*pair_under(held), rcv, snd, n), x, w, path="pair",
                     certified=certified, **facts)
        print(f"# {cell} N {n} E {e} C {c} chain: xla {base:.3f} ms, pair {pair:.3f} ms "
              f"({base / pair:.2f} x)", flush=True)
        if c % 128 == 0:
            tiled = timed("chain", chain(*pair_under(lambda by: False), rcv, snd, n), x, w,
                          path="pair_tiled", **facts)
            print(f"# {cell} N {n} E {e} C {c} chain: pair {pair:.3f} ms, pair on the tiled "
                  f"form {tiled:.3f} ms", flush=True)
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


def find(arm: str, **facts) -> float:
    """Median ms of the one recorded arm with these facts."""
    (hit,) = [r for r in RESULTS if r["arm"] == arm and "geometry" not in r
              and all(r.get(k) == v for k, v in facts.items())]
    return hit["ms"]


def go_rule(cell: str, batches) -> None:
    """ISSUE 38's rule at the worst-case bucket, and kernel against pair a bucket."""
    worst = max(batches, key=lambda b: b.num_nodes)
    at = {"cell": cell, "n": worst.num_nodes, "c": 256}
    tiled = {which: find("tiled", ids=which, **at) for which in ("receivers", "senders")}
    chain = {path: find("gs_chain", path=path, passes="grad_of_grad", **at)
             for path in ("xla", "kernel", "pair")}
    go = max(tiled.values()) <= 1.5 and chain["xla"] / chain["pair"] >= 1.2
    print(f"# GO RULE at N {at['n']}: tiled sum {tiled['receivers']:.3f} (receivers) / "
          f"{tiled['senders']:.3f} (senders) ms against 1.5; chain xla {chain['xla']:.3f} / pair "
          f"{chain['pair']:.3f} = {chain['xla'] / chain['pair']:.2f} x against 1.2 x: "
          f"{'GO' if go else 'NO GO'}", flush=True)
    RESULTS.append({"arm": "go_rule", "go": go, "tiled_ms": tiled, "chain_ms": chain, **at})
    for batch in batches:
        at = {"cell": cell, "n": batch.num_nodes, "c": 256, "passes": "grad_of_grad"}
        kernel, pair = find("gs_chain", path="kernel", **at), find("gs_chain", path="pair", **at)
        print(f"# certified batch at N {batch.num_nodes}: kernel {kernel:.3f} ms, pair {pair:.3f} "
              f"ms: {'pair' if pair <= kernel else 'kernel'}", flush=True)


def egnn_go_rule(cell: str, batches) -> None:
    """ISSUE 45's rule: the pair's chain against plain indexing, a bucket."""
    arms = [(128, "xla"), (128, "pair"), (128, "pair_tiled"), (3, "xla"), (3, "pair")]
    chains = {b.num_nodes: {(c, path): find("chain", path=path, c=c, cell=cell, n=b.num_nodes)
                            for c, path in arms} for b in batches}
    sizes = sorted(chains)
    ratio = lambda n, c: chains[n][c, "xla"] / chains[n][c, "pair"]
    wide = all(ratio(n, 128) >= 1.15 for n in sizes[:2]) and ratio(sizes[-1], 128) >= 1.0
    held = [b.num_nodes for b in batches if b.meta.send_fits and b.meta.recv_fits]
    narrow = all(ratio(n, 3) >= 1.0 for n in held)
    tiled = all(chains[n][128, "pair_tiled"] < chains[n][128, "pair"] for n in sizes[:-1])
    for n in sizes:
        print(f"# N {n}: C 128 xla / pair {ratio(n, 128):.2f} x (pair "
              f"{chains[n][128, 'pair']:.3f}, tiled pair {chains[n][128, 'pair_tiled']:.3f} ms); "
              f"C 3 xla / pair {ratio(n, 3):.2f} x", flush=True)
    reads = "take segment.gather too" if narrow else "stay plain indexing"
    print(f"# GO RULE (ISSUE 45): C 128 >= 1.15 x at N {sizes[0]} and {sizes[1]}, no slower at "
          f"N {sizes[-1]}: {'GO' if wide else 'NO GO'}; C 3 no slower under a held certificate "
          f"(N {held}): the position reads {reads}; tiled pair faster at every bucket the "
          f"window runs: {'yes' if tiled else 'no'}", flush=True)
    RESULTS.append({"arm": "egnn_go_rule", "go": wide, "narrow": narrow, "tiled_faster": tiled,
                    "cell": cell})


def main() -> None:
    if jax.default_backend() != "tpu":
        raise SystemExit("a time comes only from the chip: no TPU here")
    geometries = [tuple(int(v) for v in a.split(",")) for a in sys.argv[1:] if "," in a]
    sections = [a for a in sys.argv[1:] if "," not in a] or ["schnet", "dimenet", "egnn_worst"]
    with jax.default_matmul_precision("highest"):  # the cells' own
        if "schnet" in sections:
            batches = first_batches("schnet_mlip_oc20.fill")
            for batch in batches:
                probe_shape("schnet_mlip_oc20.fill", batch, (256,), geometries, gs=True)
            go_rule("schnet_mlip_oc20.fill", batches)
        if "dimenet" in sections:  # the small bucket: its [E, K I] rows onto 152 atom slots
            probe_shape("dimenetpp_mlip_oc20.fill", first_batches("dimenetpp_mlip_oc20.fill")[0],
                        (3200,), geometries, chains=False)
        if "painn" in sections:
            probe_shape("painn_mlip_md17.fill", first_batches("painn_mlip_md17.fill")[0],
                        (384, 128), geometries)
        if "egnn" in sections or "egnn_worst" in sections:
            batches = first_batches("egnn_mlip_mptrj.fill", certified=True)
            picked = (batches[:-1] if "egnn" in sections else []) + (
                batches[-1:] if "egnn_worst" in sections else [])
            for batch in picked:
                probe_shape("egnn_mlip_mptrj.fill", batch, (128, 3), [])
            if len(picked) == len(batches):
                egnn_go_rule("egnn_mlip_mptrj.fill", batches)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_row_sum.json", "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "results": RESULTS}, f, indent=1)


if __name__ == "__main__":
    main()
