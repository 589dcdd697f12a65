"""Where does a DimeNet++ step of the benchmark's cell go non-finite?

    python3 run-scripts/debug_dimenet_nan.py <seed> <pad_buckets> <perc_train> <stages> [entry]

Builds the cell's program as the harness does (``pad_buckets`` and
``perc_train`` as given) and takes compared step ``entry`` (default 0) of
``run.check_entries``. Stages, any of:

  e f g  the energies, the loss (with forces) and the parameter gradient of
         the loss as plain jits: finiteness and size of every result
  c      with e / f: the same under ``jax.experimental.checkify`` float checks
  s      the run's own path: ``train_epoch`` over the compared steps
  t      separate jits from the seeded weights and from those one step on, the
         same graphs in a bucket 8 nodes and 128 edges larger, then the loss
         with a tap (host callback on value and cotangent) at every gather,
         sum and activation of the conv stack
  v      the loss with ``triplet_basis``'s barriers taken out, then with
         barriers at one kind of site each (sums, gathers, silu, basis), then
         as the model has it

What it found (chip, PR 35; PERF.md section 6): seed 2147500012, 3 buckets,
perc_train 0.2, entry 1: forces NaN for every real atom unless the two parts
of sbf are computed behind barriers. Not part of a benchmark run.
"""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]


def main(argv):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import checkify

    from lib import weights
    from lib.cells import Cell
    from lib.program import Program
    from run import check_entries, signatures
    from hydragnn_tpu.models.mlip import energy_force_loss, make_energy_and_forces, make_graph_energy_fn

    seed, buckets, perc = int(argv[0]), int(argv[1]), float(argv[2])
    stages = argv[3] if len(argv) > 3 else "efg"
    which = int(argv[4]) if len(argv) > 4 else 0
    t0 = time.perf_counter()

    def say(msg):
        print(f"[{time.perf_counter() - t0:7.1f}s] {msg}", flush=True)

    cell = Cell("dimenetpp_mlip_oc20.fill")
    cell.config["NeuralNetwork"]["Training"]["pad_buckets"] = buckets
    cell.traffic["training"]["perc_train"] = perc
    jax.config.update("jax_default_matmul_precision", cell.config["precision"]["matmul"])
    say(f"devices {jax.devices()}")
    graphs = cell.generator.generate(cell.traffic["params"], seed)
    prog = Program(cell.config, cell.traffic, graphs,
                   lambda sh: weights.make_weights(sh, seed, cell.config["weights"]), log=say)
    checked = check_entries(prog, signatures(prog, 2), 3)
    say(f"compared steps {[(list(map(int, c)), p.as_tuple()) for c, p in checked]}")
    chunk, pad = checked[which]
    batch = jax.tree.map(jnp.asarray, prog.collate(chunk, pad))
    say(f"batch {pad.as_tuple()} meta {batch.meta}")
    variables = {"params": prog.params0}
    model = prog.model
    spec = model.spec
    energy_fn = make_graph_energy_fn(model)
    eandf = make_energy_and_forces(model)

    def energies(variables, batch):
        return energy_fn(variables, batch.pos, batch)

    def loss(variables, batch):
        e, f = eandf(variables, batch)
        return energy_force_loss(spec, e, f, batch)[0], (e, f)

    def grad(variables, batch):
        (l, (e, f)), g = jax.value_and_grad(loss, has_aux=True)(variables, batch)
        return l, g

    def report(name, tree):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        bad = []
        for path, leaf in flat:
            a = np.asarray(leaf, np.float64)
            if not np.isfinite(a).all():
                bad.append(jax.tree_util.keystr(path))
        top = max((float(np.nanmax(np.abs(np.asarray(l, np.float64)))) for _, l in flat
                   if np.asarray(l).size), default=0.0)
        say(f"{name}: {len(flat)} leaves, {len(bad)} not finite {bad[:6]}, largest |value| {top:.4g}")

    if "t" in stages:
        # which tensor goes non-finite first: the failing batch (entry
        # ``which``), from the seeded weights and from the weights one step on
        # (the step program comes from the compile cache), as separate jits
        # of the energies, the loss and the gradient; the same graphs in a
        # bucket 8 nodes and 128 edges larger; then the loss again with a tap
        # on every gather, sum and activation of the conv stack, forward
        # values and the cotangents of the force pass
        import functools

        from hydragnn_tpu.graphs import segment
        from hydragnn_tpu.graphs.batching import PadSpec
        from hydragnn_tpu.models import dimenet
        from hydragnn_tpu.train.step import TrainState

        prog.state = TrainState(params=jax.tree.map(jnp.asarray, prog.params0), batch_stats={},
                                opt_state=jax.jit(prog.optimizer.init)(prog.params0),
                                step=jnp.zeros((), jnp.int32))
        prog.step.captured, prog.step.capture = [], 1
        prog.steps(checked[:1])
        one_on = jax.device_get(prog.step.captured[0][0])
        near = PadSpec(pad.n_node + 8, pad.n_edge + 128, pad.n_graph, 50 * (pad.n_edge + 128),
                       node_cap=pad.node_cap)
        wider = jax.tree.map(jnp.asarray, prog.collate(chunk, near))
        for tag, params in (("seeded", prog.params0), ("one step on", one_on)):
            v = {"params": jax.tree.map(jnp.asarray, params)}
            report(f"{tag}: energies", jax.jit(energies)(v, batch))
            report(f"{tag}: loss with forces", jax.jit(loss)(v, batch))
            report(f"{tag}: loss and gradient", jax.jit(grad)(v, batch))
            report(f"{tag}: loss with forces, bucket {near.as_tuple()}", jax.jit(loss)(v, wider))
        report(f"one step on: loss and gradient, bucket {near.as_tuple()}", jax.jit(grad)(v, wider))

        seen = {}

        def record(name, finite, top):
            if not bool(finite):
                say(f"  tap {name}: NOT finite")
            seen[name] = float(top)

        counts = {}

        @functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
        def tap(x, name):
            jax.debug.callback(record, name + " value", jnp.isfinite(x).all(), jnp.max(jnp.abs(x)))
            return x

        def tap_fwd(x, name):
            return tap(x, name), None

        def tap_bwd(name, _, ct):
            jax.debug.callback(record, name + " cotangent", jnp.isfinite(ct).all(), jnp.max(jnp.abs(ct)))
            return (ct,)

        tap.defvjp(tap_fwd, tap_bwd)

        def site(kind, x):
            counts[kind] = counts.get(kind, 0) + 1
            return tap(x, f"{kind}#{counts[kind]}")

        gather0, sum0, silu0 = segment.gather, segment.segment_sum, dimenet.nn.silu
        radial0, angular0 = dimenet.radial_on_edges, dimenet.angular_on_triplets
        segment.gather = lambda x, ids, hints=None: site(
            f"gather{list(x.shape)} out", gather0(site(f"gather{list(x.shape)} in", x), ids, hints))
        segment.segment_sum = lambda d, ids, n, hints=None: site(
            f"sum{list(d.shape)} out", sum0(site(f"sum{list(d.shape)} in", d), ids, n, hints))
        dimenet.nn.silu = lambda x: site(f"silu{list(x.shape)} out", silu0(site(f"silu{list(x.shape)} in", x)))
        dimenet.radial_on_edges = lambda x, *a: site("radial out", radial0(site("radial in", x), *a))
        dimenet.angular_on_triplets = lambda c, *a: site("angular out", angular0(site("cos", c), *a))
        try:
            out = jax.jit(lambda v, b: loss(v, b))(v, batch)  # traced anew, with the taps
            jax.block_until_ready(out)
            jax.effects_barrier()
        finally:
            segment.gather, segment.segment_sum, dimenet.nn.silu = gather0, sum0, silu0
            dimenet.radial_on_edges, dimenet.angular_on_triplets = radial0, angular0
        report("one step on: loss with forces, tapped program", out)
        big = sorted(seen.items(), key=lambda kv: -(kv[1] if kv[1] == kv[1] else 1e300))[:12]
        say(f"  {len(seen)} taps; largest: {big}")

    if "v" in stages:
        # the loss of the failing batch (entry ``which``) from the seeded
        # weights, as variants of one program: optimization barriers at one
        # kind of site each, to see which fusion the fault needs
        from hydragnn_tpu.graphs import segment
        from hydragnn_tpu.models import dimenet

        v = {"params": jax.tree.map(jnp.asarray, prog.params0)}
        barrier = jax.lax.optimization_barrier
        sum0, gather0, silu0 = segment._sum, segment._gather, dimenet.nn.silu
        radial0, angular0 = dimenet.radial_on_edges, dimenet.angular_on_triplets

        def patches(kind):
            if kind == "sums":     # every scatter-add takes a finished operand
                segment._sum = lambda d, ids, n, fits: sum0(barrier(d), ids, n, fits)
            elif kind == "gathers":  # every gather hands on a finished result (and cotangent)
                segment._gather = lambda x, ids, n, fits: barrier(gather0(x, ids, n, fits))
            elif kind == "silu":
                dimenet.nn.silu = lambda x: barrier(silu0(barrier(x)))
            elif kind == "basis":
                dimenet.radial_on_edges = lambda x, *a: barrier(radial0(barrier(x), *a))
                dimenet.angular_on_triplets = lambda c, *a: barrier(angular0(barrier(c), *a))

        for kind in ("none", "sums", "gathers", "silu", "basis", "as the model has it"):
            if kind != "as the model has it":  # take ``triplet_basis``'s own barriers out
                jax.lax.optimization_barrier = lambda x: x
            patches(kind)
            try:
                l, (e, f) = jax.jit(lambda v, b: loss(v, b))(v, batch)
                f = np.asarray(f)
            finally:
                jax.lax.optimization_barrier = barrier
                segment._sum, segment._gather, dimenet.nn.silu = sum0, gather0, silu0
                dimenet.radial_on_edges, dimenet.angular_on_triplets = radial0, angular0
            rows = np.flatnonzero(~np.isfinite(f).all(axis=1))
            graph_of = np.asarray(batch.batch)[rows]
            say(f"barriers at {kind}: loss {float(l):.6g}; {len(rows)} of {len(f)} force rows not finite, "
                f"graphs {np.unique(graph_of, return_counts=True)}, first rows {rows[:12].tolist()}")

    if "s" in stages:
        # the run's own path: ``train_epoch`` over the compared steps, one
        # compiled step program a bucket
        from hydragnn_tpu.train.step import TrainState

        def fresh(params):
            params = jax.tree.map(jnp.asarray, params)
            return TrainState(params=params, batch_stats={},
                              opt_state=jax.jit(prog.optimizer.init)(params),
                              step=jnp.zeros((), jnp.int32))

        def drive(tag, params, entries):
            prog.state = fresh(params)
            prog.step.captured, prog.step.capture = [], len(entries)
            prog.steps(entries)
            captured = jax.device_get(prog.step.captured)
            before = params
            for i, (p, opt_state, l) in enumerate(captured):
                report(f"{tag} step {i + 1} {entries[i][1].as_tuple()[:2]} loss {float(l):.6g}; params", p)
                mu = jax.tree_util.tree_leaves(opt_state)
                if not (np.isfinite(float(l)) and all(np.isfinite(np.asarray(x)).all() for x in mu)):
                    # the same batch, the weights this step began from, outside the step program
                    b = jax.tree.map(jnp.asarray, prog.collate(*entries[i]))
                    v = {"params": jax.tree.map(jnp.asarray, before)}
                    report(f"{tag} step {i + 1} again, loss and gradient as a plain jit", jax.jit(grad)(v, b))
                    err, out = jax.jit(checkify.checkify(loss, errors=checkify.float_checks))(v, b)
                    say(f"{tag} step {i + 1} again, checkify of the loss: {err.get()}")
                    break
                before = p

        drive("seeded", prog.params0, checked)

    for key, name, fn in (("e", "energies", energies), ("f", "loss with forces", loss),
                          ("g", "loss and parameter gradient", grad)):
        if key not in stages:
            continue
        plain = jax.jit(fn)(variables, batch)
        jax.block_until_ready(plain)
        report(name, plain)
        if "C" in stages or ("c" in stages and key != "g"):
            err, out = jax.jit(checkify.checkify(fn, errors=checkify.float_checks))(variables, batch)
            jax.block_until_ready(out)
            say(f"checkify {name}: {err.get()}")
            report(name + " (checked program)", out)


if __name__ == "__main__":
    main(sys.argv[1:])
    sys.stdout.flush()
    os._exit(0)
