#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that hydragnn_tpu still starts on the chip.

    python3 chip_smoke.py [--seed N]          # on a machine with a TPU

One process drives the system's main path once, through the entry points a
user calls, at the shipped width of the one config file the repo ships, and
checks what comes out by the repo's own means. Legs, each printing its wall
time and PASS/FAIL (any failure -> non-zero exit, and no result line):

  device   jax.default_backend() must be "tpu" — there is NO CPU path; prints
           platform / device_kind / count, jax / jaxlib / libtpu versions,
           the resolved compile-cache directory; rebuilds the native
           libraries from their tracked .cpp (native | numpy).
  kernels  every default-on Pallas kernel, fp32 and bf16, compiled with
           interpret=False at the train leg's shapes, run on the device and
           compared with its XLA reference, forward and VJP. Prints the
           routing table (kernel x dtype -> mosaic | xla: <static reason>)
           and holds each compiled program to it. MACE's fused tensor product
           at its cell's worst-case bucket and fused_segment_sum's tiled form
           at PaiNN's cell shape (N 21,512, past the resident budget) as well,
           the latter through grad-of-grad of one segment.gather / sum pair;
           SchNet's gather-multiply-sum with no certificate at its cell's
           worst-case bucket (the pair on the tiled sum).
  train    hydragnn_tpu.run_training + run_prediction on examples/qm9/qm9.json
           as shipped (GIN, hidden 64, 4 conv layers, bf16, batch 64, AdamW)
           over seeded synthetic QM9-sized molecules, a few epochs of a few
           steps: loss finite and lower at the end, predictions finite, and
           the compiled step holds exactly the Mosaic calls the table promises.
  mlip     the examples/LennardJones EGNN config as shipped (energy + forces
           through jax.grad), a few steps: force loss finite.
  serve    PredictionServer.add_model with the train leg's state, warm-up,
           eight requests, answers equal to run_prediction's for the same
           samples (in-process, no sockets).
  mesh     only when jax.device_count() > 1: the train leg with
           auto-parallel on — shards on every device, one step's loss equal
           to the sequential per-device reference, collectives = the gradient
           all-reduce, no Mosaic call under the mesh.

Single-chip legs pin one device with HYDRAGNN_AUTO_PARALLEL=0, so the script
answers the same on a one-chip and a four-chip machine. The last line of
stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
Depth is cut (epochs, sample counts); widths are the shipped ones; weights
and data come from --seed.
"""

from __future__ import annotations

import argparse
import contextlib
import copy
import importlib.util
import io
import json
import os
import re
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

N_MOLECULES = 640   # -> 512 train = 8 steps/epoch at the shipped batch 64
QM9_EPOCHS = 4
LJ_CONFIGS = 96     # -> 76 train = 5 steps/epoch at the shipped batch 16
LJ_EPOCHS = 3
MD_ATOMS = 4096     # cell-list kernel: liquid-density box, 5 A cutoff
KERNEL_DTYPES = ("float32", "bfloat16")
# relative-to-scale tolerance per compute dtype (bf16: 8 mantissa bits, a
# few roundings deep)
TOL = {"float32": 1e-4, "bfloat16": 2e-2}


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class _Tee(io.TextIOBase):
    """stdout that also keeps what passed through (the epoch loop reports
    its losses the way a user reads them: on stdout)."""

    def __init__(self, stream):
        self.stream, self.kept = stream, io.StringIO()

    def write(self, s):
        self.kept.write(s)
        return self.stream.write(s)

    def flush(self):
        self.stream.flush()


def run_training_logged(config, samples):
    """``hydragnn_tpu.run_training`` plus the per-epoch train losses it
    printed: ``(state, model, augmented config, losses, stdout text)``."""
    import hydragnn_tpu

    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        state, model, aug = hydragnn_tpu.run_training(
            copy.deepcopy(config), samples=samples)
    text = tee.kept.getvalue()
    losses = [float(x) for x in re.findall(
        r"Train Loss: ([-+.\deE]+|nan|inf)", text)]
    return state, model, aug, losses, text


def _load_example(relpath: str, name: str):
    spec = importlib.util.spec_from_file_location(name, os.path.join(HERE, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mosaic_calls(compiled) -> int:
    return len(re.findall(r'custom_call_target="tpu_custom_call"', compiled.as_text()))


def _collectives(compiled) -> dict:
    text = compiled.as_text()
    return {
        op: len(re.findall(rf"= \S+ {op}(?:-start)?\(", text))
        for op in ("all-reduce", "all-gather", "all-to-all",
                   "collective-permute", "reduce-scatter")
    }


def _rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"shape {got.shape} != reference {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite kernel output")
    return float(np.abs(got - want).max() / max(1.0, float(np.abs(want).max())))


class Smoke:
    def __init__(self, seed: int):
        self.seed = seed
        self.results: dict[str, tuple[bool, float]] = {}
        self.compile_s = 0.0
        self.cache_hits = 0
        self.routing: dict[str, dict[str, str]] = {}

    # -- harness ------------------------------------------------------------
    def run_leg(self, name: str, fn, needs: tuple[str, ...] = ()) -> None:
        missing = [n for n in needs if not self.results.get(n, (False,))[0]]
        t0 = time.perf_counter()
        if missing:
            print(f"[{name}] FAIL: needs the {', '.join(missing)} leg(s)", flush=True)
            self.results[name] = (False, 0.0)
            return
        print(f"[{name}] ...", flush=True)
        try:
            fn()
            ok = True
        except Exception:
            traceback.print_exc()
            ok = False
        dt = time.perf_counter() - t0
        self.results[name] = (ok, dt)
        print(f"[{name}] {'PASS' if ok else 'FAIL'} {dt:.1f}s", flush=True)

    # -- device ---------------------------------------------------------------
    def leg_device(self) -> None:
        import importlib.metadata as md

        import jax
        import jaxlib

        dev = jax.devices()[0]
        self.device = {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        }
        print(f"  platform={dev.platform} device_kind={dev.device_kind!r} "
              f"count={len(jax.devices())}")
        print(f"  jax={jax.__version__} jaxlib={jaxlib.__version__} "
              f"libtpu={md.version('libtpu')} python={sys.version.split()[0]}")
        check(dev.platform == "tpu", f"device platform is {dev.platform!r}")

        from hydragnn_tpu import native
        from hydragnn_tpu.utils.compile_cache import enable_compile_cache

        placed = "JAX_COMPILATION_CACHE_DIR" if os.environ.get(
            "JAX_COMPILATION_CACHE_DIR") else "<checkout>/.jax_cache"
        print(f"  compile cache: {enable_compile_cache()} ({placed})")
        built = native.rebuild()
        print("  native libraries (rebuilt from tracked .cpp): "
              + ", ".join(f"{k}={v}" for k, v in built.items()))

    # -- shared set-up ----------------------------------------------------------
    def qm9(self):
        """(config as shipped with depth cut, samples) — built once."""
        if not hasattr(self, "_qm9"):
            example = _load_example("examples/qm9/qm9.py", "qm9_example")
            with open(os.path.join(HERE, "examples/qm9/qm9.json")) as f:
                config = json.load(f)
            # depth cuts only: epochs, and no end-of-run plots
            config["NeuralNetwork"]["Training"]["num_epoch"] = QM9_EPOCHS
            config["Visualization"]["create_plots"] = False
            samples = example.synthetic_molecules(N_MOLECULES, seed=self.seed)
            self._qm9 = (config, samples)
        return self._qm9

    def qm9_loaders(self):
        from hydragnn_tpu.preprocess.load_data import dataset_loading_and_splitting

        config, samples = self.qm9()
        return dataset_loading_and_splitting(copy.deepcopy(config), samples=samples)

    # -- kernels ------------------------------------------------------------------
    def _cell(self, kernel: str, dtype: str, route, fused, reference, args,
              diff: tuple[int, ...], keep=None) -> None:
        """One routing-table cell: compile ``fused`` (interpret=False), hold
        the program to ``route``, run it, compare fwd and VJP with
        ``reference`` evaluated in fp32 on the same values."""
        import jax
        import jax.numpy as jnp

        from hydragnn_tpu.ops import routing

        self.routing.setdefault(kernel, {})[dtype] = routing.describe(route)
        compiled = jax.jit(fused).lower(*args).compile()
        n_calls = _mosaic_calls(compiled)
        check((n_calls > 0) == (route is None),
              f"{kernel}[{dtype}]: routing table says "
              f"{routing.describe(route)!r} but the compiled program holds "
              f"{n_calls} Mosaic call(s)")
        f32 = lambda x: (x.astype(jnp.float32)
                         if jnp.issubdtype(x.dtype, jnp.floating) else x)
        args32 = tuple(f32(a) for a in args)
        sel = (lambda x: x) if keep is None else (lambda x: x * keep)
        out = compiled(*args)
        want = jax.jit(reference)(*args32)
        err = _rel_err(sel(f32(out)), sel(want))
        check(err <= TOL[dtype], f"{kernel}[{dtype}] fwd: rel err {err:.2e}")
        # VJP against a fixed random cotangent
        g = jax.random.normal(jax.random.PRNGKey(self.seed + 17), want.shape)
        g = sel(g)
        loss = lambda fn: (lambda *a: (f32(fn(*a)) * g).sum())
        got_g = jax.jit(jax.grad(loss(fused), argnums=diff))(*args)
        want_g = jax.jit(jax.grad(loss(reference), argnums=diff))(*args32)
        errs = [_rel_err(a, b) for a, b in zip(got_g, want_g)]
        check(max(errs) <= TOL[dtype],
              f"{kernel}[{dtype}] vjp: rel err {max(errs):.2e}")
        print(f"  {kernel:<28}{dtype:<9} {self.routing[kernel][dtype]:<10} "
              f"calls={n_calls} fwd={err:.1e} vjp={max(errs):.1e}")

    def leg_kernels(self) -> None:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from hydragnn_tpu import md
        from hydragnn_tpu.ops import fused_cell_list as fcl
        from hydragnn_tpu.ops import fused_scatter as fs
        from hydragnn_tpu.ops import fused_softmax as fsm
        from hydragnn_tpu.ops import routing

        config, _ = self.qm9()
        arch = config["NeuralNetwork"]["Architecture"]
        batch = next(iter(self.qm9_loaders()[0]))
        meta = batch.meta
        n, e, g = batch.num_nodes, batch.num_edges, batch.num_graphs
        hidden = int(arch["hidden_dim"])
        print(f"  train-leg batch: {n} nodes, {e} edges, {g} graphs, "
              f"hidden {hidden}; {meta}")
        check(meta is not None and meta.gs_fits and meta.recv_fits,
              f"collate did not certify the kernel layouts: {meta}")
        snd, rcv = jnp.asarray(batch.senders), jnp.asarray(batch.receivers)
        emask = jnp.asarray(batch.edge_mask)
        key = jax.random.split(jax.random.PRNGKey(self.seed), 8)
        # GAT's attention ids: receivers + alignment pad + one self-loop each
        att_ids = jnp.concatenate([
            rcv, jnp.full(fsm.self_loop_pad(e), n - 1, jnp.int32),
            jnp.arange(n, dtype=jnp.int32),
        ])
        att_keep = (att_ids != n - 1).astype(jnp.float32)[:, None]
        n_max = int(meta.max_n_node)
        valid = (jnp.arange(n_max)[None, :]
                 < jnp.asarray(batch.n_node)[:, None])[:, None, None, :]

        for dtype in KERNEL_DTYPES:
            dt = jnp.dtype(dtype)
            # gather -> scale -> scatter, at the first conv layer's width
            # (the raw feature column) and at the hidden width
            for c in (batch.x.shape[1], hidden):
                h = jax.random.normal(key[0], (n, c)).astype(dt)
                w = (jax.random.uniform(key[1], (e,)) * emask).astype(dt)
                self._cell(
                    f"fused_gather_scatter[C={c}]", dtype,
                    fs.scatter_route(h, e, n, fs.GS_CERT_WINDOW),
                    lambda h, w: fs.fused_gather_scatter(
                        h, snd, rcv, n, w, fits=meta.gs_fits, interpret=False),
                    lambda h, w: fs.reference_gather_scatter(h, snd, rcv, n, w),
                    (h, w), diff=(0, 1),
                )
            data = (jax.random.normal(key[2], (e, hidden))
                    * emask[:, None]).astype(dt)
            self._cell(
                "fused_segment_sum", dtype,
                fs.scatter_route(data, e, n, 128),
                lambda d: fs.fused_segment_sum(d, rcv, n, fits=meta.recv_fits),
                lambda d: jax.ops.segment_sum(d, rcv, num_segments=n),
                (data,), diff=(0,),
            )
            logits = jax.random.normal(key[3], (att_ids.shape[0], 6)).astype(dt)
            self._cell(
                "fused_segment_softmax", dtype,
                fsm.segment_softmax_route(logits, n),
                lambda x: fsm.fused_segment_softmax(
                    x, att_ids, n, fits=meta.attn_fits, interpret=False),
                lambda x: fsm.reference_segment_softmax(x, att_ids, n),
                (logits,), diff=(0,), keep=att_keep,
            )
            dense = jax.random.normal(key[4], (g, 4, n_max, n_max)).astype(dt)
            self._cell(
                "fused_masked_softmax", dtype,
                fsm.masked_softmax_route(dense),
                lambda x: fsm.fused_masked_softmax(x, valid, interpret=False),
                lambda x: jax.nn.softmax(jnp.where(valid, x, -1e9), axis=-1),
                (dense,), diff=(0,),
            )

        self._tensor_product_cells(key[6])
        self._row_sum_cells(key[7])

        # MD neighbour build: integer outputs, no VJP, positions are fp32
        box = (MD_ATOMS / 0.033) ** (1.0 / 3.0)  # ~liquid argon density
        cutoff, cell = 5.0, np.eye(3, dtype=np.float32) * box
        pbc = np.ones(3, bool)
        grid, cap = md.plan_cell_grid(cell, cutoff, MD_ATOMS)
        window = fcl.cell_window(cap)
        n_cells = int(np.prod(grid))
        route = fcl.cell_list_route(MD_ATOMS, n_cells, window)
        self.routing["fused_binned_radius_graph"] = {
            "float32": routing.describe(route),
            "bfloat16": "n/a (positions are fp32)",
        }
        pos = jax.random.uniform(key[5], (MD_ATOMS, 3)) * box
        max_edges = 96 * MD_ATOMS
        build = lambda fused: jax.jit(lambda p: md.binned_radius_graph(
            p, cutoff, max_edges, jnp.asarray(cell), jnp.asarray(pbc), grid,
            cap, fused=fused))
        compiled = build(True).lower(pos).compile()
        n_calls = _mosaic_calls(compiled)
        check((n_calls > 0) == (route is None),
              f"fused_binned_radius_graph: table says "
              f"{routing.describe(route)!r}, program holds {n_calls} Mosaic call(s)")
        got, want = compiled(pos), build(False)(pos)

        def edge_set(out):
            s, r, _, mask, n_edges = (np.asarray(x) for x in out)
            check(int(n_edges) <= max_edges, f"edge overflow: {int(n_edges)}")
            real = mask > 0
            return set(zip(s[real].tolist(), r[real].tolist())), int(n_edges)

        (es_got, ne_got), (es_want, ne_want) = edge_set(got), edge_set(want)
        check(ne_got == ne_want and es_got == es_want,
              f"cell-list edge sets differ: {ne_got} vs {ne_want} edges, "
              f"{len(es_got ^ es_want)} in the symmetric difference")
        print(f"  {'fused_binned_radius_graph':<28}{'float32':<9} "
              f"{routing.describe(route):<10} calls={n_calls} "
              f"{MD_ATOMS} atoms, grid {grid}, window {window}: "
              f"{ne_got} edges = XLA build's edge set")

        print("  routing table:")
        for kernel, row in self.routing.items():
            print(f"    {kernel:<28} " + "  ".join(
                f"{d}={row[d]}" for d in KERNEL_DTYPES))

    def _tensor_product_cells(self, key) -> None:
        """MACE's fused tensor product, both layers' path sets at 128
        channels and the worst-case bucket of ``mace_mlip_mptrj.fill`` (two
        444-atom structures of 64 neighbours an atom, then padded slots at
        the dummy node): forward and VJP against the slab-building XLA path."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from hydragnn_tpu.models import mace
        from hydragnn_tpu.models.harmonics import coupling_paths
        from hydragnn_tpu.ops import fused_tensor_product as ftp
        from hydragnn_tpu.ops import routing

        n, e, real, c = 896, 56960, 444 * 64, 128
        rcv = np.full(e, n - 1, np.int32)
        rcv[:real] = np.repeat(np.arange(real // 64), 64)
        rcv, live = jnp.asarray(rcv), jnp.asarray(np.arange(e) < real, jnp.float32)
        for l_in in (0, 1):
            paths = tuple(coupling_paths(l_in, 3, 3))
            plan = mace.couplings(paths, (l_in + 1) ** 2, 16, c)[1]
            k = jax.random.split(jax.random.fold_in(key, l_in), 3)
            hs = jax.random.normal(k[0], (e, plan.m_in * c))
            kt = jax.random.normal(k[1], (plan.n_k, e)) * live
            rt = jax.random.normal(k[2], (plan.n_paths * c, e)) * live
            name = f"fused_tensor_product[S={plan.slab}]"
            self._cell(
                name, "float32", ftp.tensor_product_route(plan, e, n, jnp.float32, False),
                lambda hs, kt, rt: ftp.fused_tensor_product(
                    plan, rcv, hs, kt, rt, n, interpret=False),
                lambda hs, kt, rt: ftp.reference_tensor_product(plan, rcv, hs, kt, rt, n),
                (hs, kt, rt), diff=(0, 1, 2))
            # the model takes its XLA path there; nothing to compile or compare
            self.routing[name]["bfloat16"] = routing.describe(
                ftp.tensor_product_route(plan, e, n, jnp.bfloat16, False))

    def _row_sum_cells(self, key) -> None:
        """``fused_segment_sum`` past the resident budget (the tiled form) at
        ``painn_mlip_md17.fill``'s shape: 1,024 molecules of 21 atoms and 318
        edges, senders unsorted inside a molecule, receivers sorted, 128 pad
        slots at the dummy node. Forward, VJP and the gradient of a force loss
        through ONE ``segment.gather`` / ``segment.segment_sum`` pair against
        plain indexing and XLA's sum; every derivative is the kernel again."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from hydragnn_tpu.graphs import segment
        from hydragnn_tpu.ops import fused_scatter as fs
        from hydragnn_tpu.ops import routing

        n, e, atoms, edges = 21512, 325760, 21, 318
        rng = np.random.default_rng(self.seed)
        first = np.repeat(np.arange(1024) * atoms, edges)
        pad = np.full(e - first.size, n - 1)
        rcv = np.concatenate([first + np.sort(
            rng.integers(0, atoms, (1024, edges)), axis=1).ravel(), pad])
        snd = np.concatenate([first + rng.integers(0, atoms, first.size), pad])
        rcv, snd = jnp.asarray(rcv, jnp.int32), jnp.asarray(snd, jnp.int32)
        live = (jnp.arange(e) < first.size).astype(jnp.float32)[:, None]
        for c in (384, 128):
            k = jax.random.split(jax.random.fold_in(key, c), 2)
            x = jax.random.normal(k[0], (n, c))
            w = jax.random.normal(k[1], (e, c)) * live
            name = f"fused_segment_sum[tiled,C={c}]"
            route = fs.scatter_route(w, e, n, 128, tiled=True)
            check(fs.scatter_route(w, e, n, 128) is not None,
                  f"{name}: the resident form admits N {n}; this cell is the tiled form's")
            pair = lambda x, w: segment.segment_sum(segment.gather(x, rcv) * w, snd, n)
            plain = lambda x, w: jax.ops.segment_sum(x[rcv] * w, snd, num_segments=n)
            self._cell(name, "float32", route, pair, plain, (x, w), diff=(0, 1))

            def force_loss_grad(fn):
                energy = lambda x, w: jnp.sum(jnp.tanh(fn(x, w)))
                loss = lambda x, w: jnp.sum(jax.grad(energy)(x, w) ** 2)
                return jax.jit(jax.grad(loss, argnums=(0, 1)))

            compiled = force_loss_grad(pair).lower(x, w).compile()
            calls = _mosaic_calls(compiled)
            check(calls >= 3, f"{name}: the force-loss gradient holds {calls} Mosaic call(s)")
            errs = [_rel_err(a, b) for a, b in zip(compiled(x, w), force_loss_grad(plain)(x, w))]
            check(max(errs) <= TOL["float32"], f"{name} grad of grad: rel err {max(errs):.2e}")
            print(f"  {name:<28}{'float32':<9} grad-of-grad calls={calls} err={max(errs):.1e}")
            self.routing[name]["bfloat16"] = routing.describe(
                fs.scatter_route(w.astype(jnp.bfloat16), e, n, 128, tiled=True))

        # SchNet's gather-multiply-sum with no layout certificate, at the
        # worst-case bucket of ``schnet_mlip_oc20.fill`` (20 structures of 225
        # atoms and 50 neighbours an atom, 256 filters): under the RESIDENT
        # budget, and still the pair on the tiled sum (``gather_scatter_route``)
        n, e, atoms, c = 4504, 225024, 225, 256
        first = np.repeat(np.arange(20 * atoms), 50)
        pad = np.full(e - first.size, n - 1)
        rcv = jnp.asarray(np.concatenate([first, pad]), jnp.int32)
        snd = jnp.asarray(np.concatenate(
            [first // atoms * atoms + rng.integers(0, atoms, first.size), pad]), jnp.int32)
        live = (jnp.arange(e) < first.size).astype(jnp.float32)[:, None]
        k = jax.random.split(jax.random.fold_in(key, c), 2)
        x = jax.random.normal(k[0], (n, c))
        w = jax.random.normal(k[1], (e, c)) * live
        name = f"gather_scatter_sum[pair,C={c}]"
        check(fs.scatter_route(x, e, n, fs.GS_CERT_WINDOW) is None
              and fs.gather_scatter_route(x, e, n, True) is not None,
              f"{name}: the resident kernel admits N {n} and the pair is still the route")
        self._cell(name, "float32", fs.scatter_route(w, e, n, 128, tiled=True),
                   lambda x, w: fs.gather_scatter_sum(x, snd, rcv, n, w),
                   lambda x, w: fs.reference_gather_scatter(x, snd, rcv, n, w),
                   (x, w), diff=(0, 1))
        self.routing[name]["bfloat16"] = self.routing[name]["float32"]

    # -- train ----------------------------------------------------------------------
    def leg_train(self) -> None:
        import jax
        import jax.numpy as jnp
        import numpy as np

        import hydragnn_tpu
        from hydragnn_tpu.train.optimizer import select_optimizer
        from hydragnn_tpu.train.step import (
            make_train_step,
            resolve_training_precision,
        )

        config, samples = self.qm9()
        state, model, aug, losses, _ = run_training_logged(config, samples)
        check(len(losses) == QM9_EPOCHS, f"expected {QM9_EPOCHS} epochs, saw {losses}")
        check(all(np.isfinite(losses)), f"non-finite train loss: {losses}")
        check(losses[-1] < losses[0], f"loss did not fall: {losses}")
        err, _, trues, preds = hydragnn_tpu.run_prediction(
            copy.deepcopy(config), state, model, samples=samples)
        check(np.isfinite(err) and all(np.isfinite(p).all() for p in preds),
              "non-finite prediction")
        print(f"  train loss {losses[0]:.4f} -> {losses[-1]:.4f} over "
              f"{QM9_EPOCHS} epochs; test error {err:.4f} on {len(preds[0])} graphs")
        self.trained = (state, model, aug, preds)

        # the step program the loop ran, held to the routing table: GIN's
        # gather-scatter runs once per conv layer forward and once per layer
        # backward except the first (the raw features take no gradient);
        # pooling is a segment_sum over graphs
        training = aug["NeuralNetwork"]["Training"]
        arch = aug["NeuralNetwork"]["Architecture"]
        dtype = jnp.dtype(resolve_training_precision(training)).name
        batch = jax.tree.map(jnp.asarray, next(iter(self.qm9_loaders()[0])))
        step = make_train_step(
            model, select_optimizer(training["Optimizer"]),
            resolve_training_precision(training))
        t0 = time.perf_counter()
        compiled = step.lower(state, batch).compile()
        self.step_compile_s = time.perf_counter() - t0
        layers, hidden = int(arch["num_conv_layers"]), int(arch["hidden_dim"])
        on = lambda kernel: self.routing[kernel][dtype] == "mosaic"
        first = on(f"fused_gather_scatter[C={batch.x.shape[1]}]")
        rest = on(f"fused_gather_scatter[C={hidden}]")
        expected = first + (layers - 1) * 2 * rest
        from hydragnn_tpu.ops.fused_scatter import scatter_route

        pooled = jax.ShapeDtypeStruct((batch.num_nodes, hidden), jnp.dtype(dtype))
        pool_route = scatter_route(pooled, batch.num_nodes, batch.num_graphs, 128)
        expected += pool_route is None  # forward only: its VJP is an XLA gather
        n_calls = _mosaic_calls(compiled)
        print(f"  compiled {dtype} train step: {n_calls} Mosaic calls "
              f"(routing table promises {expected}; pooling over "
              f"{batch.num_graphs} graphs -> "
              f"{'mosaic' if pool_route is None else 'xla: ' + pool_route}); "
              f"lower+compile {self.step_compile_s:.2f}s")
        check(n_calls == expected,
              f"train step holds {n_calls} Mosaic calls, table promises {expected}")

    # -- mlip -------------------------------------------------------------------------
    def leg_mlip(self) -> None:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from hydragnn_tpu.datasets import lennard_jones_data
        from hydragnn_tpu.graphs.batching import GraphLoader
        from hydragnn_tpu.models.mlip import make_mlip_eval_step

        example = _load_example("examples/LennardJones/LennardJones.py", "lj_example")
        config = copy.deepcopy(example.CONFIG)
        config["NeuralNetwork"]["Training"]["num_epoch"] = LJ_EPOCHS  # depth cut
        samples = lennard_jones_data(
            number_configurations=LJ_CONFIGS, cells_per_dim=2, seed=self.seed)
        energies = np.array([s.energy_y[0] for s in samples])
        e_mean, e_std = energies.mean(), energies.std() + 1e-9
        for s in samples:  # the example's own normalisation
            s.energy_y = (s.energy_y - e_mean) / e_std
            s.forces_y = s.forces_y / e_std
        state, model, _, losses, _ = run_training_logged(config, samples)
        check(len(losses) == LJ_EPOCHS and all(np.isfinite(losses)),
              f"MLIP train losses: {losses}")
        batch_size = config["NeuralNetwork"]["Training"]["batch_size"]
        batch = next(iter(GraphLoader(samples, batch_size)))
        metrics = make_mlip_eval_step(model)(state, jax.tree.map(jnp.asarray, batch))
        e_loss, _, f_loss = (float(x) for x in metrics["tasks_loss"])
        check(np.isfinite(e_loss) and np.isfinite(f_loss),
              f"non-finite MLIP losses: energy {e_loss}, force {f_loss}")
        print(f"  EGNN energy+force: train loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
              f"eval energy loss {e_loss:.4f}, force loss {f_loss:.4f}")

    # -- serve --------------------------------------------------------------------------
    def leg_serve(self) -> None:
        import numpy as np

        from hydragnn_tpu.serve.server import PredictionServer, ServingConfig

        state, model, aug, preds = self.trained
        test_loader = self.qm9_loaders()[2]
        chunk, _ = test_loader.batch_plan()[0]
        ids = [int(i) for i in chunk[:8]]
        requests = [test_loader.samples[i] for i in ids]
        server = PredictionServer(ServingConfig())
        server.add_model("qm9", model, state, aug, samples=test_loader.samples)
        report = server.warmup(verify=True)
        server.start()
        try:
            answers = server.predict("qm9", requests, timeout=120.0)
        finally:
            server.stop()
        got = np.array([np.asarray(heads[0]).reshape(-1) for heads in answers])
        want = np.asarray(preds[0][: len(ids)]).reshape(len(ids), -1)
        # the batch evaluator and the server run the same predict core; the
        # server pads to its own buckets and keeps the XLA scatter path, so
        # equality holds to the compute dtype's precision
        err = _rel_err(got, want)
        print(f"  warm-up {report['total_s']:.1f}s; 8 requests answered, "
              f"max rel diff vs run_prediction {err:.2e}")
        check(err <= TOL["bfloat16"], f"served answers differ: rel err {err:.2e}")

    # -- mesh ------------------------------------------------------------------------------
    def leg_mesh(self) -> None:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from __graft_entry__ import _assert_close, _sequential_loss
        from hydragnn_tpu.parallel import make_mesh, shard_state
        from hydragnn_tpu.parallel.step import (
            make_parallel_train_step,
            put_batch,
            stack_device_batches,
        )
        from hydragnn_tpu.train.optimizer import select_optimizer
        from hydragnn_tpu.train.step import (
            create_train_state,
            resolve_training_precision,
        )

        n_dev = jax.device_count()
        config, samples = self.qm9()
        # the train leg again, through the normal entry point, auto-parallel on
        os.environ["HYDRAGNN_AUTO_PARALLEL"] = "1"
        try:
            *_, losses, out = run_training_logged(config, samples)
        finally:
            os.environ["HYDRAGNN_AUTO_PARALLEL"] = "0"
        check(f"auto-parallel: {n_dev}-device data mesh" in out,
              "run_training did not build the data mesh")
        check(len(losses) == QM9_EPOCHS and all(np.isfinite(losses))
              and losses[-1] < losses[0], f"mesh train losses: {losses}")

        # one step, taken apart
        _, model, aug = self.trained[:3]
        training = aug["NeuralNetwork"]["Training"]
        optimizer = select_optimizer(training["Optimizer"])
        loader = self.qm9_loaders()[0]
        loader.set_group(n_dev)  # one bucket per stacked device group
        batches = [b for _, b in zip(range(n_dev), loader)]
        check(len(batches) == n_dev, "not enough train batches for one mesh step")
        mesh = make_mesh()
        check(mesh.devices.size == n_dev, f"mesh has {mesh.devices.size} devices")
        state = create_train_state(
            model, optimizer, jax.tree.map(jnp.asarray, batches[0]),
            rng=jax.random.PRNGKey(self.seed))
        want = _sequential_loss(model, state, batches)
        # place from HOST copies: the steps donate their state, and a
        # device_put of a device array may alias it as one of the shards
        state = jax.device_get(state)
        sharded = shard_state(state, mesh)
        stacked = put_batch(stack_device_batches(batches), mesh)
        all_devices = set(jax.devices())
        for leaf in jax.tree.leaves(sharded.params):
            check({s.device for s in leaf.addressable_shards} == all_devices,
                  "a parameter is not resident on every device")
        for leaf in jax.tree.leaves(stacked):
            shards = leaf.addressable_shards
            check({s.device for s in shards} == all_devices
                  and all(s.data.shape[0] == 1 for s in shards),
                  "a batch array is not split one slice per device")
        # loss parity in fp32, as the CPU dry run checks it
        step32 = make_parallel_train_step(model, optimizer, mesh, jnp.float32)
        _, metrics = step32(sharded, stacked)
        got = float(metrics["loss"])
        _assert_close("mesh", got, want)
        # the step as configured: what the compiler made of it
        sharded = shard_state(state, mesh)
        step = make_parallel_train_step(
            model, optimizer, mesh, resolve_training_precision(training))
        compiled = step.lower(sharded, stacked).compile()
        coll, n_calls = _collectives(compiled), _mosaic_calls(compiled)
        print(f"  {n_dev}-device mesh: params and batch shards on every device; "
              f"loss {got:.6f} vs sequential reference {want:.6f}; "
              f"collectives {coll}; Mosaic calls {n_calls}")
        check(coll["all-reduce"] >= 1, "no gradient all-reduce in the mesh step")
        check(not any(coll[op] for op in coll if op != "all-reduce"),
              f"unexpected collectives in the mesh step: {coll}")
        check(n_calls == 0, f"{n_calls} Mosaic call(s) under the GSPMD mesh")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    os.environ["HYDRAGNN_AUTO_PARALLEL"] = "0"  # single-chip legs pin one device
    t_start = time.perf_counter()
    import jax

    if jax.default_backend() != "tpu":
        print(f"[device] FAIL: jax.default_backend() is {jax.default_backend()!r}, "
              "not 'tpu' — chip_smoke.py has no CPU path", file=sys.stderr)
        return 1

    smoke = Smoke(args.seed)

    def on_duration(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            smoke.compile_s += secs

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            smoke.cache_hits += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    # run_training writes ./logs/<run>: keep that out of the checkout
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    os.chdir(workdir)

    smoke.run_leg("device", smoke.leg_device)
    smoke.run_leg("kernels", smoke.leg_kernels, needs=("device",))
    smoke.run_leg("train", smoke.leg_train, needs=("device", "kernels"))
    smoke.run_leg("mlip", smoke.leg_mlip, needs=("device",))
    smoke.run_leg("serve", smoke.leg_serve, needs=("train",))
    if jax.device_count() > 1:
        smoke.run_leg("mesh", smoke.leg_mesh, needs=("train",))
    else:
        print("[mesh] not run: one device")

    total = time.perf_counter() - t_start
    print("legs: " + "  ".join(
        f"{name}={'PASS' if ok else 'FAIL'}({dt:.1f}s)"
        for name, (ok, dt) in smoke.results.items()))
    print(f"total {total:.1f}s; backend compile time {smoke.compile_s:.1f}s; "
          f"persistent-cache hits {smoke.cache_hits}")
    if not all(ok for ok, _ in smoke.results.values()):
        return 1
    print(json.dumps({"ok": True, "device": smoke.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
