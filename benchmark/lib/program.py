"""The system under test, built the way ``hydragnn_tpu.run_training`` builds it.

This is the one module of the benchmark that imports the program. It takes
from it only the entry points that ``run_training`` itself calls and the two
sources the benchmark reads (``utils.tracer`` timers, ``analysis.sentinel``
counts):

    utils.compile_cache.enable_compile_cache
    preprocess.load_data.dataset_loading_and_splitting
    config.update_config
    models.create.create_model_config
    train.optimizer.select_optimizer
    train.step.TrainState / resolve_training_precision / resolve_loss_scale
    graphs.batching.PrefetchLoader
    models.mlip.make_mlip_train_step        (what train_validate_test chooses
      for an interatomic potential on one device; a cell of another kind
      brings its branch with it)
    resilience.Resilience / wrap_step_with_guard
    train.loop.train_epoch                   (the window drives this)

What the benchmark adds is measurement only: a wrapper round the step call
(host time inside it, time of each return and, from a watcher thread, of each
step's end on the device, a profiler annotation, a copy of the state after
each of the first steps) and a wrapper round the loader
(a profiler annotation round ``__next__``; a count of real and padded slots
taken where the loader collates, on the host).
"""

from __future__ import annotations

import copy
import queue
import threading
import time

import numpy as np

PROGRAM_KEYS = ("Verbosity", "Dataset", "NeuralNetwork", "Visualization")


def to_samples(graphs: list[dict], input_scale: float):
    from hydragnn_tpu.graphs.graph import GraphSample

    samples = []
    for g in graphs:
        periodic = g["cell"] is not None
        samples.append(GraphSample(
            x=(g["z"].astype(np.float32) * input_scale)[:, None],
            pos=g["pos"], senders=g["senders"], receivers=g["receivers"],
            edge_shifts=g["shifts"], energy_y=np.array([g["energy"]], np.float32),
            forces_y=g["forces"], cell=g["cell"] if periodic else None,
            pbc=np.array([periodic] * 3), extras={"corpus_index": len(samples)},
        ))
    return samples


class StepProbe:
    """Wrapper round the step handed to ``train_epoch``.

    Two stamps a step: ``returns``, when the asynchronous step call came back
    to the loop, and ``finishes``, when the step's work ended on the device.
    The second is taken by a watcher thread that waits on each step's loss
    (an output the next call does not donate) in the order of the calls;
    the wait releases the GIL, and the loop is never held by it. ``join()``
    ends the watcher once every queued loss is stamped."""

    def __init__(self, step):
        self.step = step
        self.capture = 0          # copy the state after this many first calls
        self.captured = []        # [(params, opt_state, batch_stats, loss)]
        self._copy = None
        self._pending = queue.SimpleQueue()
        self._watcher = None
        self.clear()

    def clear(self):
        self.join()
        self.returns, self.finishes, self.dispatch_s, self.losses = [], [], [], []

    def join(self):
        """Wait until every step called so far has its finish stamp."""
        if self._watcher is not None:
            self._pending.put(None)
            self._watcher.join()
            self._watcher = None

    def _watch(self):
        while (loss := self._pending.get()) is not None:
            loss.block_until_ready()
            self.finishes.append(time.perf_counter())

    def __call__(self, state, batch):
        import jax
        import jax.numpy as jnp

        if self._watcher is None:
            self._watcher = threading.Thread(target=self._watch, name="bench_finish", daemon=True)
            self._watcher.start()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench_dispatch"):
            new_state, metrics = self.step(state, batch)
        t1 = time.perf_counter()
        self.dispatch_s.append(t1 - t0)
        self.returns.append(t1)
        self.losses.append(metrics["loss"])
        self._pending.put(metrics["loss"])
        if len(self.captured) < self.capture:
            # the next call donates new_state: keep copies, made on the device
            if self._copy is None:
                self._copy = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))
            self.captured.append(self._copy(
                (new_state.params, new_state.opt_state, new_state.batch_stats))
                + (metrics["loss"],))
        return new_state, metrics

    # train_epoch's one-shot cost probe lowers the step it is given
    def __getattr__(self, name):
        return getattr(self.step, name)


class Feed:
    """Wrapper round the loader handed to ``train_epoch``."""

    def __init__(self, loader, inner):
        self.loader, self.inner = loader, inner
        self.limit = None         # stop an epoch after this many batches
        self.samples = getattr(loader, "samples", [])
        self.pad = getattr(loader, "pad", None)
        sizes = np.array([(s.num_nodes, s.num_edges) for s in inner.samples], np.int64)
        self.collated = []        # (pad tuple, real nodes, real edges, real graphs, meta)
        collate_chunk = inner.collate_chunk

        def counted(chunk, pad):
            batch = collate_chunk(chunk, pad)
            tot = sizes[np.asarray(chunk)].sum(axis=0)
            self.collated.append(
                (pad.as_tuple(), int(tot[0]), int(tot[1]), len(chunk), batch.meta))
            return batch

        inner.collate_chunk = counted

    def __len__(self):
        n = len(self.loader)
        return n if self.limit is None else min(n, self.limit)

    def set_epoch(self, epoch):
        self.loader.set_epoch(epoch)

    def __iter__(self):
        import jax

        it = iter(self.loader)
        done = 0
        try:
            while self.limit is None or done < self.limit:
                with jax.profiler.TraceAnnotation("bench_dataload"):
                    batch = next(it, None)
                if batch is None:
                    return
                yield batch
                done += 1
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                close()


class Program:
    """Model, optimizer, loaders, state and step of one cell."""

    def __init__(self, config: dict, traffic: dict, graphs: list[dict],
                 make_weights, log=lambda msg: None):
        import jax
        import jax.numpy as jnp

        from hydragnn_tpu.config import update_config
        from hydragnn_tpu.graphs.batching import PrefetchLoader
        from hydragnn_tpu.models.create import create_model_config
        from hydragnn_tpu.preprocess.load_data import dataset_loading_and_splitting
        from hydragnn_tpu.resilience import Resilience
        from hydragnn_tpu.train.optimizer import select_optimizer
        from hydragnn_tpu.train.step import (
            TrainState, resolve_loss_scale, resolve_training_precision)
        from hydragnn_tpu.utils.compile_cache import enable_compile_cache

        self.cache_dir = enable_compile_cache()
        cfg = {k: copy.deepcopy(config[k]) for k in PROGRAM_KEYS if k in config}
        training = cfg["NeuralNetwork"]["Training"]
        training.update(traffic.get("training", {}))
        samples = to_samples(graphs, float(config["input_scale"]))
        train_loader, val_loader, test_loader = dataset_loading_and_splitting(
            cfg, samples=samples)
        cfg = update_config(cfg, train_loader.samples, val_loader.samples,
                            test_loader.samples)
        self.config = cfg
        log(f"loaders: {len(train_loader.samples)} train samples")
        training = cfg["NeuralNetwork"]["Training"]
        self.model = create_model_config(cfg)
        self.optimizer = select_optimizer(training["Optimizer"])
        self.inner_loader = train_loader
        self.corpus_index = np.array(
            [s.extras["corpus_index"] for s in train_loader.samples], np.int64)

        example = jax.tree.map(jnp.asarray, next(iter(train_loader)))
        init = lambda batch: self.model.init(jax.random.PRNGKey(0), batch, train=False)
        shapes = jax.eval_shape(init, example)
        log("shapes from eval_shape of the program's init")
        params = make_weights(shapes["params"])
        # batch statistics are the program's own initial values (zeros and
        # ones, nothing random: the reference is handed the same)
        stats = jax.jit(lambda batch: init(batch)["batch_stats"])(example) \
            if shapes.get("batch_stats") else {}
        # host copies: the step donates its state
        self.params0, self.stats0 = jax.device_get((params, stats))
        state = TrainState(params=params, batch_stats=stats,
                           opt_state=jax.jit(self.optimizer.init)(params),
                           step=jnp.zeros((), jnp.int32))
        jax.block_until_ready(state)
        log("weights and optimizer state made on the device, one jitted call each")

        precision = resolve_training_precision(training)
        loss_scale = resolve_loss_scale(training)
        if not self.model.spec.enable_interatomic_potential:
            raise NotImplementedError("only energy-and-force training has a cell so far")
        from hydragnn_tpu.models.mlip import make_mlip_train_step

        step = make_mlip_train_step(
            self.model, self.optimizer, compute_dtype=precision, loss_scale=loss_scale)
        self.resilience = Resilience.from_config(training)
        if self.resilience.guard_enabled:
            from hydragnn_tpu.resilience import wrap_step_with_guard

            step = wrap_step_with_guard(step)
        self.state = state
        self.step = StepProbe(step)

        depth = int(training.get("prefetch", 2))
        loader = train_loader
        if depth > 0:
            loader = PrefetchLoader(
                train_loader, depth=depth, device_put=True,
                workers=int(training.get("num_workers", 1)))
        self.feed = Feed(loader, train_loader)

    def epoch(self, epoch: int):
        """One pass of ``train_epoch`` over the feed; returns its mean loss."""
        from hydragnn_tpu.train.loop import train_epoch

        self.feed.limit = None
        self.feed.set_epoch(epoch)
        self.state, loss, _ = train_epoch(
            self.step, self.state, self.feed, 0, resilience=self.resilience)
        return loss

    def steps(self, entries):
        """One pass of ``train_epoch`` over these plan entries only, in this
        order, through the same prefetcher and the same step."""
        from hydragnn_tpu.train.loop import train_epoch

        inner = self.inner_loader
        inner.batch_plan = lambda: list(entries)  # shadows the method
        try:
            self.feed.limit = len(entries)
            self.state, _, _ = train_epoch(
                self.step, self.state, self.feed, 0, resilience=self.resilience)
        finally:
            del inner.batch_plan

    def release(self):
        """Free the program's device state (before the reference runs)."""
        self.step.join()
        self.state = None
        self.step.captured = []
        self.step.losses = []

    def plan(self, epoch: int):
        """The loader's (indices, pad) list for an epoch, without collating."""
        self.inner_loader.set_epoch(epoch)
        return self.inner_loader.batch_plan()

    def collate(self, chunk, pad):
        return self.inner_loader.collate_chunk(chunk, pad)
