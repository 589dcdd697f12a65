"""``run_training`` — the canonical entry point (reference
``hydragnn/run_training.py:59-211``).

Accepts a JSON config path or dict (the reference's singledispatch), plus an
optional in-memory dataset (list of ``GraphSample``). Returns the final
``TrainState`` together with the model and augmented config so callers
(tests, HPO drivers) can keep going without re-loading checkpoints.
"""

from __future__ import annotations

import os
from typing import Sequence

from .config import ModelSpec, get_log_name_config, load_config, save_config, update_config
from .models.create import create_model_config
from .preprocess.load_data import apply_variables_of_interest, dataset_loading_and_splitting
from .train.loop import train_validate_test
from .train.optimizer import select_optimizer
from .train.step import create_train_state, resolve_precision
from .utils import flags
from .utils import tracer as tr
from .utils.print_utils import print_distributed, setup_log


def run_training(config_source, samples: Sequence | None = None, rank: int = 0, world: int = 1):
    config = load_config(config_source)
    verbosity = config.get("Verbosity", {}).get("level", 0)
    training_cfg = config.get("NeuralNetwork", {}).get("Training", {})
    flags.warn_unknown()  # typo'd / subsumed HYDRAGNN_* vars warn, not vanish

    # persistent XLA compile cache: reruns skip the step compile
    # (utils/compile_cache.py has the placement rule)
    from .utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    # multi-host bootstrap (reference setup_ddp, distributed.py:151-280):
    # scheduler env cascade -> jax.distributed.initialize; no-op/idempotent in
    # single-process runs. Caller-supplied rank/world win if explicit. A
    # launcher that announces several processes and then fails to connect
    # them is an error: training on alone would be a different job.
    if world == 1:
        from .parallel.distributed import setup_ddp

        world, rank = setup_ddp(verbosity)

    # bucketed padding composes with the in-process mesh path: the epoch loop
    # registers its device-group size on the loaders (GraphLoader.set_group),
    # which coarsens the bucket choice to one shape per stacked group

    # elastic data plane: a ShardedStore passed as the dataset picks up the
    # Dataset.store config block (replication expectations, peer timeout,
    # quarantine/probe cadence) before any loader touches the network —
    # env flags (HYDRAGNN_REPLICATION, HYDRAGNN_PEER_TIMEOUT) still win
    store_cfg = config.get("Dataset", {}).get("store")
    if store_cfg and hasattr(samples, "apply_config"):
        samples.apply_config(store_cfg)

    # data loading + split (reference :90)
    train_loader, val_loader, test_loader = dataset_loading_and_splitting(
        config, samples=samples, rank=rank, world=world
    )

    # config augmentation from data (reference :92)
    config = update_config(config, train_loader.samples, val_loader.samples, test_loader.samples)

    log_name = get_log_name_config(config)
    setup_log(log_name)
    try:
        save_config(config, log_name)
    except OSError:
        pass

    # unified telemetry plane: the validated Telemetry block (env flags
    # folded in by apply_env) arms the registry/journal/trace process-wide;
    # the journal opens next to the run's logs so every subsystem's emits
    # land in ONE events.jsonl keyed by this run_id
    from . import telemetry

    tel_cfg = telemetry.configure(config)
    if tel_cfg.enabled and tel_cfg.journal and rank == 0:
        telemetry.open_journal(log_name, path="./logs")
        telemetry.emit("run_start", log_name=log_name, world=world)

    def _finish_telemetry() -> None:
        telemetry.emit("run_end", log_name=log_name)
        if tel_cfg.enabled and tel_cfg.trace_events and rank == 0:
            try:
                telemetry.save_trace(
                    os.path.join("./logs", log_name, "trace.json")
                )
            except OSError as e:
                print_distributed(verbosity, f"trace.json save failed: {e}")
        if rank == 0:
            # cost observatory: persist whatever the run's AOT sites (and
            # the opt-in train-step probe) recorded, next to this run's
            # journal — a path-valued HYDRAGNN_LEDGER redirects it. Empty
            # ledgers (plain training without the probe armed) write
            # nothing.
            try:
                telemetry.ledger.maybe_save(
                    os.path.join("./logs", log_name, "ledger.json")
                )
            except OSError as e:
                print_distributed(verbosity, f"ledger.json save failed: {e}")
        telemetry.close_journal()

    # try/finally so a CRASHED run — the post-mortem CLI's whole
    # point — still records run_end, saves trace.json, and closes
    # the journal cleanly (the torn-tail contract covers at most
    # the final line; an abandoned open journal would leave no
    # end-of-run marker at all)
    try:
        # model + optimizer (reference :97-121)
        model = create_model_config(config)
        optimizer = select_optimizer(config["NeuralNetwork"]["Training"]["Optimizer"])

        # population training (train/population.py): N ensemble members / HPO
        # trials vmapped into one jitted program — routed BEFORE the
        # single-state init below (the population builds its own N-member
        # state; initializing a throwaway single state first would waste one
        # full init compile). The member axis IS the parallelism, so this
        # route pins single-program mode (no data mesh / edge-sharding /
        # pipeline; requesting both is a config error, not a silent downgrade)
        # and returns the stacked PopulationState.
        from .train.population import resolve_population_size, train_population

        pop_n = resolve_population_size(config["NeuralNetwork"]["Training"])
        if pop_n > 1:
            arch_cfg = config["NeuralNetwork"].get("Architecture", {})
            par_mode = str(arch_cfg.get("parallelism") or "data").lower()
            from .parallel.halo import halo_enabled as _halo_enabled

            if (
                par_mode != "data"
                or arch_cfg.get("edge_sharding")
                or _halo_enabled(arch_cfg)
            ):
                raise ValueError(
                    f"Training.population.size={pop_n} cannot combine with "
                    f"Architecture.parallelism={par_mode!r}/edge_sharding/halo "
                    "— the population member axis is the program's batch "
                    "parallelism"
                )
            if world > 1:
                # each process would train its own unsynchronized population on
                # its loader shard and race on the same log dir — reject rather
                # than silently produce world x N divergent model sets
                raise ValueError(
                    f"Training.population.size={pop_n} is single-process for "
                    f"now, but this job runs {world} processes — launch one "
                    "process, or drop to per-process subprocess trials"
                )
            # Training.continue + Training.population: restore the [N]-stacked
            # PopulationState through the ordinary checkpoint machinery — the
            # stacked template (one init broadcast N ways) names the [N, ...]
            # leaf shapes, so orbax round-trips fp32 master weights + per-member
            # opt state (incl. injected hyperparameter stacks) + step counters;
            # the sidecar's population_meta block carries the resume epoch and
            # the per-member divergence bookkeeping
            pop_resume = None  # (PopulationState, start_epoch, tracker_state)
            if training_cfg.get("continue"):
                from .train.checkpoint import load_checkpoint
                from .train.population import PopulationState, population_template

                startfrom = training_cfg.get("startfrom", log_name)
                template = population_template(
                    model, optimizer, next(iter(train_loader)), pop_n
                )
                try:
                    restored, pmeta = load_checkpoint(template.state, startfrom)
                except FileNotFoundError as e:
                    raise FileNotFoundError(
                        f"Training.continue set but no checkpoint under "
                        f"logs/{startfrom}: {e}"
                    )
                saved_n = int(pmeta.get("population", 0) or 0)
                if saved_n and saved_n != pop_n:
                    raise ValueError(
                        f"checkpoint under logs/{startfrom} holds a "
                        f"{saved_n}-member population but the config asks for "
                        f"{pop_n}"
                    )
                pop_resume = (
                    PopulationState(state=restored),
                    int(pmeta.get("population_epochs_done", pmeta.get("epoch", 0))),
                    pmeta.get("member_tracker"),
                )
                print_distributed(
                    verbosity,
                    f"resumed {pop_n}-member population from {startfrom} "
                    f"({pop_resume[1]} epoch(s) already trained)",
                )
            from .utils.walltime import make_walltime_check

            # same input-pipeline prefetch the single-state path wires below:
            # collate (+ device_put at K=1; K>1 blocks stack host batches) runs
            # ahead of the step loop — the population's per-dispatch work is N x
            # heavier, but the host-side batch cost is identical and would
            # otherwise sit on the critical path
            depth = flags.get(
                flags.PREFETCH, default=int(training_cfg.get("prefetch", 2))
            )
            pf_workers = flags.get(
                flags.NUM_WORKERS, default=int(training_cfg.get("num_workers", 1))
            )
            if depth > 0:
                from .graphs.batching import PrefetchLoader
                from .train.superstep import resolve_steps_per_dispatch

                k_pop = resolve_steps_per_dispatch(config["NeuralNetwork"]["Training"])
                train_loader = PrefetchLoader(
                    train_loader, depth=depth, device_put=k_pop == 1,
                    workers=pf_workers,
                )
                val_loader = PrefetchLoader(
                    val_loader, depth=depth, device_put=True, workers=pf_workers
                )
                test_loader = PrefetchLoader(
                    test_loader, depth=depth, device_put=True, workers=pf_workers
                )
            pstate, summary = train_population(
                model, optimizer, train_loader, val_loader, test_loader,
                config["NeuralNetwork"], log_name, verbosity,
                walltime_check=make_walltime_check(),
                initial_state=None if pop_resume is None else pop_resume[0],
                start_epoch=0 if pop_resume is None else pop_resume[1],
                tracker_state=None if pop_resume is None else pop_resume[2],
            )
            try:
                from .train.checkpoint import save_checkpoint
                from .train.population import population_meta

                # the stacked TrainState has the single-state treedef with [N]
                # leaves, so the ordinary checkpoint machinery handles it;
                # member_state(pstate, i) re-slices a winner for serving. The
                # sidecar carries the full population_meta block so a later
                # continue (e.g. num_epoch raised) resumes from here. Epochs
                # done = what actually TRAINED (resume point + history length)
                # — num_epoch would lie when the walltime guard broke the loop
                # early, and a later continue would silently skip the rest.
                epochs_done = int(summary.get("start_epoch", 0)) + len(
                    summary.get("history", [])
                )
                meta = {"final": True, **population_meta(pop_n, epochs_done)}
                meta["member_tracker"] = summary.get("member_tracker")
                meta["member_status"] = [m["status"] for m in summary["members"]]
                save_checkpoint(
                    pstate.state, log_name, epoch=epochs_done, meta=meta,
                )
            except Exception as e:
                print_distributed(verbosity, f"final population save failed: {e}")
            tr.print_timers(verbosity)
            return pstate, model, config

        example = next(iter(train_loader))
        state = create_train_state(model, optimizer, example)

        # resume (reference load_existing_model_config, model.py:202-216):
        # Training.continue truthy -> restore model+optimizer from the run named
        # by Training.startfrom (default: this run's log name). A preemption
        # checkpoint's sidecar (mid_epoch) additionally carries the exact loader
        # position; it flows into train_validate_test so the resumed run
        # consumes precisely the not-yet-seen batches (hydragnn_tpu.resilience).
        resume_meta = None
        if training_cfg.get("continue"):
            from .train.checkpoint import load_checkpoint

            startfrom = training_cfg.get("startfrom", log_name)
            try:
                state, meta = load_checkpoint(state, startfrom)
                print_distributed(
                    verbosity, f"resumed from {startfrom} (epoch {meta.get('epoch')})"
                )
            except FileNotFoundError as e:
                raise FileNotFoundError(
                    f"Training.continue set but no checkpoint under logs/{startfrom}: {e}"
                )
            if meta.get("mid_epoch"):
                resume_meta = meta
                print_distributed(
                    verbosity,
                    f"mid-epoch resume: epoch {meta.get('epoch')}, "
                    f"{meta.get('raw_batches_done')} batches already trained",
                )

        # auto-scale to every local device: one SPMD program over a 1D data mesh
        # (HYDRAGNN_AUTO_PARALLEL=0 forces single-device; HYDRAGNN_USE_FSDP=1
        # shards params/optimizer state — the reference's FSDP/ZeRO env knobs).
        # FSDP_STRATEGY maps the reference's torch strategies
        # (distributed.py:435-437): NO_SHARD -> replicated, everything else ->
        # param+opt sharding; validated HERE so a typo fails loudly even when no
        # mesh ends up being built
        _fsdp_requested = flags.get(flags.USE_FSDP)
        _fsdp_strategy = str(flags.get(flags.FSDP_STRATEGY)).upper()
        if _fsdp_requested:
            _known = {"FULL_SHARD", "SHARD_GRAD_OP", "HYBRID_SHARD", "NO_SHARD"}
            if _fsdp_strategy not in _known:
                raise ValueError(
                    f"HYDRAGNN_FSDP_STRATEGY={_fsdp_strategy!r} not one of {sorted(_known)}"
                )
        # Architecture.parallelism routes the mesh layout (mirrors how
        # edge_sharding routes the long-context path): "data" (default),
        # "tensor" (feature-axis TP over an inner model axis), or
        # "pipeline" (GPipe conv-stack pipelining over a stage ring).
        arch_cfg = config["NeuralNetwork"].get("Architecture", {})
        par_mode = str(arch_cfg.get("parallelism") or "data").lower()
        if par_mode not in ("data", "tensor", "pipeline"):
            raise ValueError(
                f"Architecture.parallelism {par_mode!r} not one of "
                "'data', 'tensor', 'pipeline'"
            )
        # halo-exchange partitioning (parallel/halo.py) — the node-resident
        # large-graph route. Validated BEFORE any mesh work so an impossible
        # combination fails before anything is placed.
        from .parallel.halo import halo_config, halo_enabled

        halo_mode = halo_enabled(arch_cfg)
        halo_cfg = halo_config(arch_cfg) if halo_mode else None
        if halo_mode:
            if arch_cfg.get("edge_sharding"):
                raise ValueError(
                    "Architecture.halo.enabled and Architecture.edge_sharding "
                    "are mutually exclusive large-graph routes; pick one"
                )
            if par_mode != "data":
                raise ValueError(
                    "halo partitioning splits the graph over the DATA axis; "
                    f"Architecture.parallelism={par_mode!r} cannot combine "
                    "with it"
                )
            if _fsdp_requested and _fsdp_strategy != "NO_SHARD":
                raise ValueError(
                    "halo partitioning keeps params replicated inside its "
                    "shard_map step; HYDRAGNN_USE_FSDP param sharding is not "
                    "supported with it"
                )
        mesh = None
        # how TrainState leaves are placed on the mesh — the elastic recovery
        # path re-places the restored state with the same policy after a re-mesh
        state_param_mode = "replicated"
        # no fallback here: with HYDRAGNN_AUTO_PARALLEL on and several
        # devices present, a mesh that cannot be built is an error — a run
        # that quietly trained on the first device would report one chip's
        # work as the host's
        import jax

        n_dev = len(jax.devices())  # global (all processes)
        n_local = len(jax.local_devices())
        # edge-sharded / halo (long-context) modes feed ONE batch to the
        # whole mesh, so any loader length works
        edge_mode = bool(arch_cfg.get("edge_sharding"))
        if (
            flags.get(flags.AUTO_PARALLEL)
            and n_dev > 1
            and (edge_mode or halo_mode or len(train_loader) >= n_local)
        ):
            from .parallel import make_mesh, shard_state

            if par_mode == "pipeline":
                from jax.sharding import NamedSharding, PartitionSpec as P
                from .parallel.pipeline import (
                    make_pipeline_mesh,
                    validate_pipeline_support,
                )

                validate_pipeline_support(model, n_dev)  # explicit: fail fast
                mesh = make_pipeline_mesh(n_dev)
                rep = NamedSharding(mesh, P())
                state = jax.tree.map(
                    lambda x: jax.device_put(x, rep)
                    if hasattr(x, "shape") else x,
                    state,
                )
                print_distributed(
                    verbosity, f"pipeline-parallel: {n_dev}-stage GPipe ring"
                )
            elif par_mode == "tensor":
                tp = int(
                    arch_cfg.get("tensor_parallel_size")
                    or (4 if n_dev % 4 == 0 else 2)
                )
                if n_dev % tp:
                    raise ValueError(
                        f"tensor_parallel_size={tp} does not divide the "
                        f"{n_dev}-device mesh"
                    )
                mesh = make_mesh(n_data=n_dev // tp, n_model=tp)
                state_param_mode = "tp"
                state = shard_state(state, mesh, param_mode="tp")
                print_distributed(
                    verbosity,
                    f"tensor-parallel: ({n_dev // tp} data x {tp} model) mesh",
                )
            else:
                mesh = make_mesh()
                # FSDP_STRATEGY maps the reference's torch strategies
                # (distributed.py:435-437): NO_SHARD -> replicated,
                # everything else -> param+opt sharding over the data axis
                param_mode = (
                    "fsdp" if _fsdp_requested and _fsdp_strategy != "NO_SHARD"
                    else "replicated"
                )
                state_param_mode = param_mode
                state = shard_state(state, mesh, param_mode=param_mode)
                print_distributed(
                    verbosity,
                    f"auto-parallel: {n_dev}-device data mesh ({param_mode})",
                )
            # publish the mesh for trace-time consumers (ring attention)
            from .parallel.ring_attention import set_global_mesh

            if par_mode != "pipeline":
                set_global_mesh(mesh)
        elif par_mode != "data" or (
            halo_mode and halo_cfg.fallback == "error"
        ):
            raise ValueError(
                f"Architecture.parallelism={par_mode!r}"
                + ("/halo" if halo_mode else "")
                + " requested but no multi-device mesh is available "
                f"({n_dev} device(s), {len(train_loader)} train batches)"
            )
        elif flags.get(flags.AUTO_PARALLEL) and n_dev > 1:
            print_distributed(
                verbosity,
                f"single-device run on a {n_dev}-device host: "
                f"{len(train_loader)} train batch(es) cannot fill one "
                f"{n_local}-device step",
            )

        # TensorBoard scalars on process 0 (reference get_summary_writer,
        # model.py:193-199). tensorboardX is preferred (torch-free); the torch
        # writer is the fallback since torch ships in most reference installs.
        # HYDRAGNN_TENSORBOARD=0 disables.
        writer = None
        if flags.get(flags.TENSORBOARD):
            try:
                import jax

                if jax.process_index() == 0:
                    try:
                        from tensorboardX import SummaryWriter
                    except ImportError:
                        from torch.utils.tensorboard import SummaryWriter

                    writer = SummaryWriter(os.path.join("./logs", log_name))
            except Exception as e:
                print_distributed(
                    verbosity, f"TensorBoard logging disabled ({type(e).__name__}: {e})"
                )
                writer = None

        # walltime guard (reference distributed.py:614-639): stop before SLURM
        # kills the job so the best checkpoint survives
        from .utils.walltime import make_walltime_check

        # input-pipeline prefetch (reference HydraDataLoader's threaded prefetch,
        # load_data.py:94-204): collate + host->device transfer run a couple of
        # batches ahead of the step loop. Training.prefetch / HYDRAGNN_PREFETCH
        # set the depth; 0 disables.
        depth = flags.get(flags.PREFETCH, default=int(training_cfg.get("prefetch", 2)))
        workers = flags.get(
            flags.NUM_WORKERS, default=int(training_cfg.get("num_workers", 1))
        )
        # supersteps (Training.steps_per_dispatch / HYDRAGNN_SUPERSTEP) stack K
        # host batches into one [K, ...] block in the loop — read K here so the
        # prefetcher knows to keep batches host-side for stacking
        from .train.superstep import resolve_steps_per_dispatch

        k_dispatch = resolve_steps_per_dispatch(training_cfg)
        if depth > 0:
            from .graphs.batching import PrefetchLoader

            # under a mesh (or a superstep block) the loop stacks host batches
            # itself: prefetch the collate work but leave device placement to
            # put_batch / put_block. Supersteps only ever consume the TRAIN
            # loader as blocks — eval stays per-batch, so val/test keep the
            # prefetched device_put at any K
            dput_eval = mesh is None
            train_loader = PrefetchLoader(
                train_loader, depth=depth,
                device_put=dput_eval and k_dispatch == 1, workers=workers
            )
            val_loader = PrefetchLoader(
                val_loader, depth=depth, device_put=dput_eval, workers=workers
            )
            test_loader = PrefetchLoader(
                test_loader, depth=depth, device_put=dput_eval, workers=workers
            )

        # fault-tolerance context (hydragnn_tpu.resilience): non-finite step
        # guard + divergence rollback, preemption checkpointing, chaos harness.
        # Built HERE (not inside the loop) so the preemption outcome is visible
        # below: a preempted run must keep its mid-epoch "latest" pointer.
        from .resilience import Resilience

        resilience = Resilience.from_config(training_cfg)

        if resilience.elastic:
            # in-process elastic recovery (resilience/elastic.py): preemption /
            # host-loss / hung-dispatch faults drain to the dispatch boundary,
            # re-mesh from survivors, and resume the SAME epoch without a
            # process restart. Layouts with no in-process re-mesh (pipeline /
            # edge-sharded / tensor) still route through the controller so the
            # restart fallback is a logged policy decision, not dead-end flow.
            from .resilience import ElasticController, train_elastic

            controller = ElasticController(
                max_recoveries=resilience.max_recoveries
            )
            state = train_elastic(
                model, optimizer, state, train_loader, val_loader, test_loader,
                config["NeuralNetwork"], log_name, verbosity, writer=writer,
                walltime_check=make_walltime_check(), mesh=mesh,
                resilience=resilience, resume_meta=resume_meta,
                controller=controller, param_mode=state_param_mode,
            )
        else:
            state = train_validate_test(
                model,
                optimizer,
                state,
                train_loader,
                val_loader,
                test_loader,
                config["NeuralNetwork"],
                log_name,
                verbosity,
                writer=writer,
                walltime_check=make_walltime_check(),
                mesh=mesh,
                resilience=resilience,
                resume_meta=resume_meta,
            )
        if writer is not None:
            writer.close()

        # always save the final model (reference run_training.py:206 save_model);
        # resumable via Training.continue + startfrom=<log_name>. EXCEPT after a
        # preemption: the mid-epoch checkpoint IS the resume point, and
        # re-pointing "latest" at a final-save would discard the loader position
        # its sidecar records.
        if resilience.preempted:
            print_distributed(
                verbosity,
                "preempted: mid-epoch checkpoint is the resume point; "
                "skipping the final save",
            )
        else:
            try:
                from .train.checkpoint import save_checkpoint

                save_checkpoint(
                    state,
                    log_name,
                    epoch=int(config["NeuralNetwork"]["Training"].get("num_epoch", 0)),
                    meta={"final": True},
                )
            except Exception as e:  # a failed save must not kill a finished training
                print_distributed(verbosity, f"final model save failed: {e}")

        # end-of-run visualization (reference train_validate_test :441-491)
        if config.get("Visualization", {}).get("create_plots"):
            try:
                from .postprocess.visualizer import Visualizer
                from .run_prediction import run_prediction

                _, _, trues, preds = run_prediction(config, state, model, samples=samples)
                viz = Visualizer(log_name)
                viz.create_parity_plot(
                    trues, preds, names=config["NeuralNetwork"]["Variables_of_interest"].get("output_names")
                )
                viz.create_error_histogram(trues, preds)
            except Exception as e:  # plots must never kill a finished training
                print_distributed(verbosity, f"visualization failed: {e}")

        tr.print_timers(verbosity)
        if verbosity >= 2:
            # process-0 local devices only (the reference prints per rank,
            # distributed.py:566-581; here other hosts' chips are not covered)
            from .utils.print_utils import device_memory_summary

            print_distributed(verbosity, f"[memory host0] {device_memory_summary()}")
        return state, model, config
    finally:
        _finish_telemetry()


__all__ = ["run_training"]
