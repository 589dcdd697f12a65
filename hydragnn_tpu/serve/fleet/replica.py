"""One fleet replica: a ``PredictionServer`` behind the shared wire.

:class:`ReplicaHost` is the wire front end — a ``utils.wire.WireServer``
exposing three ops over the SAME transport the elastic data plane speaks:

* ``predict`` — one graph in (wire sample codec), per-head arrays out;
  typed admission errors (queue full, oversize, deadline, incompatible
  sample, unknown model) travel as ``n=-4`` records carrying the
  exception class name, so the router re-raises the SAME types
  ``serve.admission`` defines;
* ``ping`` — readiness + identity (model list, per-model quant flags);
  the router's health prober validates these through ``wire.check_pong``
  before lifting a quarantine, exactly like the ShardedStore prober
  validates a shard's advertised range;
* ``stats`` — per-endpoint queue depth, shed counters, and the
  STEADY-LOWERING COUNT (jit lowerings since the replica advertised
  ready — 0 is the AOT zero-recompile guarantee, now provable per
  replica across a process boundary) for routing/ops decisions;
* ``metrics`` — the replica's full telemetry-registry snapshot
  (``hydragnn_tpu.telemetry``) plus its stats dict, JSON over the wire;
  ``FleetRouter.metrics()`` folds every replica's answer into the
  fleet-wide aggregate view.

``worker_main`` is the subprocess entry (``python -m
hydragnn_tpu.serve.fleet.replica spec.json``): it boots a
``PredictionServer`` from CHECKPOINT PATHS ALONE
(``add_model_from_checkpoint``), completes AOT warm-up, and only then
binds the wire port and writes the ready file — a replica is never
routable before its executables are warm. ``spawn_replica`` is the
parent-side helper the router/bench/tests use.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

from ...utils import wire
from ..admission import AdmissionError
from .config import FleetConfig

_PREDICT_TIMEOUT_S = 120.0


class ReplicaBootError(RuntimeError):
    """A worker's ready file existed but could not be trusted: torn or
    garbage contents (writer killed mid-write, foreign file) or a payload
    missing the boot contract's fields. Carries the path and the partial
    contents so the operator sees WHAT was on disk, not an opaque
    ``JSONDecodeError`` from deep inside the poll loop."""


class ReplicaHost(wire.WireServer):
    """Wire front end of one (already registered + warmed) ``PredictionServer``.

    In-process it gives tests/bench a real-RPC replica without a
    subprocess boot; ``worker_main`` wraps the identical class around a
    checkpoint-booted server — one serving path, two deployment shapes."""

    def __init__(self, server, host: str = "127.0.0.1", port: int = 0,
                 auth_token: str | None = None,
                 predict_timeout_s: float = _PREDICT_TIMEOUT_S,
                 journal=None):
        from ...analysis.sentinel import compile_counts

        self.server = server
        self._predict_timeout_s = float(predict_timeout_s)
        # lowering counter snapshot AT READY: stats() reports the delta,
        # which a warmed replica must keep at zero (the strict-sentinel
        # property, observable over the wire)
        self._ready_lowerings = int(compile_counts()["lowerings"])
        # journal= routes this replica's trace-scoped records into its own
        # EventJournal (subprocess workers: their log dir; in-process
        # tests: a distinct dir per replica) instead of the process-global
        # journal the router writes
        super().__init__(host=host, port=port, auth_token=auth_token,
                         name="ReplicaHost", journal=journal)

    # -- wire ops -----------------------------------------------------------

    def pong_fields(self) -> dict:
        names = sorted(self.server._models)
        quant = np.asarray(
            [
                1 if self.server._models[n].cfg.quantize
                and self.server._models[n].executables_quant else 0
                for n in names
            ],
            np.int64,
        )
        return {
            "ready": np.asarray(1, np.int64),
            "models": wire.text_field(",".join(names)),
            "quantized": quant,
        }

    def handle_frame(self, z: dict) -> bytes | dict:
        if "stats" in z:
            return {
                "n": np.asarray(0, np.int64),
                "stats": wire.text_field(json.dumps(self.stats())),
            }
        if "metrics" in z:
            return {
                "n": np.asarray(0, np.int64),
                "metrics": wire.text_field(json.dumps(self.metrics())),
            }
        if "predict" in z:
            return self._handle_predict(z)
        raise ValueError(f"unknown fleet op in frame keys {sorted(z)}")

    def _handle_predict(self, z: dict) -> dict:
        from ... import telemetry as tel

        model = wire.field_text(z.get("model"))
        sample = wire.samples_from_frame(z)[0]
        # the handler thread's scope (set by WireServer from the frame's
        # trace context) decides whether this predict is part of a traced
        # request — only then does it journal, so untraced traffic adds
        # zero records
        traced = bool(tel.get_context().get("request_id"))
        try:
            fut = self.server.submit(model, sample)
            result = fut.result(timeout=self._predict_timeout_s)
        except AdmissionError as e:
            # typed shed: the router re-raises the same admission class on
            # its side of the wire (never laundered into a transport fault
            # — a shed is an ANSWER, failover would re-ask the question)
            if traced:
                self.emit_event(
                    "replica_execute", model=model, shed=type(e).__name__,
                )
            return {
                "n": np.asarray(-4, np.int64),
                "etype": wire.text_field(type(e).__name__),
                "detail": wire.text_field(str(e)[:512]),
            }
        if traced:
            self.emit_event(
                "replica_execute", model=model,
                latency_s=round(float(result["latency_s"]), 6),
            )
        out = {
            "n": np.asarray(1, np.int64),
            "nheads": np.asarray(len(result["heads"]), np.int64),
            "latency_s": np.asarray(result["latency_s"], np.float64),
        }
        for i, head in enumerate(result["heads"]):
            out[f"h{i}"] = np.asarray(head)
        return out

    def stats(self) -> dict:
        from ...analysis.sentinel import compile_counts

        per_model = self.server.stats()
        return {
            "models": per_model,
            "queue_depth": sum(m["queue_depth"] for m in per_model.values()),
            "shed": sum(m["shed"] for m in per_model.values()),
            "served": sum(m["served"] for m in per_model.values()),
            # jit lowerings since this replica advertised ready: 0 is the
            # per-replica zero-recompile guarantee
            "steady_lowerings": int(compile_counts()["lowerings"])
            - self._ready_lowerings,
        }

    def metrics(self) -> dict:
        """The ``metrics`` wire op's payload: the replica process's whole
        telemetry registry (``stats()`` first, so derived gauges are
        fresh) plus the stats dict the aggregate row sums."""
        from ... import telemetry as tel

        stats = self.stats()  # publishes the serve gauges as a side effect
        return {"stats": stats, "registry": tel.snapshot()}


# -- subprocess worker --------------------------------------------------------


def _build_server(spec: dict):
    """Boot a ``PredictionServer`` from a worker spec: models come from
    checkpoint paths alone (``add_model_from_checkpoint``); bucket-table
    samples ride a wire-codec file next to the spec. Import cost (jax,
    models) is paid here, inside the worker process."""
    from ..server import PredictionServer, ServingConfig

    serving = dict(spec.get("serving") or {})
    server = PredictionServer(ServingConfig(**serving))
    # serialized-AOT boot: honored only when the fleet block (or the
    # HYDRAGNN_SERIALIZED_BOOT flag) says so — endpoints with an
    # artifact_dir deserialize warm executables instead of recompiling,
    # falling back loudly per bucket on a fingerprint mismatch
    fleet_cfg = FleetConfig.from_config(
        {"fleet": dict(serving.get("fleet") or {})}
    )
    for m in spec["models"]:
        with open(m["samples_file"], "rb") as f:
            samples = wire.samples_from_frame(wire.unpack_arrays(f.read()))
        kwargs = {
            k: m[k]
            for k in ("batch_size", "max_buckets", "denormalize", "epoch")
            if k in m
        }
        if fleet_cfg.serialized_boot and m.get("artifact_dir"):
            kwargs["artifact_dir"] = m["artifact_dir"]
        server.add_model_from_checkpoint(
            m["name"], m["log_name"], path=m.get("path", "./logs/"),
            samples=samples, **kwargs,
        )
    return server


def worker_main(argv=None) -> int:
    """``python -m hydragnn_tpu.serve.fleet.replica spec.json``.

    Boot order is the readiness contract: build → warm (AOT, verified
    lowering-free) → start → bind the wire port → write the ready file.
    A boot failure writes ``{"error": ...}`` to the ready file so the
    parent surfaces the cause instead of timing out blind."""
    argv = sys.argv[1:] if argv is None else argv
    with open(argv[0]) as f:
        spec = json.load(f)

    def _write_ready(payload: dict) -> None:
        ready = spec["ready_file"]
        tmp = ready + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, ready)  # atomic: the parent never reads a torn file

    try:
        from ... import telemetry as tel

        # the worker's own observability surfaces, rooted in its log dir
        # (default: next to the spec): the journal the fleet CLI merges
        # with the router's, and the cost ledger of its warmed executables
        log_dir = spec.get("log_dir") or os.path.dirname(
            os.path.abspath(spec.get("ready_file", argv[0])))
        journal = None
        if tel.enabled():
            journal = tel.open_journal(
                file=os.path.join(log_dir, "events.jsonl"),
                run_id=f"replica-{os.getpid()}",
            )
        server = _build_server(spec)
        server.warmup(verify=True)  # ready MEANS warm: zero first-request compiles
        tel.ledger.maybe_save(os.path.join(log_dir, "ledger.json"))
        server.start()
        host = ReplicaHost(
            server,
            host=spec.get("bind_host", "127.0.0.1"),
            port=int(spec.get("port", 0)),
            auth_token=spec.get("auth"),
            journal=journal,
        )
    except Exception:
        import traceback

        _write_ready({"error": traceback.format_exc(limit=8)})
        return 1

    stop = {"flag": False}

    def _terminate(signum, frame):
        stop["flag"] = True

    signal.signal(signal.SIGTERM, _terminate)
    signal.signal(signal.SIGINT, _terminate)
    _write_ready({"port": host.port, "pid": os.getpid()})
    while not stop["flag"]:
        time.sleep(0.1)
    host.close()
    server.stop()
    from ... import telemetry as tel

    tel.close_journal()
    return 0


class ReplicaProcess:
    """Handle on one spawned replica worker."""

    def __init__(self, proc: subprocess.Popen, port: int, spec_path: str,
                 log_path: str):
        self.proc = proc
        self.port = port
        self.spec_path = spec_path
        self.log_path = log_path

    def terminate(self, timeout_s: float = 10.0) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=timeout_s)

    def kill(self) -> None:
        """The chaos path: SIGKILL, no teardown — a faithful host loss."""
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=10.0)

    def log_tail(self, n: int = 40) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return "<no log>"


def write_samples_file(samples, path: str) -> str:
    """Persist bucket-table samples for a worker spec (wire codec — the
    same no-pickle frame format everything else speaks)."""
    with open(path, "wb") as f:
        f.write(wire.encode_samples(list(samples)))
    return path


def _read_ready_file(path: str) -> dict:
    """Parse a worker's ready file, typed-erroring on anything short of the
    boot contract. ``_write_ready`` is atomic (tmp + ``os.replace``), so a
    HEALTHY writer never leaves a torn file — but a writer killed mid-write,
    a crashed filesystem, or a foreign file can. Those used to surface as an
    opaque ``JSONDecodeError``; now they raise :class:`ReplicaBootError`
    naming the path and the partial contents."""
    try:
        with open(path, errors="replace") as f:
            raw = f.read()
    except OSError as e:
        raise ReplicaBootError(f"ready file {path} unreadable: {e!r}") from e
    try:
        ready = json.loads(raw)
    except ValueError as e:
        raise ReplicaBootError(
            f"ready file {path} is torn/garbage (writer killed mid-write?): "
            f"{e}; partial contents: {raw[:256]!r}"
        ) from e
    if not isinstance(ready, dict) or not (
        "error" in ready or "port" in ready
    ):
        raise ReplicaBootError(
            f"ready file {path} violates the boot contract (expected a dict "
            f"with 'port' or 'error'): {raw[:256]!r}"
        )
    return ready


def spawn_replica(spec: dict, timeout_s: float | None = None,
                  env: dict | None = None) -> ReplicaProcess:
    """Launch one worker subprocess and block until it advertises ready
    (which, per the boot contract, means AOT warm-up finished). Raises
    with the worker's log tail on boot failure/timeout.

    An accelerator belongs to one process: a parent that has touched JAX
    holds the chip, and a worker spawned from it fails (loudly, here) at
    backend init. On a one-chip machine run replicas as in-process
    ``ReplicaHost``s; spawn workers only from a JAX-free parent with one
    device each.

    ``timeout_s=None`` (the default) takes ``Serving.fleet.boot_timeout_s``
    from the spec's serving block — one knob for every boot site instead of
    a hardcoded constant; pass an explicit value to override per call."""
    if timeout_s is None:
        timeout_s = FleetConfig.from_config(
            {"fleet": dict((spec.get("serving") or {}).get("fleet") or {})}
        ).boot_timeout_s
    workdir = tempfile.mkdtemp(prefix="hydragnn-fleet-")
    spec = dict(spec)
    spec.setdefault("ready_file", os.path.join(workdir, "ready.json"))
    spec_path = os.path.join(workdir, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    log_path = os.path.join(workdir, "worker.log")
    run_env = dict(os.environ)
    if env:
        run_env.update(env)
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "hydragnn_tpu.serve.fleet.replica",
             spec_path],
            stdout=log, stderr=subprocess.STDOUT, env=run_env,
        )
    handle = ReplicaProcess(proc, port=0, spec_path=spec_path,
                            log_path=log_path)
    deadline = time.monotonic() + float(timeout_s)
    while time.monotonic() < deadline:
        if os.path.exists(spec["ready_file"]):
            try:
                ready = _read_ready_file(spec["ready_file"])
            except ReplicaBootError:
                handle.terminate()
                raise
            if "error" in ready:
                handle.terminate()
                raise RuntimeError(
                    f"replica worker failed to boot:\n{ready['error']}"
                )
            handle.port = int(ready["port"])
            return handle
        if proc.poll() is not None:
            raise RuntimeError(
                f"replica worker exited rc={proc.returncode} before ready:\n"
                f"{handle.log_tail()}"
            )
        time.sleep(0.1)
    handle.terminate()
    raise TimeoutError(
        f"replica worker not ready within {timeout_s}s:\n{handle.log_tail()}"
    )


if __name__ == "__main__":
    sys.exit(worker_main())


__all__ = [
    "ReplicaBootError",
    "ReplicaHost",
    "ReplicaProcess",
    "spawn_replica",
    "worker_main",
    "write_samples_file",
]
