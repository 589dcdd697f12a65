"""Persistent XLA compilation cache + serialized-AOT executable artifacts.

Compiling a train step for the TPU takes seconds to minutes; the persistent
cache lets every later process (reruns, the benchmark, a second
``chip_smoke.py``) load the executable from disk instead. The reference has
no analog (torch eager).

Placement (:func:`cache_dir`): where ``JAX_COMPILATION_CACHE_DIR`` is set the
cache lives there and this module sets NOTHING in code — jax reads the
variable itself, so whoever runs the program decides. Otherwise it is the
fixed ``<checkout>/.jax_cache`` next to the package, never a path relative to
the working directory: the path is part of the cache key, so a directory that
moves with ``cwd`` never hits. ``HYDRAGNN_COMPILE_CACHE=0`` is the off switch
for the cache this module places.

The serialized-AOT artifact layer (:func:`save_artifact` /
:func:`load_artifact`) goes one step further for the serving fleet: warm-up
persists each per-(model, bucket) predict executable as a ``jax.export``
StableHLO blob keyed like the cost ledger (model/bucket/kind/backend/
precision), so a BOOTING replica deserializes and compiles the exact same
program instead of re-tracing the model — the thing that makes autoscaling
responsive. Artifacts are fingerprinted on the ABSTRACT call signature
(arg shapes/dtypes/tree structure + jax version + backend + precision),
never on parameter values, so a new checkpoint of the same architecture —
the blue/green rollout case — reuses them; any mismatch raises a typed
:class:`ArtifactError` for the caller to fall back LOUDLY to
compile-from-source.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import struct

_enabled = False

#: File magic for serialized-AOT artifacts; bump the trailing digit on any
#: layout change so a stale artifact fails the header check, not deserialize.
ARTIFACT_MAGIC = b"HGNNAOT1"


class ArtifactError(RuntimeError):
    """A serialized-AOT artifact is missing, torn, or does not match the
    current program's fingerprint. Callers treat this as 'compile from
    source instead' — loudly, never silently."""


def _checkout_cache_dir() -> str:
    package_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package_root), ".jax_cache")


def cache_dir() -> str | None:
    """The directory the persistent compile cache resolves to (see the
    module docstring), or None when ``HYDRAGNN_COMPILE_CACHE=0``."""
    from . import flags

    if not flags.get(flags.COMPILE_CACHE):
        return None
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _checkout_cache_dir()


def enable_compile_cache() -> str | None:
    """Idempotently enable the persistent compilation cache at
    :func:`cache_dir`. Returns the directory, or None when switched off. A
    directory that cannot be created raises: a cache that silently failed to
    enable looks exactly like a cold one."""
    global _enabled
    path = cache_dir()
    if path is None or _enabled:
        return path
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        # cache anything that took meaningful compile time
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    _enabled = True
    return path


def shape_structs(tree):
    """Abstract twin of a pytree of arrays: every leaf becomes a
    ``jax.ShapeDtypeStruct`` (static aux data — ``BatchMeta`` — passes
    through untouched). Lets AOT warm-up lower against a batch *signature*
    without materializing or transferring batch-sized buffers."""
    import jax
    import numpy as np

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype), tree
    )


def aot_compile(jitted, *args, ledger_entry: dict | None = None):
    """Ahead-of-time lower + compile one signature of a jitted callable and
    return the executable: ``aot_compile(fn, state, shape_structs(batch))``.

    The returned executable is invoked directly (``compiled(state, batch)``)
    and never re-traces — zero ``jaxpr_to_mlir_module`` events per call, which
    is what lets the serving tier's steady state pass the strict recompile
    sentinel. Pair with :func:`enable_compile_cache` first so the backend
    compile itself hits the persistent disk cache across process restarts
    (the 20-40 s first-compile cost becomes a one-time cost per cache dir).

    Args may mix concrete arrays (live params) and ``ShapeDtypeStruct``
    signatures (the per-bucket batch shape).

    ``ledger_entry`` labels the executable's cost-ledger record
    (``{"model": ..., "bucket": ..., "kind": ..., "precision": ...}``) —
    every AOT site feeds the cost observatory
    (``telemetry/ledger.py``); reading ``cost_analysis()`` off an
    already-built executable is free, and capture is a no-op when the
    telemetry plane (or ``HYDRAGNN_LEDGER``) is off. A telemetry failure
    never fails the compile.
    """
    import time

    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    elapsed = time.perf_counter() - t0
    try:
        from ..telemetry import ledger as _ledger

        _ledger.record(compiled, compile_s=elapsed, **(ledger_entry or {}))
    except Exception:
        pass
    return compiled


def _register_export_pytrees(args) -> None:
    """``jax.export`` refuses to serialize a pytree whose container types it
    has not been told how to name — and a served call signature is full of
    NamedTuples (``TrainState``, ``GraphBatch``, optax optimizer states).
    Walk ``args`` and register every NamedTuple type under its
    module-qualified name. Idempotent, and the SAME walk runs on the save
    and load sides (both hold the call args), so writer and booting reader
    always agree on the vocabulary."""
    from jax import export as jax_export

    seen: set = set()

    def walk(x):
        t = type(x)
        if isinstance(x, tuple) and hasattr(t, "_fields"):
            if t not in seen:
                seen.add(t)
                try:
                    jax_export.register_namedtuple_serialization(
                        t, serialized_name=f"{t.__module__}.{t.__qualname__}"
                    )
                except ValueError:
                    pass  # already registered (earlier save/load this process)
            for v in x:
                walk(v)
        elif isinstance(x, (list, tuple)):
            for v in x:
                walk(v)
        elif isinstance(x, dict):
            for v in x.values():
                walk(v)

    walk(args)


def abstract_fingerprint(*args, precision: str | None = None,
                         backend: str | None = None) -> str:
    """Architecture-level fingerprint of an AOT call signature: the abstract
    shapes/dtypes + pytree structure of ``args``, the jax version, the
    backend platform, and the compute precision. Parameter VALUES are
    deliberately excluded — two checkpoints of the same architecture share a
    fingerprint, which is what lets a blue/green rollout boot new-weight
    replicas from the old generation's artifacts."""
    import jax

    if backend is None:
        backend = jax.default_backend()
    leaves, treedef = jax.tree.flatten(shape_structs(args))
    sig = {
        "jax": jax.__version__,
        "backend": str(backend),
        "precision": str(precision),
        "tree": str(treedef),
        "leaves": [[list(x.shape), str(x.dtype)] for x in leaves],
    }
    blob = json.dumps(sig, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def artifact_path(artifact_dir: str, *, model: str, bucket,
                  kind: str = "predict", precision: str | None = None) -> str:
    """Filesystem path of one executable artifact, keyed like the cost
    ledger: ``<dir>/<model>/<kind>--<precision>--<bucket>.aot`` with the
    bucket repr sanitized + hash-suffixed (bucket reprs contain characters
    no filesystem wants)."""
    braw = str(bucket)
    bsafe = re.sub(r"[^A-Za-z0-9._-]+", "_", braw).strip("_")[:80]
    bhash = hashlib.sha1(braw.encode()).hexdigest()[:10]
    psafe = re.sub(r"[^A-Za-z0-9._-]+", "_", str(precision))
    return os.path.join(
        artifact_dir, str(model), f"{kind}--{psafe}--{bsafe}-{bhash}.aot"
    )


def save_artifact(artifact_dir: str, jitted, *args, model: str, bucket,
                  kind: str = "predict", precision: str | None = None,
                  ledger_entry: dict | None = None):
    """Export + persist one AOT signature and return its executable:
    ``(compiled, path)``.

    The executable handed back is compiled FROM the exported StableHLO (not
    from the original traced function), i.e. the very same program a booting
    worker gets back out of :func:`load_artifact` — so serialized boot is
    bit-identical to the warm-up that wrote the artifact, by construction.
    The write is atomic (tmp + ``os.replace``), matching the replica
    ready-file discipline: a reader never sees a torn artifact, only the old
    one or the new one.
    """
    import time

    import jax
    from jax import export as jax_export

    t0 = time.perf_counter()
    _register_export_pytrees(args)
    exported = jax_export.export(jitted)(*args)
    blob = exported.serialize()
    header = {
        "fingerprint": abstract_fingerprint(*args, precision=precision),
        "model": str(model),
        "bucket": str(bucket),
        "kind": str(kind),
        "precision": str(precision),
        "backend": jax.default_backend(),
        "jax": jax.__version__,
    }
    hdr = json.dumps(header, sort_keys=True).encode()
    path = artifact_path(
        artifact_dir, model=model, bucket=bucket, kind=kind,
        precision=precision,
    )
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(ARTIFACT_MAGIC)
        f.write(struct.pack("<I", len(hdr)))
        f.write(hdr)
        f.write(blob)
    os.replace(tmp, path)
    compiled = jax.jit(exported.call).lower(*args).compile()
    elapsed = time.perf_counter() - t0
    try:
        from ..telemetry import ledger as _ledger

        _ledger.record(compiled, compile_s=elapsed, **(ledger_entry or {}))
    except Exception:
        pass
    return compiled, path


def load_artifact(artifact_dir: str, *args, model: str, bucket,
                  kind: str = "predict", precision: str | None = None,
                  ledger_entry: dict | None = None):
    """Deserialize one persisted artifact and compile its StableHLO into a
    live executable — seconds, vs minutes of trace + compile from source.

    Raises :class:`ArtifactError` when the artifact is missing, torn, or its
    fingerprint does not match the CURRENT abstract signature (different jax
    version, backend, precision, or bucket shapes). Callers catch that and
    fall back to compile-from-source loudly; they never serve a stale
    program.
    """
    import jax

    path = artifact_path(
        artifact_dir, model=model, bucket=bucket, kind=kind,
        precision=precision,
    )
    if not os.path.exists(path):
        raise ArtifactError(f"no serialized artifact at {path}")
    try:
        with open(path, "rb") as f:
            magic = f.read(len(ARTIFACT_MAGIC))
            if magic != ARTIFACT_MAGIC:
                raise ArtifactError(
                    f"artifact {path} has bad magic {magic!r} (expected "
                    f"{ARTIFACT_MAGIC!r}) — torn write or foreign file"
                )
            (hdr_len,) = struct.unpack("<I", f.read(4))
            header = json.loads(f.read(hdr_len).decode())
            blob = f.read()
    except ArtifactError:
        raise
    except Exception as e:
        raise ArtifactError(f"artifact {path} unreadable: {e!r}") from e
    want = abstract_fingerprint(*args, precision=precision)
    got = header.get("fingerprint")
    if got != want:
        raise ArtifactError(
            f"artifact {path} fingerprint mismatch (artifact "
            f"{str(got)[:12]}… from jax {header.get('jax')}/"
            f"{header.get('backend')}, current {want[:12]}… from jax "
            f"{jax.__version__}/{jax.default_backend()}) — recompiling "
            "from source"
        )
    from jax import export as jax_export

    import time

    t0 = time.perf_counter()
    _register_export_pytrees(args)
    try:
        exported = jax_export.deserialize(blob)
        compiled = jax.jit(exported.call).lower(*args).compile()
    except Exception as e:
        raise ArtifactError(
            f"artifact {path} failed to deserialize/compile: {e!r}"
        ) from e
    try:
        from ..telemetry import ledger as _ledger

        _ledger.record(
            compiled, compile_s=time.perf_counter() - t0,
            **(ledger_entry or {}),
        )
    except Exception:
        pass
    return compiled
