"""Runtime recompilation sentinel.

Static rules (GL001-GL003) catch retrace hazards you can see in the source;
this module catches the ones you can't — shape-unstable batches, pytree
structure drift, weak-typed scalars — by counting ACTUAL jit cache misses
while a region of code runs.

jax reports every trace / backend compile / persistent-cache event through
``jax.monitoring``; one module-level listener (installed lazily, never
removed — listeners are append-only in jax) feeds monotonic counters, and
:func:`no_recompile` turns "this region must not compile more than N
programs" into an assertion:

    step = make_train_step(model, opt)
    for batch in one_batch_of_each_bucket:        # one compile a padded shape,
        state, _ = step(state, batch)             # outside the region
    with no_recompile(what="train epoch"):
        for batch in loader:                      # every bucket is warm
            state, _ = step(state, batch)

Pairs with ``utils.compile_cache``: the persistent-cache counters distinguish
"retraced but the XLA binary came from disk" (cheap-ish, still a trace bug)
from full recompiles. ``tests/conftest.py`` re-exports the
``compile_sentinel`` fixture so any test can assert compile-count stability.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager

# duration-event keys emitted by jax._src.dispatch / compiler (stable across
# the 0.4.x line; hard-coded so importing private modules isn't needed)
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_COUNTER_KEYS = {
    TRACE_EVENT: "traces",
    LOWER_EVENT: "lowerings",
    BACKEND_COMPILE_EVENT: "backend_compiles",
    CACHE_HIT_EVENT: "persistent_cache_hits",
    CACHE_MISS_EVENT: "persistent_cache_misses",
}

_lock = threading.Lock()
_counters = {name: 0 for name in _COUNTER_KEYS.values()}
# per jitted function (the events' ``fun_name``): counter -> [count, seconds]
# of the three duration events
_by_function: dict[str, dict[str, list]] = {}
_installed = False


class RecompileError(RuntimeError):
    """A ``no_recompile`` region triggered more jit compilations than it
    declared."""


def _on_event(event: str, *args, **kw) -> None:
    name = _COUNTER_KEYS.get(event)
    if name is not None:
        with _lock:
            _counters[name] += 1
            if args:  # a duration event: (seconds,), fun_name=<jitted function>
                record = _by_function.setdefault(
                    str(kw.get("fun_name", "")), {}).setdefault(name, [0, 0.0])
                record[0] += 1
                record[1] += float(args[0])


def install() -> None:
    """Register the monitoring listeners (idempotent, thread-safe: listeners
    are append-only in jax, so a double registration would double-count
    every event forever)."""
    global _installed
    with _lock:
        if _installed:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_event)
        monitoring.register_event_listener(_on_event)
        _installed = True


def compile_counts() -> dict[str, int]:
    """Snapshot of process-lifetime compile counters (since install)."""
    install()
    with _lock:
        return dict(_counters)


def compile_seconds() -> dict[str, dict[str, tuple[int, float]]]:
    """Process-lifetime ``{fun_name: {counter: (count, seconds)}}`` of the
    trace, lowering and backend-compile events (a backend compile's seconds
    include its read of the persistent cache): where start-up time went, by
    program. The counts sum to ``compile_counts()``'s."""
    install()
    with _lock:
        return {
            fun: {name: (n, secs) for name, (n, secs) in record.items()}
            for fun, record in _by_function.items()
        }


@contextmanager
def no_recompile(max_compiles: int = 0, what: str = "region"):
    """Fail with :class:`RecompileError` if the wrapped region triggers more
    jit traces than declared.

    ``max_compiles`` is the number of NEW compilations the region is allowed
    (0 = everything must already be warm). Counts *lowerings* (exactly one
    ``jaxpr_to_mlir_module`` event per jit cache miss — the trace event fires
    more than once per miss, and the backend-compile event is absorbed by the
    persistent XLA cache; a retrace that hits the disk cache still counts,
    because on TPU the trace + lowering alone can stall a step and signals a
    cache-key instability that will eventually miss). Note EVERY compile in
    the region counts, including incidental op compiles like a first
    ``jnp.ones`` — build inputs before entering the region.

    Yields the entry snapshot of the counters; inspect
    :func:`compile_counts` afterwards for the exit values.
    """
    install()
    before = compile_counts()
    yield before
    after = compile_counts()
    new = after["lowerings"] - before["lowerings"]
    if new > max_compiles:
        hits = after["persistent_cache_hits"] - before["persistent_cache_hits"]
        backend = after["backend_compiles"] - before["backend_compiles"]
        raise RecompileError(
            f"{what!r} triggered {new} jit compilation(s), declared at most "
            f"{max_compiles} ({backend} backend compile(s), "
            f"{hits} persistent-cache hit(s)). Recompilation in a hot loop "
            "burns accelerator time: pre-warm every (shape, dtype, treedef) "
            "bucket before entering the region, pad batches to stable "
            "shapes, or raise max_compiles if the new program is intended."
        )


def assert_compile_count(fn, args_list, expected: int, what: str = "callable"):
    """Call ``fn(*args)`` for each args tuple; assert exactly ``expected``
    new compilations (lowerings) happened in total. Convenience for
    tests/benches."""
    before = compile_counts()["lowerings"]
    results = [fn(*args) for args in args_list]
    got = compile_counts()["lowerings"] - before
    if got != expected:
        raise RecompileError(
            f"{what!r} compiled {got} time(s) over {len(args_list)} call(s); "
            f"expected exactly {expected}"
        )
    return results


try:  # pytest fixture — importable from any conftest; no hard pytest dep
    import pytest
except ImportError:  # pragma: no cover
    pass
else:

    @pytest.fixture
    def compile_sentinel():
        """``no_recompile`` as a fixture:

        def test_steady_state(compile_sentinel):
            step(state, batch)  # warm
            with compile_sentinel(max_compiles=0, what="steady state"):
                step(state, batch)
        """
        install()
        return no_recompile
