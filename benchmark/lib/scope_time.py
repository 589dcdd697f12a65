"""Device time under one named scope of the model, for per-layer metrics of a
single module below a conv layer (``lib/spans.py::module`` stops at the conv
layer's child): the self time of the device operations whose scope path holds
the given segments in a row, any differentiation pass, ms a step, mean over
the chips. None where the trace holds no such operation."""

from __future__ import annotations

from lib import spans


def _holds(path: tuple, segments: tuple) -> bool:
    # flax names a method other than __call__ "<module>.<method>"
    names = [seg.split(".")[0] for seg in path]
    k = len(segments)
    return any(tuple(names[i:i + k]) == segments for i in range(len(names) - k + 1))


def scope_ms(ctx, *segments: str):
    def total():
        loaded, events = spans.load(ctx), ctx.get("events")
        if not loaded or not events or not events.get("devices") or not ctx.get("steps"):
            return None
        inside, found, ns = {}, False, 0.0
        for dev_events in events["devices"].values():
            selfs = spans.self_times([(s, s + d) for _, s, d in dev_events])
            for (text, _, _), t in zip(dev_events, selfs):
                if text not in inside:
                    scope = spans.scope_of(text, loaded["scopes"])
                    inside[text] = bool(scope and _holds(scope["path"], segments))
                if inside[text]:
                    found, ns = True, ns + t
        return 1e-6 * ns / (len(events["devices"]) * ctx["steps"]) if found else None

    return spans._kept(ctx, "_scope_ms:" + "/".join(segments), total)


def roofline_share(ctx, ms, work) -> float | None:
    """100 x the least time the chip could take for ``work`` (``(FLOP, bytes)``
    of the window's steps) over ``ms`` a step of device time."""
    peaks = ctx["peaks"]
    if not ms or not peaks:
        return None
    flop, nbytes = work
    t_flop = flop / peaks["bf16_flops_per_s"] / ctx["chips"]
    t_byte = nbytes / peaks["hbm_bytes_per_s"] / ctx["chips"]
    ctx["say"](f"roofline of a scope: needed {flop:.4g} FLOP ({t_flop:.4g} s), {nbytes:.4g} B "
               f"({t_byte:.4g} s) against {1e-3 * ms * ctx['steps']:.4g} s of device time; the "
               f"{'bytes' if t_byte >= t_flop else 'FLOP'} bound applies")
    return 100.0 * max(t_flop, t_byte) / (1e-3 * ms * ctx["steps"])


def real_sizes(ctx) -> tuple[int, int]:
    """Real atoms and edges of the window's batches."""
    return sum(c[1] for c in ctx["collated"]), sum(c[2] for c in ctx["collated"])
