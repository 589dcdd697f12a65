"""Kernels: the least time the chip could take for the window's steps over the
time its devices were busy. Least time = max(needed FLOP / peak FLOP/s,
needed bytes / peak bytes/s); needed work from ``benchmark/ops/<arch>.py``
over the REAL atoms and edges of the window's batches; peaks from
``benchmark/peaks.json`` (the published bf16 peak for every precision, so an
fp32 ``highest`` cell, six passes, cannot pass about a sixth on the compute
side). Says on an earlier line which bound applied."""


def read(ctx):
    t, peaks = ctx["trace"], ctx["peaks"]
    if not t or not t.get("busy_s") or not peaks:
        return None
    nodes = sum(c[1] for c in ctx["collated"])
    edges = sum(c[2] for c in ctx["collated"])
    graphs = sum(c[3] for c in ctx["collated"])
    flop, nbytes = ctx["ops"].needed(ctx["config"], nodes, edges, graphs)
    t_flop = flop / peaks["bf16_flops_per_s"] / ctx["chips"]
    t_byte = nbytes / peaks["hbm_bytes_per_s"] / ctx["chips"]
    ctx["say"](f"roofline: needed {flop:.4g} FLOP ({t_flop:.4g} s a chip), {nbytes:.4g} B "
               f"({t_byte:.4g} s a chip); the {'bytes' if t_byte >= t_flop else 'FLOP'} bound applies")
    return 100.0 * max(t_flop, t_byte) / t["busy_s"]
