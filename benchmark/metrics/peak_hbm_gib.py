"""Step program: the peak footprint on the fullest chip,
``peak_bytes_in_use + peak_bytes_reserved`` of ``device.memory_stats()``
(``run.py::device_peak_bytes``: the TPU runtime keeps a running program's
temporaries in reserved memory), read after the window and before the
reference runs. In ``egnn_mlip_mptrj.fill`` it is the worst-case bucket's
program (3.5% of steps, almost all padding); the other buckets need about
1 GiB."""


def read(ctx):
    return ctx["peak_bytes"] / 2**30 if ctx["peak_bytes"] else None
