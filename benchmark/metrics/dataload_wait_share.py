"""Host input pipeline: the share of the window the epoch loop spent waiting
for its next batch — the program's own ``dataload`` timer
(``utils/tracer.py``, started and stopped in ``train/loop.py::_timed_iter``)
over the window."""


def read(ctx):
    return 100.0 * ctx["timers"]["dataload"] / ctx["window_s"]
