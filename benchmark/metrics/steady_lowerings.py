"""Step program: programs lowered inside the window, from
``analysis/sentinel.py::compile_counts()`` before and after it. Should be 0:
every (shape, certificate) the window meets is warmed in set-up."""


def read(ctx):
    return float(ctx["lowerings"])
