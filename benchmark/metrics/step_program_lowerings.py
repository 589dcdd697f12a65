"""Step program: lowerings of the step function over the whole process (the
window's are ``steady_lowerings``), from the program's record of compile
events by function (``analysis/sentinel.py::compile_seconds``). The
reference's programs have other names (``_block_terms``) and are left out."""

from lib import spans


def read(ctx):
    record = spans.step_compiles()
    return float(record["lowerings"][0]) if record else None
