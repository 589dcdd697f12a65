"""Step program: seconds of trace + lowering + backend compile (reads of the
compile cache included) of the step function over the whole process, from
``analysis/sentinel.py::compile_seconds``. Says the three parts on an earlier
line."""

from lib import spans


def read(ctx):
    record = spans.step_compiles()
    if not record:
        return None
    ctx["say"]("step program compile: " + ", ".join(
        f"{n} {name} {secs:.1f} s" for name, (n, secs) in record.items()))
    return sum(secs for _, secs in record.values())
