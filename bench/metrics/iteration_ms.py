"""Model runner: median length of the traced window's engine-loop turns
that ran a plan (the program's ``runtime.iteration`` spans with tokens),
on the trace's clock -- ``step_ms`` from inside, without no-work time."""
from bench.metrics import _spans


def read(r):
    return _spans.read(r, "iteration_ms")
