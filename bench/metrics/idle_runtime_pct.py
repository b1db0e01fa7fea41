"""Runtime: share of the traced window in which the chip stood idle under
``runtime.drain``, ``runtime.pump`` or ``runtime.publish``, or in the self
time of ``runtime.iteration`` and ``engine.step`` (the innermost program
span over each idle instant, ``_spans.py``)."""
from bench.metrics import _spans


def read(r):
    return _spans.read(r, "runtime")
