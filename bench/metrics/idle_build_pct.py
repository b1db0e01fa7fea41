"""Model runner (inputs): share of the traced window in which the chip
stood idle under ``engine.build``, the host arrays of the fused batch (the
innermost program span over each idle instant, ``_spans.py``)."""
from bench.metrics import _spans


def read(r):
    return _spans.read(r, "build")
