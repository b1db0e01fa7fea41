"""Model runner (transfers): share of the traced window in which the chip
stood idle under ``engine.put``, the fused batch's transfers to the device
(the innermost program span over each idle instant, ``_spans.py``)."""
from bench.metrics import _spans


def read(r):
    return _spans.read(r, "put")
