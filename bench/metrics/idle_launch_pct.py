"""Model runner (launch): share of the traced window in which the chip
stood idle under ``engine.dispatch`` or ``engine.sample``, the program
enqueues and the sampled tokens' readback (the innermost program span over
each idle instant, ``_spans.py``)."""
from bench.metrics import _spans


def read(r):
    return _spans.read(r, "launch")
