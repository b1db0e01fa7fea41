"""Scheduler: share of the traced window in which the chip stood idle
under the program's ``sched.plan``, ``sched.commit`` or ``engine.events``
span (the innermost program span over each idle instant, ``_spans.py``)."""
from bench.metrics import _spans


def read(r):
    return _spans.read(r, "sched")
