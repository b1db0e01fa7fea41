"""Runtime: share of the traced window in which the chip stood idle with
nothing schedulable -- under ``runtime.idle`` or ``runtime.backoff``, or in
a loop turn whose plan was empty -- which host speed cannot remove
(``_spans.py``)."""
from bench.metrics import _spans


def read(r):
    return _spans.read(r, "no_work")
