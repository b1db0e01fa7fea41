"""Kernels: the ragged paged attention kernel's share of its roofline over
the traced window (``_roofline.py``; its work is the family's
``KERNELS["ragged_paged_attention"]``)."""
from bench.metrics import _roofline


def read(r):
    return _roofline.read(r, "ragged_paged_attention")
