"""Kernels: a kernel's share of its roofline over the traced window -- the
least time its work needs (per iteration, the larger of its operations over
peak FLOP/s and its bytes over peak HBM bandwidth, priced by the model
family's ``costs.py``, ``bench/costs.py kernel_cost``) over the device time
of its events in the trace.  Only iterations wholly inside the trace are
priced; the kernel time also holds what aborted iterations ran, so the
share errs low, never high.  None without a trace, without the kernel's
events, or where the family does not price the kernel.

A kernel's reader is then three lines::

    from bench.metrics import _roofline

    def read(r):
        return _roofline.read(r, "<op family>")
"""
from __future__ import annotations

from bench import costs


def read(r, kernel: str):
    if r.trace is None or kernel not in costs.kernels(r.m):
        return None
    kernel_s = r.trace["op_s"].get(kernel, 0.0)
    if kernel_s <= 0:
        return None
    a, b = r.trace_t
    least = 0.0
    for t0, t1, items, aborted in r.steps:
        if aborted or t0 < a or t1 > b:
            continue
        flops, nbytes = costs.kernel_cost(r.m, kernel, items)
        least = least + max(flops / r.peaks["flops_per_s"],
                            nbytes / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
