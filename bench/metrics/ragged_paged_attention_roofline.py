"""Kernels: the ragged paged attention kernel's share of its roofline over
the traced window -- the least time its work needs (per iteration, the
larger of its operations over peak FLOP/s and its bytes over peak HBM
bandwidth, ``bench/costs.py attention_cost``) over the device time of its
events in the trace.  Only iterations wholly inside the trace are priced;
the kernel time also holds what aborted iterations ran, so the share errs
low, never high."""
from bench import costs

KERNEL = "ragged_paged_attention"


def read(r):
    if r.trace is None:
        return None
    kernel_s = r.trace["op_s"].get(KERNEL, 0.0)
    if kernel_s <= 0:
        return None
    a, b = r.trace_t
    least = 0.0
    for t0, t1, items, aborted in r.steps:
        if aborted or t0 < a or t1 > b:
            continue
        flops, nbytes = costs.attention_cost(r.m, items)
        least = least + max(flops / r.peaks["flops_per_s"],
                            nbytes / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
