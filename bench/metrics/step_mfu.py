"""Whole step: model operations of the window's completed iterations
(``bench/costs.py step_flops``: every token through every layer, the work
of the family's kernels -- its causal attention -- and the head on each
sampled token) over the window's length times the chip's peak."""
from bench import costs


def read(r):
    flops = sum(costs.step_flops(r.m, items)
                for t0, t1, items, aborted in r.steps
                if not aborted and r.in_window(t1))
    return 100.0 * flops / (r.seconds * r.peaks["flops_per_s"])
