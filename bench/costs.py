"""Operations and bytes of the work an iteration does, from its plan's
shapes -- the benchmark's own arithmetic, kept apart from the program's.

An iteration's plan is a list of ``(q_len, ctx_start, online, sampled)``
items: a prefill chunk of ``q_len`` tokens after ``ctx_start`` cached ones,
or a decode (``q_len`` 1); ``sampled`` when the item yields a token.  Only
useful work is counted: no padding to buckets, and causal attention over
the keys each query may see.  What a model family adds -- its matmuls per
token, its head and its kernels' work -- is its own ``costs.py``
(``bench/family.py``).
"""
from __future__ import annotations

import json
import os

from bench import family

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and HBM bytes/s of one chip, by ``device_kind``; a kind
    that is not in the table is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}")
    return table[device_kind]


def attention_pairs(q: int, ctx: int) -> int:
    """(query, key) pairs of one sequence's causal attention: query j of the
    chunk sees ctx + j + 1 keys."""
    return q * ctx + q * (q + 1) // 2


def _family(m: dict):
    return family.module(m["family"], "costs")


def kernels(m: dict) -> dict:
    """The kernels the model's family prices: op family -> cost function."""
    return _family(m).KERNELS


def kernel_cost(m: dict, kernel: str, items) -> tuple:
    """(flops, bytes) ``kernel`` needs for one plan, by the model's family;
    a kernel the family does not price is an error."""
    return kernels(m)[kernel](m, items)


def step_flops(m: dict, items) -> int:
    """Model operations of one plan: every token through the layers, the
    work of the family's kernels (attention), and the head on each token
    that is sampled."""
    fam = _family(m)
    tokens = sum(it[0] for it in items)
    kernel = sum(cost(m, items)[0] for cost in fam.KERNELS.values())
    head = fam.head_flops(m) * sum(1 for it in items if it[3])
    return tokens * fam.matmul_flops_per_token(m) + kernel + head
