"""Operations and bytes of the work an iteration does, from its plan's
shapes -- the benchmark's own arithmetic, kept apart from the program's.

An iteration's plan is a list of ``(q_len, ctx_start, online, sampled)``
items: a prefill chunk of ``q_len`` tokens after ``ctx_start`` cached ones,
or a decode (``q_len`` 1); ``sampled`` when the item yields a token.  Only useful work is counted: no padding to buckets,
and causal attention over the keys each query may see.
"""
from __future__ import annotations

import json
import os

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


def attention_cost(m: dict, items, kv_bytes: int = 4) -> tuple:
    """(flops, bytes) the ragged attention kernel needs for one plan, over
    all layers: QK^T and PV on every visible pair; reading each sequence's
    KV once, its queries once and writing its outputs once."""
    h, kvh, hd, L = m["heads"], m["kv_heads"], m["head_dim"], m["layers"]
    flops = nbytes = 0
    for q, ctx, _online, _sampled in items:
        flops += 4 * h * hd * attention_pairs(q, ctx)
        nbytes += 2 * (ctx + q) * kvh * hd * kv_bytes + 2 * q * h * hd * 4
    return flops * L, nbytes * L


def matmul_flops_per_token(m: dict) -> int:
    """Weight matmul operations of one token through every layer."""
    d, hd, f = m["d_model"], m["head_dim"], m["d_ff"]
    h, kvh = m["heads"], m["kv_heads"]
    per_layer = d * (h + 2 * kvh) * hd + h * hd * d + 3 * d * f
    return 2 * per_layer * m["layers"]


def step_flops(m: dict, items) -> int:
    """Model operations of one plan: every token through the layers, the
    attention it needs, and the head on each token that is sampled."""
    tokens = sum(it[0] for it in items)
    attn, _ = attention_cost(m, items)
    head = 2 * m["d_model"] * m["vocab"] * sum(1 for it in items if it[3])
    return tokens * matmul_flops_per_token(m) + attn + head
