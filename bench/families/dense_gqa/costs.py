"""Operations and bytes of the dense GQA family (``bench/family.py``): only
useful work is counted, no padding to buckets, and causal attention over
the keys each query may see."""
from __future__ import annotations

from bench.costs import attention_pairs


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


def head_flops(m: dict) -> int:
    """Operations of the head on one sampled token."""
    return 2 * m["d_model"] * m["vocab"]


KERNELS = {"ragged_paged_attention": attention_cost}
