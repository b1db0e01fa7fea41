"""Operations and bytes of the test family: GQA attention over the cache;
per token the projections, the router over all experts and the k chosen
experts' SwiGLU."""
from __future__ import annotations

from bench.costs import attention_pairs


def attention_cost(m: dict, items) -> tuple:
    h, kvh, hd, L = m["heads"], m["kv_heads"], m["head_dim"], m["layers"]
    flops = nbytes = 0
    for q, ctx, _online, _sampled in items:
        flops += 4 * h * hd * attention_pairs(q, ctx)
        nbytes += 2 * (ctx + q) * kvh * hd * 4 + 2 * q * h * hd * 4
    return flops * L, nbytes * L


def matmul_flops_per_token(m: dict) -> int:
    d, hd, f = m["d_model"], m["head_dim"], m["d_ff"]
    h, kvh = m["heads"], m["kv_heads"]
    per_layer = (d * (h + 2 * kvh) * hd + h * hd * d + d * m["experts"]
                 + m["top_k"] * 3 * d * f)
    return 2 * per_layer * m["layers"]


def head_flops(m: dict) -> int:
    return 2 * m["d_model"] * m["vocab"]


KERNELS = {"ragged_paged_attention": attention_cost}
