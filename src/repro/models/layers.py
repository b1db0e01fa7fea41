"""Core neural-net layers shared by every architecture family.

Two attention execution paths:

* ``dense_attention`` — full-sequence self attention (training, encoding,
  monolithic/chunked prefill).  Causal + optional sliding-window masking.
* ``cached_attention_decode`` — single-token decode against a pre-allocated
  contiguous KV cache ``(B, cache_len, kv_heads, head_dim)`` with per-sequence
  lengths.  Sliding-window archs use a ring buffer (cache_len == window).

The *paged* physical layout (block tables) lives in ``repro.kvcache`` and the
Pallas kernels; these dense-layout functions double as the numerical oracle
for those kernels and as the lowering target for the multi-pod dry-run (the
roofline byte counts are identical between contiguous and paged layouts).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig

Params = Dict[str, jnp.ndarray]

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * w


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float
) -> jnp.ndarray:
    """x: (B, T, H, D); positions: (B, T) absolute token positions."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, T, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention parameter init
# ---------------------------------------------------------------------------


def init_attention(cfg: ModelConfig, key: jax.Array, dtype) -> Params:
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    scale = cfg.d_model**-0.5
    p = {
        "wq": jax.random.normal(kq, (cfg.d_model, cfg.num_heads, hd), dtype) * scale,
        "wk": jax.random.normal(kk, (cfg.d_model, cfg.num_kv_heads, hd), dtype)
        * scale,
        "wv": jax.random.normal(kv, (cfg.d_model, cfg.num_kv_heads, hd), dtype)
        * scale,
        "wo": jax.random.normal(ko, (cfg.num_heads, hd, cfg.d_model), dtype)
        * (cfg.num_heads * hd) ** -0.5,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads, hd), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads, hd), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads, hd), dtype)
    if cfg.o_bias:
        p["bo"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def _proj2d(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """(B,T,d) @ (d,H,hd) as a 2D matmul + reshape.

    §Perf hillclimb #1: the 3D einsum form made GSPMD pick pathological
    reshardings ("involuntary full rematerialization" — full f32 weight
    replication inside every layer iteration, +TBs of all-gather on the
    104B train config).  A 2D contraction with the head dims merged keeps
    the sharding propagation on well-trodden matmul paths; the reshape is
    sharding-preserving because the head axis is major in (H*hd).
    """
    d, h, hd = w.shape
    b, t, _ = x.shape
    return (x @ w.reshape(d, h * hd)).reshape(b, t, h, hd)


def project_qkv(
    cfg: ModelConfig, p: Params, x: jnp.ndarray, kv_src: Optional[jnp.ndarray] = None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """q from x; k,v from kv_src (defaults to x — self attention)."""
    kv_src = x if kv_src is None else kv_src
    q = _proj2d(x, p["wq"])
    k = _proj2d(kv_src, p["wk"])
    v = _proj2d(kv_src, p["wv"])
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    return q, k, v


def out_proj(p: Params, attn: jnp.ndarray) -> jnp.ndarray:
    h, hd, d = p["wo"].shape
    b, t = attn.shape[:2]
    out = attn.reshape(b, t, h * hd) @ p["wo"].reshape(h * hd, d)
    if "bo" in p:
        out = out + p["bo"]
    return out


# ---------------------------------------------------------------------------
# Attention core (grouped-query, masked)
# ---------------------------------------------------------------------------


def gqa_scores_softmax_values(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    logit_softcap: float = 0.0,
) -> jnp.ndarray:
    """q: (B,Tq,H,D); k/v: (B,Tk,Hkv,D); mask broadcastable to (B,1,Tq,Tk)."""
    b, tq, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, tq, hkv, g, d)
    scores = jnp.einsum(
        "bthgd,bshd->bhgts", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * (d**-0.5)
    if logit_softcap:
        scores = jnp.tanh(scores / logit_softcap) * logit_softcap
    if mask is not None:
        scores = jnp.where(mask[:, :, None, :, :] if mask.ndim == 4 else mask,
                           scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgts,bshd->bthgd", probs, v.astype(jnp.float32))
    return out.reshape(b, tq, h, d).astype(q.dtype)


def causal_mask(
    q_positions: jnp.ndarray,
    k_positions: jnp.ndarray,
    sliding_window: int = 0,
) -> jnp.ndarray:
    """(B,Tq),(B,Tk) -> bool (B,1,Tq,Tk): True = attend."""
    qp = q_positions[:, None, :, None]
    kp = k_positions[:, None, None, :]
    m = kp <= qp
    if sliding_window:
        m = m & (kp > qp - sliding_window)
    return m


# Above this many query tokens, full-sequence attention switches to the
# blockwise (flash-style) form: O(T^2) score tensors for 4k-32k sequences do
# not fit HBM.  On TPU the Pallas flash kernel replaces this path; the
# blockwise jnp form is its XLA-lowerable twin with identical numerics, used
# by the multi-pod dry-run and the CPU training loop.
BLOCKWISE_THRESHOLD = 1024
BLOCK_Q = 512
BLOCK_K = 1024


def blockwise_attention(
    q: jnp.ndarray,  # (B, Tq, H, D) roped
    k: jnp.ndarray,  # (B, Tk, Hkv, D) roped
    v: jnp.ndarray,
    q_positions: jnp.ndarray,  # (B, Tq)
    kv_positions: jnp.ndarray,  # (B, Tk)
    *,
    causal: bool,
    sliding_window: int = 0,
    logit_softcap: float = 0.0,
    block_q: int = BLOCK_Q,
    block_k: int = BLOCK_K,
) -> jnp.ndarray:
    """Flash-style online-softmax attention: scan over q blocks, inner scan
    over kv blocks with (m, l, acc) carry — peak memory O(bq·bk) per head."""
    b, tq, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    bq = min(block_q, tq)
    bk = min(block_k, tk)
    pad_q = (-tq) % bq
    pad_k = (-tk) % bk
    f32 = jnp.float32

    qp = jnp.pad(q_positions, ((0, 0), (0, pad_q)), constant_values=-(10**9))
    kp = jnp.pad(kv_positions, ((0, 0), (0, pad_k)), constant_values=10**9)
    qq = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kk = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vv = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))

    nq, nk = (tq + pad_q) // bq, (tk + pad_k) // bk
    # (nq, B, bq, Hkv, G, D)
    qb = qq.reshape(b, nq, bq, hkv, g, d).transpose(1, 0, 2, 3, 4, 5)
    kb = kk.reshape(b, nk, bk, hkv, d).transpose(1, 0, 2, 3, 4)
    vb = vv.reshape(b, nk, bk, hkv, d).transpose(1, 0, 2, 3, 4)
    qpb = qp.reshape(b, nq, bq).transpose(1, 0, 2)
    kpb = kp.reshape(b, nk, bk).transpose(1, 0, 2)
    scale = d**-0.5

    def q_step(_, qblk):
        qi, qpos = qblk  # (B,bq,Hkv,G,D), (B,bq)

        def kv_step(carry, kblk):
            m_p, l_p, acc = carry
            ki, vi, kpos = kblk
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qi.astype(f32), ki.astype(f32)
            ) * scale
            if logit_softcap:
                s = jnp.tanh(s / logit_softcap) * logit_softcap
            valid = kpos[:, None, :] <= (10**8)  # kill k padding
            if causal:
                valid = valid & (kpos[:, None, :] <= qpos[:, :, None])
            if sliding_window:
                valid = valid & (
                    kpos[:, None, :] > qpos[:, :, None] - sliding_window
                )
            s = jnp.where(valid[:, None, None, :, :], s, -1e30)
            m_c = jnp.max(s, axis=-1)
            m_n = jnp.maximum(m_p, m_c)
            p_ = jnp.exp(s - m_n[..., None])
            alpha = jnp.exp(m_p - m_n)
            l_n = alpha * l_p + jnp.sum(p_, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p_, vi.astype(f32)
            )
            return (m_n, l_n, acc), None

        m0 = jnp.full((b, hkv, g, bq), -1e30, f32)
        l0 = jnp.zeros((b, hkv, g, bq), f32)
        a0 = jnp.zeros((b, hkv, g, bq, d), f32)
        (m_f, l_f, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kb, vb, kpb))
        safe_l = jnp.where(l_f == 0, 1.0, l_f)
        out = (acc / safe_l[..., None]).astype(q.dtype)  # (B,Hkv,G,bq,D)
        return None, out.transpose(0, 3, 1, 2, 4)  # (B,bq,Hkv,G,D)

    _, outs = jax.lax.scan(q_step, None, (qb, qpb))  # (nq,B,bq,Hkv,G,D)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * bq, h, d)
    return out[:, :tq]


def dense_attention(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    kv_src: Optional[jnp.ndarray] = None,
    kv_positions: Optional[jnp.ndarray] = None,
    causal: Optional[bool] = None,
) -> jnp.ndarray:
    """Full-sequence attention (training / encoding / prefill)."""
    q, k, v = project_qkv(cfg, p, x, kv_src)
    q = apply_rope(q, positions, cfg.rope_theta)
    kv_pos = positions if kv_positions is None else kv_positions
    if kv_src is None:  # self-attention: rope keys too
        k = apply_rope(k, kv_pos, cfg.rope_theta)
    causal = cfg.causal if causal is None else causal
    if x.shape[1] > BLOCKWISE_THRESHOLD:
        from repro.distributed.act_sharding import constrain_heads

        q, k, v = constrain_heads(q), constrain_heads(k), constrain_heads(v)
        attn = blockwise_attention(
            q, k, v, positions, kv_pos,
            causal=causal,
            sliding_window=cfg.sliding_window,
            logit_softcap=cfg.logit_softcap,
        )
    else:
        mask = (
            causal_mask(positions, kv_pos, cfg.sliding_window) if causal else None
        )
        attn = gqa_scores_softmax_values(q, k, v, mask, cfg.logit_softcap)
    return out_proj(p, attn)


# ---------------------------------------------------------------------------
# Cached attention (contiguous layout, slot-position tracked)
#
# A KV cache is the triple (k, v, slot_pos):
#   k, v:     (B, C, Hkv, D)
#   slot_pos: (B, C) int32 — absolute token position stored in each slot,
#             -1 for empty.  Full caches map position p -> slot p; sliding-
#             window caches are ring buffers with slot = p % C.  Tracking
#             slot_pos explicitly makes masking exact for both layouts and
#             for chunked prefill, at negligible memory cost.
# ---------------------------------------------------------------------------


class KVCache:
    """Lightweight namespace for cache helpers (pytrees stay plain dicts)."""

    @staticmethod
    def init(batch, capacity, kv_heads, head_dim, dtype) -> Dict[str, jnp.ndarray]:
        return {
            "k": jnp.zeros((batch, capacity, kv_heads, head_dim), dtype),
            "v": jnp.zeros((batch, capacity, kv_heads, head_dim), dtype),
            "pos": jnp.full((batch, capacity), -1, jnp.int32),
        }


def write_kv(
    cache: Dict[str, jnp.ndarray],
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    positions: jnp.ndarray,
    valid: Optional[jnp.ndarray] = None,
) -> Dict[str, jnp.ndarray]:
    """Write L new tokens per sequence.

    k_new/v_new: (B, L, Hkv, D); positions: (B, L) absolute positions.
    valid: optional (B, L) bool — padded slots are not written.
    Slot index = position (full cache) or position % C (ring).
    """
    b, l = positions.shape
    c = cache["k"].shape[1]
    slots = positions % c
    rows = jnp.arange(b)[:, None]
    if valid is None:
        new_k = cache["k"].at[rows, slots].set(k_new)
        new_v = cache["v"].at[rows, slots].set(v_new)
        new_pos = cache["pos"].at[rows, slots].set(positions)
    else:
        # Route invalid writes to a scratch slot... simpler: where-merge.
        old_k = cache["k"][rows, slots]
        old_v = cache["v"][rows, slots]
        old_p = cache["pos"][rows, slots]
        vm = valid[..., None, None]
        new_k = cache["k"].at[rows, slots].set(jnp.where(vm, k_new, old_k))
        new_v = cache["v"].at[rows, slots].set(jnp.where(vm, v_new, old_v))
        new_pos = cache["pos"].at[rows, slots].set(
            jnp.where(valid, positions, old_p)
        )
    return {"k": new_k, "v": new_v, "pos": new_pos}


def attend_cache(
    cfg: ModelConfig,
    q: jnp.ndarray,  # (B, Tq, H, D) — already roped
    cache: Dict[str, jnp.ndarray],
    q_positions: jnp.ndarray,  # (B, Tq)
) -> jnp.ndarray:
    """Causal (+ sliding-window) attention of q against the cache contents."""
    slot_pos = cache["pos"]  # (B, C)
    qp = q_positions[:, None, :, None]  # (B,1,Tq,1)
    kp = slot_pos[:, None, None, :]  # (B,1,1,C)
    valid = (kp >= 0) & (kp <= qp)
    if cfg.sliding_window:
        valid = valid & (kp > qp - cfg.sliding_window)
    return gqa_scores_softmax_values(
        q, cache["k"], cache["v"], valid, cfg.logit_softcap
    )


def cached_attention(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,  # (B, L, d_model) — L=1 decode, L>1 prefill chunk
    cache: Dict[str, jnp.ndarray],
    positions: jnp.ndarray,  # (B, L) absolute positions of the new tokens
    valid: Optional[jnp.ndarray] = None,  # (B, L) padding mask
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Unified decode-step / chunked-prefill attention against a KV cache."""
    q, k, v = project_qkv(cfg, p, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    cache = write_kv(cache, k, v, positions, valid)
    attn = attend_cache(cfg, q, cache, positions)
    return out_proj(p, attn), cache


# ---------------------------------------------------------------------------
# Paged attention (shared block pool, block-table addressed)
#
# The paged cache for one layer is {"k", "v"}: (num_blocks, bs, Hkv·D) —
# each page a lane-dense tile of every KV head, head-major along the lanes
# (``kvcache.cache_ops``).  The split oracle paths below take one layer's
# slice of the engine-owned shared pool; the fused path takes the stacked
# pool and a layer index.  Sequences address it through ``block_tables``
# (B, M); slot for absolute position p is (table[p // bs], p % bs).  Keys
# are stored roped, exactly like the contiguous cache, so preempt/resume
# restores are bitwise exact.
#
# Padding: negative table entries are read as zeros on the gather path and
# *drop* writes on the scatter path; the engine additionally points padded
# batch rows at a dedicated scratch block so their shapes stay uniform.
#
# Tensor-parallel serving (DESIGN.md §11): when a ``mesh`` is passed, the
# pool's lane axis (whole heads, since the model axis divides Hkv) and the
# q/k/v head axes are constrained over the mesh's ``model`` axis, so GSPMD
# computes attention head-parallel; the attention output is
# gathered (an exact, arithmetic-free collective) before the output
# projection so no contraction ever runs over a sharded dim — sharded
# serving therefore emits bitwise-identical tokens.
# ---------------------------------------------------------------------------


def _kv_shard_mesh(pool: Dict[str, jnp.ndarray], mesh):
    """The mesh to shard this layer's paged attention over, or None when
    there is nothing to shard (no mesh, or a 1-way ``model`` axis).  The
    KV heads divide the axis: ``RealEngine`` refuses a mesh whose axis
    does not, never replicating in silence."""
    if mesh is None or "model" not in mesh.axis_names:
        return None
    return mesh if mesh.shape["model"] > 1 else None


def shard_paged_heads(x: jnp.ndarray, mesh, head_axis: int) -> jnp.ndarray:
    """Constrain the (kv-)head axis of ``x`` over the mesh's ``model`` axis
    — for a pool, its head-major lane axis, split into whole heads.

    No-op when ``mesh`` is None (see ``distributed.sharding.pool_pspec``
    for why head_dim is never split)."""
    if mesh is None:
        return x
    head_axis = head_axis % x.ndim
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec: list = [None] * x.ndim
    spec[head_axis] = "model"
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec))
    )


def replicate_on_mesh(x: jnp.ndarray, mesh) -> jnp.ndarray:
    """Gather ``x`` to every chip of ``mesh`` (exact — pure data movement).
    Applied to the attention output before ``out_proj`` so the h·hd
    contraction is never sharded (bitwise token identity, DESIGN.md §11)."""
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))


def paged_prefill_attention(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,  # (B, L, d_model) — prefill chunk
    pool: Dict[str, jnp.ndarray],
    block_tables: jnp.ndarray,  # (B, M)
    positions: jnp.ndarray,  # (B, L) absolute positions of the chunk
    mesh=None,  # tensor-parallel serving mesh (DESIGN.md §11)
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Chunked prefill against the shared paged pool.

    Scatters the chunk's roped KV into the pool, then attends causally over
    the gathered per-sequence context (the jnp path; block tables make the
    gather order identical to the logical position order, so numerics match
    the contiguous cache exactly).
    """
    from repro.kvcache.cache_ops import gather_paged, write_paged_chunk

    mesh = _kv_shard_mesh(pool, mesh)
    q, k, v = project_qkv(cfg, p, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = shard_paged_heads(q, mesh, 2)
    k = shard_paged_heads(k, mesh, 2)
    v = shard_paged_heads(v, mesh, 2)
    k_pool, v_pool = write_paged_chunk(
        pool["k"], pool["v"], k, v, block_tables, positions
    )
    k_pool = shard_paged_heads(k_pool, mesh, -1)
    v_pool = shard_paged_heads(v_pool, mesh, -1)
    bs = k_pool.shape[1]
    hd = q.shape[-1]
    max_ctx = block_tables.shape[1] * bs
    kk = gather_paged(k_pool, block_tables, max_ctx, hd)  # (B, T, Hkv, D)
    vv = gather_paged(v_pool, block_tables, max_ctx, hd)
    b = x.shape[0]
    kv_pos = jnp.broadcast_to(
        jnp.arange(max_ctx, dtype=jnp.int32), (b, max_ctx)
    )
    # Causal masking doubles as the validity mask: slots at kv_pos <= q_pos
    # were all written by this sequence; later slots (incl. scratch-padded
    # columns) are excluded.  Paged mode never runs sliding-window archs.
    mask = causal_mask(positions, kv_pos)
    attn = gqa_scores_softmax_values(q, kk, vv, mask, cfg.logit_softcap)
    attn = replicate_on_mesh(attn, mesh)
    return out_proj(p, attn), {"k": k_pool, "v": v_pool}


def paged_decode_attention(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,  # (B, 1, d_model)
    pool: Dict[str, jnp.ndarray],
    block_tables: jnp.ndarray,  # (B, M)
    positions: jnp.ndarray,  # (B, 1) — the new token's absolute position
    mesh=None,  # tensor-parallel serving mesh (DESIGN.md §11)
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One-token decode against the shared paged pool.

    Dispatches to the Pallas ragged kernel's ``Qmax = 1`` case on TPU
    (shard_mapped over KV heads when a mesh is given) and the ``cache_ops``
    jnp oracle on CPU (see ``repro.kernels.ops``).
    """
    from repro.kernels import ops as kernel_ops
    from repro.kvcache.cache_ops import append_paged

    mesh = _kv_shard_mesh(pool, mesh)
    q, k, v = project_qkv(cfg, p, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = shard_paged_heads(q, mesh, 2)
    k = shard_paged_heads(k, mesh, 2)
    v = shard_paged_heads(v, mesh, 2)
    k_pool, v_pool = append_paged(
        pool["k"], pool["v"], k[:, 0], v[:, 0], block_tables, positions[:, 0]
    )
    k_pool = shard_paged_heads(k_pool, mesh, -1)
    v_pool = shard_paged_heads(v_pool, mesh, -1)
    out = kernel_ops.paged_attention(
        q[:, 0], k_pool, v_pool, block_tables, positions[:, 0] + 1,
        logit_softcap=cfg.logit_softcap, mesh=mesh,
    )
    out = replicate_on_mesh(out, mesh)
    return out_proj(p, out[:, None]), {"k": k_pool, "v": v_pool}


# ---------------------------------------------------------------------------
# Fused ragged paged attention (one dispatch per mixed iteration, §12)
# ---------------------------------------------------------------------------


class RaggedMeta(NamedTuple):
    """Addressing metadata for one fused ragged token batch (DESIGN.md §12).

    The engine lowers an ``IterationPlan`` to a flattened token axis of
    length T (bucket-padded) over S sequences (bucket-padded, each with
    ``q_len`` <= Qmax queries) and resolves all indirection on the host —
    the device programs see only flat gather/scatter index vectors:

      dst_row/dst_off  (T,)       KV-pool scatter target per new token
                                  (padded tokens -> the scratch row)
      qpad             (S, Qmax)  flat token index per padded query slot
                                  (clamped; garbage slots are masked/unread)
      q_pos            (S, Qmax)  absolute position per padded query slot
      kv_lens          (S,)       valid context incl. this iteration
      unpad_seq/unpad_j (T,)      (sequence, slot) of each flat token, for
                                  gathering attention output back to flat
    """

    dst_row: jnp.ndarray
    dst_off: jnp.ndarray
    qpad: jnp.ndarray
    q_pos: jnp.ndarray
    kv_lens: jnp.ndarray
    unpad_seq: jnp.ndarray
    unpad_j: jnp.ndarray


def paged_ragged_attention(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,  # (1, T, d_model) — flattened ragged token batch
    pool: Dict[str, jnp.ndarray],  # stacked (layers, N, bs, Hkv·D) leaves
    layer: jnp.ndarray,  # () int32 — this layer's index in the stack
    block_tables: jnp.ndarray,  # (S, M)
    positions: jnp.ndarray,  # (1, T) absolute position of each flat token
    meta: RaggedMeta,
    mesh=None,  # tensor-parallel serving mesh (DESIGN.md §11)
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Fused mixed-batch attention against layer ``layer`` of the stacked
    paged pool, which it updates in place and returns whole.

    Projects/ropes the whole flattened batch at once, scatters every new
    token's KV into the layer in ONE fused write (prefill chunks and decode
    tokens alike — ``cache_ops.write_ragged`` on the stack seen as one
    pool of layers · N rows, a free reshape), then dispatches the single
    ragged paged-attention kernel on the same layer index: Pallas on TPU
    (shard_mapped over KV heads on a mesh), the ``cache_ops`` jnp oracle on
    CPU.  Nothing slices the layer out of the stack.  The padded (S, Qmax)
    query layout exists only inside the attention op; the output is
    gathered straight back to the flat token axis.
    """
    from repro.kernels import ops as kernel_ops
    from repro.kvcache.cache_ops import write_ragged

    mesh = _kv_shard_mesh(pool, mesh)
    q, k, v = project_qkv(cfg, p, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = shard_paged_heads(q, mesh, 2)
    k = shard_paged_heads(k, mesh, 2)
    v = shard_paged_heads(v, mesh, 2)
    # the stack seen as one pool of layers · N rows (a free reshape of its
    # row-major layout); this layer's rows start at layer · N
    stack = pool["k"].shape  # (layers, N, bs, Hkv·D)
    flat = (stack[0] * stack[1],) + stack[2:]
    rows = jnp.where(meta.dst_row >= 0, meta.dst_row + layer * stack[1], -1)
    k_pool, v_pool = write_ragged(
        pool["k"].reshape(flat), pool["v"].reshape(flat), k[0], v[0], rows,
        meta.dst_off,
    )
    k_pool = shard_paged_heads(k_pool.reshape(stack), mesh, -1)
    v_pool = shard_paged_heads(v_pool.reshape(stack), mesh, -1)
    q_pad = jnp.take(q[0], meta.qpad, axis=0)  # (S, Qmax, H, D)
    out = kernel_ops.ragged_paged_attention(
        q_pad, k_pool, v_pool, block_tables, meta.q_pos, meta.kv_lens,
        layer=layer, logit_softcap=cfg.logit_softcap, mesh=mesh,
    )
    out = replicate_on_mesh(out, mesh)
    flat = out[meta.unpad_seq, meta.unpad_j][None]  # (1, T, H, D)
    return out_proj(p, flat), {"k": k_pool, "v": v_pool}


# ---------------------------------------------------------------------------
# Cross-attention (VLM): q from text, static k/v from image embeddings
# ---------------------------------------------------------------------------


def cross_attention(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,
    cross_k: jnp.ndarray,
    cross_v: jnp.ndarray,
) -> jnp.ndarray:
    """x: (B,T,d); cross_k/v: (B,P,Hkv,D) precomputed from image embeds."""
    q = _proj2d(x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    attn = gqa_scores_softmax_values(q, cross_k, cross_v, None, cfg.logit_softcap)
    return out_proj(p, attn)


def project_cross_kv(
    cfg: ModelConfig, p: Params, img: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compute the static cross-attention k/v once per request (prefill)."""
    k = _proj2d(img, p["wk"])
    v = _proj2d(img, p["wv"])
    if "bk" in p:
        k = k + p["bk"]
        v = v + p["bv"]
    return k, v


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(cfg: ModelConfig, key: jax.Array, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = cfg.d_model**-0.5
    s_out = cfg.d_ff**-0.5
    p = {
        "w_up": jax.random.normal(k1, (cfg.d_model, cfg.d_ff), dtype) * s_in,
        "w_down": jax.random.normal(k2, (cfg.d_ff, cfg.d_model), dtype) * s_out,
    }
    if cfg.activation in ("swiglu", "geglu"):
        p["w_gate"] = jax.random.normal(k3, (cfg.d_model, cfg.d_ff), dtype) * s_in
    if cfg.mlp_bias:
        p["b_up"] = jnp.zeros((cfg.d_ff,), dtype)
        p["b_down"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def mlp(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    up = x @ p["w_up"]
    if "b_up" in p:
        up = up + p["b_up"]
    if cfg.activation == "swiglu":
        up = jax.nn.silu(x @ p["w_gate"]) * up
    elif cfg.activation == "geglu":
        up = jax.nn.gelu(x @ p["w_gate"]) * up
    else:
        up = jax.nn.gelu(up)
    down = up @ p["w_down"]
    if "b_down" in p:
        down = down + p["b_down"]
    return down
