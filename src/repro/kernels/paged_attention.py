"""Ragged paged attention — Pallas TPU kernel.

One dispatch serves every sequence of a mixed iteration: prefill chunks
(``q_len`` up to Qmax queries) and decodes (``q_len = 1``) share one grid
(DESIGN.md §12).  KV lives as *pages* in a global block pool addressed via
block tables; the kernel streams KV pages HBM→VMEM one page per grid step,
using scalar-prefetched block tables in the BlockSpec index maps (the
TPU-native analogue of the GPU gather: the DMA engine performs the
indirection, no materialized gather).

Layout: pools (L, N, page, Hkv·D) — every layer of a segment stacked, and
each page a lane-dense ``(page, Hkv·D)`` tile holding all KV heads of its
tokens, head-major along the lanes.  That is the chip's own row-major
tiled layout for the pool, so no program ever relays it out, and a grid
step's K/V block is one page of one layer: the layer index is a
scalar-prefetch argument of the index map, so the kernel DMAs layer
``layer``'s pages straight out of the stack and the caller never slices
it.  The query arrives grouped by the wrapper as (S, Hkv, Qmax·G, D)
(G = query heads per KV head — GQA group), so the kernel never reshapes;
per-row query positions ride along as an (S, Qmax·G, 1) block.  Grid
(S, Qmax/QB, M): each step runs every KV head of one page against its own
query group; query rows are tiled QB query slots at a time so a long
prefill chunk stays inside the chip's scoped VMEM, and M = max pages per
sequence is the innermost, sequential axis with f32 online-softmax
accumulators in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Query slots per row tile: a multiple of 8, so a tile of QB·G rows obeys
# the (8, 128) rule for any GQA group G; Qmax = 512 untiled is over v5e's
# 16 MB scoped VMEM limit.
QUERY_TILE = 128
# VMEM bytes a query tile may take across all KV heads of a step: the
# double-buffered query and output blocks, the accumulator and the two
# lane-padded softmax statistics, about six lane-padded rows per row.
TILE_VMEM_BYTES = 8 * 2**20


def _query_tile(qmax: int, rows_per_slot: int, d: int) -> int:
    """Query slots per tile: QUERY_TILE, fewer where many KV heads times a
    wide group would overflow ``TILE_VMEM_BYTES`` (e.g. 8 KV heads of 7
    query heads on one chip), never below 8."""
    per_slot = rows_per_slot * max(d, 128) * 4 * 6
    fit = max(8, TILE_VMEM_BYTES // per_slot // 8 * 8)
    return min(qmax, QUERY_TILE, fit)


def _dot_precision():
    """The in-kernel dots follow ``jax.default_matmul_precision`` as XLA's
    matmuls do: "highest"/"float32" contract in float32, anything else
    takes Mosaic's default (bf16 passes for float32 operands)."""
    if jax.config.jax_default_matmul_precision in ("highest", "float32"):
        return jax.lax.Precision.HIGHEST
    return None


def _ragged_kernel(
    layer_ref,  # (1,) scalar-prefetch (SMEM): the pool layer to read
    block_tables_ref,  # (S, M) scalar-prefetch (SMEM)
    kv_lens_ref,  # (S,) scalar-prefetch (SMEM)
    q_ref,  # (1, Hkv, R, D), R = QB*G rows of this query tile per head
    q_pos_ref,  # (1, R, 1) absolute position of each query row
    k_ref,  # (1, 1, page, Hkv*D)
    v_ref,  # (1, 1, page, Hkv*D)
    o_ref,  # (1, Hkv, R, D)
    acc_ref,  # (Hkv, R, D) f32
    m_ref,  # (Hkv, R, 1) f32
    l_ref,  # (Hkv, R, 1) f32
    *,
    scale: float,
    page: int,
    pages_per_seq: int,
    logit_softcap: float,
    precision,
):
    del layer_ref  # consumed by the index maps
    s = pl.program_id(0)
    mi = pl.program_id(2)
    hkv, _, d = acc_ref.shape

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = kv_lens_ref[s]
    page_start = mi * page

    @pl.when(page_start < kv_len)
    def _compute():
        k_all = k_ref[0, 0].astype(jnp.float32)  # (page, Hkv*D)
        v_all = v_ref[0, 0].astype(jnp.float32)
        tok = page_start + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        # causal per query row; the kv_len bound only matters for padded
        # query rows whose garbage positions could otherwise reach junk
        # beyond the sequence
        keep = (tok <= q_pos_ref[0]) & (tok < kv_len)  # (R, page)
        for h in range(hkv):  # each KV head against its own query group
            q = q_ref[0, h].astype(jnp.float32)  # (R, D)
            k = k_all[:, h * d : (h + 1) * d]  # (page, D)
            v = v_all[:, h * d : (h + 1) * d]
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32,
            ) * scale  # (R, page)
            if logit_softcap:
                sc = jnp.tanh(sc / logit_softcap) * logit_softcap
            sc = jnp.where(keep, sc, NEG_INF)

            m_prev, l_prev = m_ref[h], l_ref[h]
            m_cur = jnp.max(sc, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(sc - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32,
            )
            m_ref[h] = m_new

    @pl.when(mi == pages_per_seq - 1)
    def _finalize():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "logit_softcap"))
def ragged_paged_attention(
    q: jnp.ndarray,  # (S, Qmax, H, D) — per-sequence padded query tokens
    k_pool: jnp.ndarray,  # (L, N, page, Hkv*D) — layers stacked
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # (S, M) int32, -1 padded
    q_positions: jnp.ndarray,  # (S, Qmax) absolute position of each query
    kv_lens: jnp.ndarray,  # (S,) valid tokens (incl. this iteration's)
    *,
    layer: jnp.ndarray,  # () int32 — the layer of the stack to attend
    logit_softcap: float = 0.0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused ragged paged attention over layer ``layer`` of the stacked
    pool.  Padded query slots (their positions are garbage) are masked by
    the causal + kv_len bound and their output rows are never read back.
    Decode is the ``Qmax = 1`` case.  Returns (S, Qmax, H, D)."""
    s, qmax, h, d = q.shape
    _, n, page, width = k_pool.shape
    hkv = width // d
    g = h // hkv
    m = block_tables.shape[1]
    qb = _query_tile(qmax, hkv * g, d)
    qpad = -(-qmax // qb) * qb
    if qpad != qmax:  # pad slots: position -1 masks every key, never read
        q = jnp.pad(q, ((0, 0), (0, qpad - qmax), (0, 0), (0, 0)))
        q_positions = jnp.pad(
            q_positions, ((0, 0), (0, qpad - qmax)), constant_values=-1
        )
    r = qb * g  # rows per query tile and KV head

    # grouped-KV-head-major rows: row qi*G + gi of head block h_ is query
    # slot qi of query head h_*G + gi
    qg = q.reshape(s, qpad, hkv, g, d).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(s, hkv, qpad * g, d)
    q_pos = jnp.repeat(q_positions.astype(jnp.int32), g, axis=1)[:, :, None]
    tables = jnp.maximum(block_tables, 0).astype(jnp.int32)
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)

    def kv_map(s_, qi, mi, ly, bt, kl):
        return (ly[0], bt[s_, mi], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s, qpad // qb, m),
        in_specs=[
            pl.BlockSpec(
                (1, hkv, r, d), lambda s_, qi, mi, ly, bt, kl: (s_, 0, qi, 0)
            ),
            pl.BlockSpec(
                (1, r, 1), lambda s_, qi, mi, ly, bt, kl: (s_, qi, 0)
            ),
            pl.BlockSpec((1, 1, page, width), kv_map),
            pl.BlockSpec((1, 1, page, width), kv_map),
        ],
        out_specs=pl.BlockSpec(
            (1, hkv, r, d), lambda s_, qi, mi, ly, bt, kl: (s_, 0, qi, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((hkv, r, d), jnp.float32),
            pltpu.VMEM((hkv, r, 1), jnp.float32),
            pltpu.VMEM((hkv, r, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _ragged_kernel, scale=d**-0.5, page=page, pages_per_seq=m,
            logit_softcap=logit_softcap, precision=_dot_precision(),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, hkv, qpad * g, d), q.dtype),
        interpret=interpret,
    )(layer, tables, kv_lens.astype(jnp.int32), qg, q_pos, k_pool, v_pool)
    out = out.reshape(s, hkv, qpad, g, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(s, qpad, h, d)[:, :qmax]


def paged_attention(
    q: jnp.ndarray,  # (B, H, D) — one decode token per sequence
    k_pool: jnp.ndarray,  # (N, page, Hkv*D) — one layer's pool
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, M) int32, -1 padded
    seq_lens: jnp.ndarray,  # (B,) int32 — valid tokens (incl. current)
    *,
    logit_softcap: float = 0.0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged decode attention: the ``Qmax = 1`` case of the ragged kernel
    over a one-layer stack, the query sitting at position ``seq_len - 1``.
    Returns (B, H, D)."""
    out = ragged_paged_attention(
        q[:, None], k_pool[None], v_pool[None], block_tables,
        (seq_lens - 1)[:, None], seq_lens, layer=jnp.int32(0),
        logit_softcap=logit_softcap, interpret=interpret,
    )
    return out[:, 0]


def ragged_paged_attention_sharded(
    q: jnp.ndarray,  # (S, Qmax, H, D)
    k_pool: jnp.ndarray,  # (L, N, page, Hkv*D)
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # (S, M)
    q_positions: jnp.ndarray,  # (S, Qmax)
    kv_lens: jnp.ndarray,  # (S,)
    mesh,
    *,
    layer: jnp.ndarray,  # () int32
    logit_softcap: float = 0.0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Tensor-parallel ragged attention (DESIGN.md §11/§12): shard_maps the
    kernel over the mesh's ``model`` axis — each chip runs the grid on its
    local Hkv/tp heads of every page: the page's lanes are head-major, so
    a contiguous 1/tp of them is Hkv/tp whole heads.  The layer index,
    block tables, positions and lengths replicate, and no collective runs
    inside: GQA groups stay local because the query-head axis is grouped
    KV-head-major, so sharding H into contiguous chunks of H/tp keeps each
    chip's queries paired with its own KV heads.  The heads must divide
    the axis (``shard_map`` refuses otherwise; the engine refuses such a
    mesh when it is built)."""
    from jax.sharding import PartitionSpec as P

    def fn(q, k_pool, v_pool, layer, block_tables, q_positions, kv_lens):
        return ragged_paged_attention(
            q, k_pool, v_pool, block_tables, q_positions, kv_lens,
            layer=layer, logit_softcap=logit_softcap, interpret=interpret,
        )

    heads = P(None, None, "model", None)
    pool = P(None, None, None, "model")
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(heads, pool, pool, P(), P(None, None), P(None, None),
                  P(None)),
        out_specs=heads,
        check_vma=False,
    )(q, k_pool, v_pool, layer, block_tables, q_positions, kv_lens)
