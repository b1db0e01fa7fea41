"""Ragged paged attention — Pallas TPU kernel.

One dispatch serves every sequence of a mixed iteration: prefill chunks
(``q_len`` up to Qmax queries) and decodes (``q_len = 1``) share one grid
(DESIGN.md §12).  KV lives as *pages* in a global block pool addressed via
block tables; the kernel streams KV pages HBM→VMEM one page per grid step,
using scalar-prefetched block tables in the BlockSpec index maps (the
TPU-native analogue of the GPU gather: the DMA engine performs the
indirection, no materialized gather).

Layout: pools (N, Hkv, page, D) — KV heads outside the page, so a grid
step's K/V block is ``(page, D)`` of one head and its last two dims are the
array's own (the chip's (8, 128) tiling rule).  The query arrives grouped
by the wrapper as (S, Hkv, Qmax·G, D) (G = query heads per KV head — GQA
group), so the kernel never reshapes; per-row query positions ride along
as an (S, Qmax·G, 1) block.  Grid (S, Hkv, Qmax/QB, M): query rows are
tiled QB query slots (QB·G rows) at a time so a long prefill chunk stays
inside the chip's scoped VMEM, and M = max pages per sequence is the
innermost, sequential axis with f32 online-softmax accumulators in VMEM
scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Query slots per row tile: a multiple of 8, so a tile of QB·G rows obeys
# the (8, 128) rule for any GQA group G.  At QB = 128 an f32 tile of a
# 7-wide group keeps the kernel near 3 MB of VMEM; Qmax = 512 untiled
# needs 22 MB, over v5e's 16 MB scoped limit.
QUERY_TILE = 128


def _dot_precision():
    """The in-kernel dots follow ``jax.default_matmul_precision`` as XLA's
    matmuls do: "highest"/"float32" contract in float32, anything else
    takes Mosaic's default (bf16 passes for float32 operands)."""
    if jax.config.jax_default_matmul_precision in ("highest", "float32"):
        return jax.lax.Precision.HIGHEST
    return None


def _ragged_kernel(
    block_tables_ref,  # (S, M) scalar-prefetch (SMEM)
    kv_lens_ref,  # (S,) scalar-prefetch (SMEM)
    q_ref,  # (1, 1, R, D), R = QB*G rows of this query tile
    q_pos_ref,  # (1, R, 1) absolute position of each query row
    k_ref,  # (1, 1, page, D)
    v_ref,  # (1, 1, page, D)
    o_ref,  # (1, 1, R, D)
    acc_ref,  # (R, D) f32
    m_ref,  # (R, 1) f32
    l_ref,  # (R, 1) f32
    *,
    scale: float,
    page: int,
    pages_per_seq: int,
    logit_softcap: float,
    precision,
):
    s = pl.program_id(0)
    mi = pl.program_id(3)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = kv_lens_ref[s]
    page_start = mi * page

    @pl.when(page_start < kv_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (R, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (page, D)
        v = v_ref[0, 0].astype(jnp.float32)
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32,
        ) * scale  # (R, page)
        if logit_softcap:
            sc = jnp.tanh(sc / logit_softcap) * logit_softcap
        tok = page_start + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        # causal per query row; the kv_len bound only matters for padded
        # query rows whose garbage positions could otherwise reach junk
        # beyond the sequence
        keep = (tok <= q_pos_ref[0]) & (tok < kv_len)  # (R, page)
        sc = jnp.where(keep, sc, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(sc, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(mi == pages_per_seq - 1)
    def _finalize():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "logit_softcap"))
def ragged_paged_attention(
    q: jnp.ndarray,  # (S, Qmax, H, D) — per-sequence padded query tokens
    k_pool: jnp.ndarray,  # (N, Hkv, page, D)
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # (S, M) int32, -1 padded
    q_positions: jnp.ndarray,  # (S, Qmax) absolute position of each query
    kv_lens: jnp.ndarray,  # (S,) valid tokens (incl. this iteration's)
    *,
    logit_softcap: float = 0.0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused ragged paged attention over the paged pool.  Padded query
    slots (their positions are garbage) are masked by the causal + kv_len
    bound and their output rows are never read back.  Decode is the
    ``Qmax = 1`` case.  Returns (S, Qmax, H, D)."""
    s, qmax, h, d = q.shape
    n, hkv, page, _ = k_pool.shape
    g = h // hkv
    m = block_tables.shape[1]
    qb = min(qmax, QUERY_TILE)
    qpad = -(-qmax // qb) * qb
    if qpad != qmax:  # pad slots: position -1 masks every key, never read
        q = jnp.pad(q, ((0, 0), (0, qpad - qmax), (0, 0), (0, 0)))
        q_positions = jnp.pad(
            q_positions, ((0, 0), (0, qpad - qmax)), constant_values=-1
        )
    r = qb * g  # rows per query tile

    # grouped-KV-head-major rows: row qi*G + gi of head block h_ is query
    # slot qi of query head h_*G + gi
    qg = q.reshape(s, qpad, hkv, g, d).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(s, hkv, qpad * g, d)
    q_pos = jnp.repeat(q_positions.astype(jnp.int32), g, axis=1)[:, :, None]
    tables = jnp.maximum(block_tables, 0).astype(jnp.int32)

    def kv_map(s_, h_, qi, mi, bt, kl):
        return (bt[s_, mi], h_, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, hkv, qpad // qb, m),
        in_specs=[
            pl.BlockSpec(
                (1, 1, r, d), lambda s_, h_, qi, mi, bt, kl: (s_, h_, qi, 0)
            ),
            pl.BlockSpec(
                (1, r, 1), lambda s_, h_, qi, mi, bt, kl: (s_, qi, 0)
            ),
            pl.BlockSpec((1, 1, page, d), kv_map),
            pl.BlockSpec((1, 1, page, d), kv_map),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, r, d), lambda s_, h_, qi, mi, bt, kl: (s_, h_, qi, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((r, d), jnp.float32),
            pltpu.VMEM((r, 1), jnp.float32),
            pltpu.VMEM((r, 1), jnp.float32),
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
    )(tables, kv_lens.astype(jnp.int32), qg, q_pos, k_pool, v_pool)
    out = out.reshape(s, hkv, qpad, g, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(s, qpad, h, d)[:, :qmax]


def paged_attention(
    q: jnp.ndarray,  # (B, H, D) — one decode token per sequence
    k_pool: jnp.ndarray,  # (N, Hkv, page, D)
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, M) int32, -1 padded
    seq_lens: jnp.ndarray,  # (B,) int32 — valid tokens (incl. current)
    *,
    logit_softcap: float = 0.0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged decode attention: the ``Qmax = 1`` case of the ragged kernel,
    the query sitting at position ``seq_len - 1``.  Returns (B, H, D)."""
    out = ragged_paged_attention(
        q[:, None], k_pool, v_pool, block_tables, (seq_lens - 1)[:, None],
        seq_lens, logit_softcap=logit_softcap, interpret=interpret,
    )
    return out[:, 0]


def ragged_paged_attention_sharded(
    q: jnp.ndarray,  # (S, Qmax, H, D)
    k_pool: jnp.ndarray,  # (N, Hkv, page, D)
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # (S, M)
    q_positions: jnp.ndarray,  # (S, Qmax)
    kv_lens: jnp.ndarray,  # (S,)
    mesh,
    *,
    logit_softcap: float = 0.0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Tensor-parallel ragged attention (DESIGN.md §11/§12): shard_maps the
    kernel over the mesh's ``model`` axis — each chip runs the grid on its
    local Hkv/tp heads of every page.  Block tables, positions and lengths
    replicate, and no collective runs inside: GQA groups stay local because
    the query-head axis is grouped KV-head-major, so sharding H into
    contiguous chunks of H/tp keeps each chip's queries paired with its own
    KV heads.  The heads must divide the axis (``shard_map`` refuses
    otherwise; the engine refuses such a mesh when it is built)."""
    from jax.sharding import PartitionSpec as P

    fn = functools.partial(
        ragged_paged_attention, logit_softcap=logit_softcap,
        interpret=interpret,
    )
    heads = P(None, None, "model", None)
    pool = P(None, "model", None, None)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(heads, pool, pool, P(None, None), P(None, None), P(None)),
        out_specs=heads,
        check_vma=False,
    )(q, k_pool, v_pool, block_tables, q_positions, kv_lens)
