"""JAX ops over the *paged* physical KV layout.

Physical pool per layer: ``k_pool, v_pool: (num_blocks, block_size, Hkv·D)``
— one page is a lane-dense ``(block_size, Hkv·D)`` tile holding every KV
head of its tokens, head-major along the lanes: the chip's own row-major
tiled layout, which the Pallas kernel DMAs as one block and no program
relays out (DESIGN.md §12).  The serving pools stack these per layer,
``(layers, num_blocks, block_size, Hkv·D)``; the fused program writes a
layer of the stack through its rows in the flattened stack and the kernel
reads it by index.  Sequences address it through
``block_tables: (B, max_blocks_per_seq) int32`` (-1 padded) +
``seq_lens: (B,)``.

These ops are the pure-jnp oracle for the Pallas ``paged_attention`` kernel
and the physical half of the block manager's accounting.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def append_paged(
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    k_new: jnp.ndarray,  # (B, Hkv, D) — one token per sequence
    v_new: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, M)
    seq_lens: jnp.ndarray,  # (B,) length BEFORE the append
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter one new token per sequence into its tail block.

    Negative (padding) table entries drop the write instead of aliasing a
    real block — padded batch rows are harmless by construction."""
    b = k_new.shape[0]
    bs = k_pool.shape[1]
    block_idx = seq_lens // bs
    offset = seq_lens % bs
    rows = jnp.take_along_axis(block_tables, block_idx[:, None], axis=1)[:, 0]
    rows = jnp.where(rows >= 0, rows, k_pool.shape[0])  # pad -> OOB -> drop
    k_pool = k_pool.at[rows, offset].set(k_new.reshape(b, -1), mode="drop")
    v_pool = v_pool.at[rows, offset].set(v_new.reshape(b, -1), mode="drop")
    return k_pool, v_pool


def write_paged_chunk(
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    k_new: jnp.ndarray,  # (B, L, Hkv, D) — chunked-prefill tokens
    v_new: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, M)
    positions: jnp.ndarray,  # (B, L) absolute token positions of the chunk
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter a multi-token prefill chunk into each sequence's blocks.

    The engine allocates blocks covering every position before dispatch, so
    each (position // block_size) indexes a valid table column.  Positions
    landing on padding (negative table entries, or beyond the table width)
    drop the write rather than aliasing a real block.
    """
    b, l = positions.shape
    bs = k_pool.shape[1]
    m = block_tables.shape[1]
    in_table = positions // bs < m  # (B, L)
    block_idx = jnp.clip(positions // bs, 0, m - 1)
    offsets = positions % bs
    rows = jnp.take_along_axis(block_tables, block_idx, axis=1)  # (B, L)
    rows = jnp.where((rows >= 0) & in_table, rows, k_pool.shape[0])  # drop
    k_pool = k_pool.at[rows, offsets].set(k_new.reshape(b, l, -1), mode="drop")
    v_pool = v_pool.at[rows, offsets].set(v_new.reshape(b, l, -1), mode="drop")
    return k_pool, v_pool


def write_ragged(
    k_pool: jnp.ndarray,  # (num_blocks, bs, Hkv·D)
    v_pool: jnp.ndarray,
    k_new: jnp.ndarray,  # (T, Hkv, D) — flattened ragged token batch
    v_new: jnp.ndarray,
    dst_rows: jnp.ndarray,  # (T,) physical pool row per token
    dst_offsets: jnp.ndarray,  # (T,) slot within the block
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter a flattened ragged token batch into the pool (DESIGN.md §12).

    The engine resolves each token's (block row, slot) on the host when it
    builds the ragged batch — the device sees a flat destination list, so
    prefill-chunk and decode tokens of a fused iteration land in ONE
    scatter with no per-sequence table lookup.  The fused program hands it
    its whole stacked pool seen as one pool of ``layers · num_blocks`` rows
    (a free reshape), so only the T written rows move.  Padded tokens
    carry the scratch row; negative rows (not produced by the engine, but
    tolerated for symmetry with ``write_paged_chunk``) drop the write.
    """
    t = k_new.shape[0]
    rows = jnp.where(dst_rows >= 0, dst_rows, k_pool.shape[0])
    k_pool = k_pool.at[rows, dst_offsets].set(k_new.reshape(t, -1), mode="drop")
    v_pool = v_pool.at[rows, dst_offsets].set(v_new.reshape(t, -1), mode="drop")
    return k_pool, v_pool


def extract_block(pool: jnp.ndarray, block_id) -> jnp.ndarray:
    """O(block) copy out of the pool by *physical* id: (bs, Hkv·D).

    This (with ``write_block``) is the incremental-checkpoint unit — a
    preempt/resume moves whole physical blocks, never per-request pytrees.
    """
    return pool[block_id]


def write_block(pool: jnp.ndarray, block_id, data: jnp.ndarray) -> jnp.ndarray:
    """O(block) restore of one physical block (swap-in / resume)."""
    return pool.at[block_id].set(data)


def copy_blocks(
    pool: jnp.ndarray,  # (num_blocks, bs, Hkv·D)
    src_ids: jnp.ndarray,  # (N,) physical source blocks
    dst_ids: jnp.ndarray,  # (N,) physical destination blocks
) -> jnp.ndarray:
    """O(block) batched pool-internal copy: ``pool[dst] = pool[src]``.

    The copy-on-write unit (DESIGN.md §14): when a sequence is about to
    write into a block it shares (refcount > 1), the engine duplicates the
    block inside the pool so the write lands in an exclusively owned copy.
    The id lists come padded to a fixed bucket with scratch→scratch pairs,
    so one compiled program serves any COW batch — sharing changes
    indices, never shapes.  Fuses ``extract_block`` + ``write_block``
    without a host round-trip.
    """
    return pool.at[dst_ids].set(pool[src_ids])


def gather_paged(
    pool: jnp.ndarray,  # (num_blocks, bs, Hkv·D)
    block_tables: jnp.ndarray,  # (B, M)
    max_ctx: int,
    head_dim: int,
) -> jnp.ndarray:
    """Gather per-sequence contiguous KV (B, max_ctx, Hkv, D)."""
    _, bs, width = pool.shape
    m = max_ctx // bs
    tables = block_tables[:, :m]  # (B, m)
    safe = jnp.maximum(tables, 0)
    gathered = pool[safe]  # (B, m, bs, Hkv·D)
    gathered = jnp.where((tables >= 0)[:, :, None, None], gathered, 0)
    b = tables.shape[0]
    return gathered.reshape(b, m * bs, width // head_dim, head_dim)


def paged_attention_ref(
    q: jnp.ndarray,  # (B, H, D) — single decode token per sequence
    k_pool: jnp.ndarray,  # (num_blocks, bs, Hkv·D) — one layer's pool
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, M)
    seq_lens: jnp.ndarray,  # (B,) tokens valid in the cache (incl. current)
    logit_softcap: float = 0.0,
) -> jnp.ndarray:
    """Oracle decode attention over the paged pool. Returns (B, H, D)."""
    b, h, d = q.shape
    bs = k_pool.shape[1]
    m = block_tables.shape[1]
    max_ctx = m * bs
    k = gather_paged(k_pool, block_tables, max_ctx, d)  # (B, T, Hkv, D)
    v = gather_paged(v_pool, block_tables, max_ctx, d)
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bthd->bhgt", qg, k.astype(jnp.float32)) * d**-0.5
    if logit_softcap:
        scores = jnp.tanh(scores / logit_softcap) * logit_softcap
    valid = jnp.arange(max_ctx)[None, :] < seq_lens[:, None]  # (B, T)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgt,bthd->bhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def ragged_paged_attention_ref(
    q: jnp.ndarray,  # (S, Qmax, H, D) — per-sequence padded query tokens
    k_pool: jnp.ndarray,  # (num_blocks, bs, Hkv·D) — one layer's pool
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # (S, M)
    q_positions: jnp.ndarray,  # (S, Qmax) absolute position of each query
    kv_lens: jnp.ndarray,  # (S,) valid context incl. this iteration's tokens
    logit_softcap: float = 0.0,
) -> jnp.ndarray:
    """Oracle for the fused ragged paged-attention dispatch (DESIGN.md §12).

    One call covers every sequence of a mixed iteration: prefill chunks
    occupy ``q_len`` query slots, decode tokens are the ``q_len = 1``
    degenerate case.  Padded query slots (beyond a sequence's ``q_len``)
    compute garbage that the caller's unpad gather never reads.

    Numerics are identical to the split paths: block tables gather KV in
    logical position order over the same ``M * bs`` context width, and the
    causal mask ``kv_pos <= q_pos`` (with ``kv_pos < kv_len`` bounding
    padded rows) reduces to the decode path's validity mask at
    ``q_len = 1``.  Returns (S, Qmax, H, D).
    """
    s, tq, h, d = q.shape
    bs = k_pool.shape[1]
    m = block_tables.shape[1]
    max_ctx = m * bs
    k = gather_paged(k_pool, block_tables, max_ctx, d)  # (S, T, Hkv, D)
    v = gather_paged(v_pool, block_tables, max_ctx, d)
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(s, tq, hkv, g, d)
    scores = jnp.einsum(
        "bthgd,bshd->bhgts", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * (d**-0.5)
    if logit_softcap:
        scores = jnp.tanh(scores / logit_softcap) * logit_softcap
    kv_pos = jnp.arange(max_ctx, dtype=jnp.int32)
    mask = (kv_pos[None, None, :] <= q_positions[:, :, None]) & (
        kv_pos[None, None, :] < kv_lens[:, None, None]
    )  # (S, Qmax, T)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgts,bshd->bthgd", probs, v.astype(jnp.float32))
    return out.reshape(s, tq, h, d).astype(q.dtype)


def checkpoint_gather_ref(
    pool: jnp.ndarray,  # (num_blocks, bs, Hkv·D)
    block_ids: jnp.ndarray,  # (N,) device blocks to checkpoint
) -> jnp.ndarray:
    """Oracle for the incremental-checkpoint delta gather: pack the selected
    blocks into a dense staging buffer (N, bs, Hkv·D) for one contiguous
    device→host DMA."""
    return pool[block_ids]
