"""Incremental-checkpoint delta gather — Pallas TPU kernel.

ConServe's IC hot path: each iteration, the set of newly *completed* KV
pages of offline sequences must be shipped device→host.  Pages are scattered
across the pool, so a naive copy issues one small DMA per page.  This kernel
packs the selected pages into a dense, lane-aligned staging buffer so the
device→host transfer is ONE contiguous DMA — the TPU analogue of the paper's
separate-CUDA-stream checkpoint copy (DESIGN.md §3).

Grid (K,): one page per step; the scalar-prefetched page-id list drives the
input BlockSpec index map, so the HBM→VMEM load of each page is the DMA
engine's indirection, and the store lands at the dense output slot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(ids_ref, pool_ref, out_ref):
    del ids_ref  # consumed by the index map
    out_ref[...] = pool_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def checkpoint_gather(
    pool: jnp.ndarray,  # (N, page, Hkv*D) — or any (N, ...) page layout
    block_ids: jnp.ndarray,  # (K,) int32 — device pages to checkpoint
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns the packed staging buffer (K, ...) of whole pages."""
    page = pool.shape[1:]
    rest = (0,) * len(page)
    k = block_ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k,),
        in_specs=[pl.BlockSpec((1, *page), lambda i, ids: (ids[i], *rest))],
        out_specs=pl.BlockSpec((1, *page), lambda i, ids: (i, *rest)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k, *page), pool.dtype),
        interpret=interpret,
    )(block_ids.astype(jnp.int32), pool)


def checkpoint_scatter(
    pool: jnp.ndarray,  # (N, page, Hkv*D)
    staging: jnp.ndarray,  # (K, page, Hkv*D) — swapped-in pages
    block_ids: jnp.ndarray,  # (K,) destination device pages
) -> jnp.ndarray:
    """Swap-in: scatter staged pages back into the pool (prefetch path).

    Scatter-to-dynamic-index is a plain XLA scatter (already optimal — one
    DMA per page is unavoidable on the write side); no kernel needed.
    """
    return pool.at[block_ids].set(staging)
