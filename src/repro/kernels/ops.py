"""Public jit'd entry points for the kernel layer.

Backend dispatch: on a TPU the Pallas kernels always run compiled.  Off
the TPU the jnp reference runs by default, or the Pallas kernels in
``interpret=True`` mode when ``REPRO_KERNEL_BACKEND=interpret`` (interpret
mode executes the kernel body python-side per grid step — exact but slow
for large grids).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention as _flash_pallas
from .kv_checkpoint import checkpoint_gather as _ckpt_pallas
from .kv_checkpoint import checkpoint_scatter
from .paged_attention import ragged_paged_attention as _ragged_pallas
from .paged_attention import (
    ragged_paged_attention_sharded as _ragged_shmap,
)

__all__ = [
    "flash_attention",
    "paged_attention",
    "ragged_paged_attention",
    "checkpoint_gather",
    "checkpoint_scatter",
    "kernel_backend",
]


def kernel_backend() -> str:
    """'pallas' on a TPU; off it 'ref' (default) or 'interpret', chosen by
    ``REPRO_KERNEL_BACKEND``.  The override never applies on a TPU: asking
    for anything but the compiled kernels there is an error."""
    forced = os.environ.get("REPRO_KERNEL_BACKEND") or None
    if jax.default_backend() == "tpu":
        if forced not in (None, "pallas"):
            raise RuntimeError(
                f"REPRO_KERNEL_BACKEND={forced!r}: a TPU runs the compiled "
                "Pallas kernels only"
            )
        return "pallas"
    if forced not in (None, "ref", "interpret"):
        raise ValueError(
            f"REPRO_KERNEL_BACKEND={forced!r}: off a TPU it is 'ref' or "
            "'interpret'"
        )
    return forced or "ref"


def flash_attention(q, k, v, *, causal=True, sliding_window=0, q_offset=0,
                    block_q=128, block_k=128):
    be = kernel_backend()
    if be == "ref":
        return ref.flash_attention_ref(
            q, k, v, causal=causal, sliding_window=sliding_window,
            q_offset=q_offset,
        )
    return _flash_pallas(
        q, k, v, causal=causal, sliding_window=sliding_window,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=(be == "interpret"),
    )


def paged_attention(q, k_pool, v_pool, block_tables, seq_lens, *,
                    logit_softcap=0.0, mesh=None):
    """Decode attention over one layer's pool (N, page, Hkv·D).  ``mesh``:
    tensor-parallel serving mesh (DESIGN.md §11).  The Pallas path
    shard_maps the kernel over KV heads; the jnp reference needs no
    explicit handling — its operands arrive sharding-constrained and GSPMD
    partitions the oracle einsums over the head axis."""
    be = kernel_backend()
    if be == "ref":
        return ref.paged_attention_ref(
            q, k_pool, v_pool, block_tables, seq_lens,
            logit_softcap=logit_softcap,
        )
    # the Qmax = 1 case of the ragged kernel over a one-layer stack, the
    # query at seq_len - 1
    return ragged_paged_attention(
        q[:, None], k_pool[None], v_pool[None], block_tables,
        (seq_lens - 1)[:, None], seq_lens, layer=jnp.int32(0),
        logit_softcap=logit_softcap, mesh=mesh,
    )[:, 0]


def ragged_paged_attention(q, k_pool, v_pool, block_tables, q_positions,
                           kv_lens, *, layer, logit_softcap=0.0, mesh=None):
    """Fused mixed-batch attention over layer ``layer`` of the stacked pool
    (L, N, page, Hkv·D) (DESIGN.md §12): one dispatch serves every
    sequence of an iteration, prefill chunks and decodes alike.  Same
    backend dispatch contract as ``paged_attention``: Pallas (shard_mapped
    over KV heads on a mesh) on TPU, its index map reading the layer out of
    the stack; the ``cache_ops`` jnp oracle on that layer's slice on CPU —
    where GSPMD partitions the oracle einsums over the already-constrained
    head axis."""
    be = kernel_backend()
    if be == "ref":
        return ref.ragged_paged_attention_ref(
            q, k_pool[layer], v_pool[layer], block_tables, q_positions,
            kv_lens, logit_softcap=logit_softcap,
        )
    if mesh is not None:
        return _ragged_shmap(
            q, k_pool, v_pool, block_tables, q_positions, kv_lens, mesh,
            layer=layer, logit_softcap=logit_softcap,
            interpret=(be == "interpret"),
        )
    return _ragged_pallas(
        q, k_pool, v_pool, block_tables, q_positions, kv_lens, layer=layer,
        logit_softcap=logit_softcap, interpret=(be == "interpret"),
    )


def checkpoint_gather(pool, block_ids):
    be = kernel_backend()
    if be == "ref":
        return ref.checkpoint_gather_ref(pool, block_ids)
    return _ckpt_pallas(pool, block_ids, interpret=(be == "interpret"))
