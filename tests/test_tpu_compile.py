"""Ahead-of-time compiles for a described TPU v5e (no chip needed).

The TPU compiler is installed with JAX, so it can compile for a chip that
is described rather than attached.  These tests compile the kernels of the
serving path at published head counts, head dims and page sizes, plus one
whole fused segment program of qwen2-0.5b, and fail where the chip's
compiler would refuse them (tile alignment, unsupported in-kernel shape
casts, too much fast memory, a program that does not fit HBM) — things the
interpret-mode kernel tests cannot see.  Nothing here runs; numerics are
covered by ``test_kernels.py``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention import (
    ragged_paged_attention,
    ragged_paged_attention_sharded,
)

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ragged_args(cfg, s, qmax, num_blocks, page, max_len, dtype, q_sh, pool_sh,
                 rep_sh, layers=8):
    """The kernel's operands: queries, a stack of ``layers`` pool layers
    (N, page, Hkv·D) for K and for V, tables, positions, lengths and the
    layer index."""
    hd = cfg.resolved_head_dim
    pool = (layers, num_blocks, page, cfg.num_kv_heads * hd)
    return (
        _sds((s, qmax, cfg.num_heads, hd), dtype, q_sh),
        _sds(pool, dtype, pool_sh),
        _sds(pool, dtype, pool_sh),
        _sds((s, max_len // page), jnp.int32, rep_sh),
        _sds((s, qmax), jnp.int32, rep_sh),
        _sds((s,), jnp.int32, rep_sh),
        _sds((), jnp.int32, rep_sh),
    )


@pytest.mark.parametrize("precision", [None, "highest"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("qmax", [1, 256, 512])  # decode, chunk sizes
def test_ragged_kernel_compiles_at_qwen2_shapes(one_chip, qmax, dtype,
                                                precision):
    """qwen2-0.5b: 14 query heads over 2 KV heads (GQA groups of 7),
    head_dim 64, page 16, a 2048-token context; at the default matmul
    precision and at "highest" (float32 contraction in the kernel)."""
    cfg = get_config("qwen2-0.5b")
    args = _ragged_args(cfg, 16, qmax, 3841, 16, 2048, dtype,
                        one_chip, one_chip, one_chip)
    *args, layer = args
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(ragged_paged_attention).lower(
            *args, layer=layer
        ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", ["yi-34b", "command-r-plus-104b",
                                  "llama-2-7b"])
def test_ragged_kernel_compiles_at_many_kv_heads(one_chip, arch):
    """Every KV head of a page in one grid step: at 8 heads of 7 or 12
    query heads, or 32 of one, a 128-slot query tile of a 512-token chunk
    overflows the chip's scoped VMEM, so the kernel tiles fewer slots."""
    cfg = get_config(arch)
    *args, layer = _ragged_args(cfg, 8, 512, 1024, 16, 2048, jnp.float32,
                                one_chip, one_chip, one_chip, layers=2)
    compiled = jax.jit(ragged_paged_attention).lower(
        *args, layer=layer
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_ragged_kernel_compiles_at_yi34b_heads(topo):
    """yi-34b heads (56 query over 8 KV, head_dim 128) shard_mapped over a
    4-chip ``model`` axis: each chip runs the kernel on its 2 KV heads —
    the 256 lanes of each page the pool's lane sharding gives it — of the
    layer the replicated index names in an 8-layer stack."""
    mesh = jax.sharding.Mesh(
        np.asarray(topo.devices).reshape(1, 4), ("data", "model")
    )
    heads = NamedSharding(mesh, P(None, None, "model", None))
    pool = NamedSharding(mesh, P(None, None, None, "model"))
    rep = NamedSharding(mesh, P())
    cfg = get_config("yi-34b")
    args = _ragged_args(cfg, 8, 64, 1024, 16, 2048, jnp.float32,
                        heads, pool, rep)
    *args, layer = args
    fn = functools.partial(ragged_paged_attention_sharded, mesh=mesh)
    compiled = jax.jit(fn).lower(*args, layer=layer).compile()
    assert "tpu_custom_call" in compiled.as_text()
    per_chip_pool = 2 * 8 * 1024 * 16 * (cfg.num_kv_heads // 4) * 128 * 4
    assert compiled.memory_analysis().argument_size_in_bytes < 2 * per_chip_pool


def test_fused_segment_compiles_at_qwen2_widths(one_chip, monkeypatch):
    """One whole fused segment program (8 of qwen2-0.5b's 24 layers, a
    256-token chunk beside decode rows) at published widths in float32:
    it must carry the Pallas kernel and fit one chip's HBM beside the
    weights it takes as arguments."""
    from repro.kernels import ops
    from repro.models import transformer as tf

    # the dispatcher asks jax.default_backend(), which is the CPU here;
    # steer it to the chip's branch for this compile only
    monkeypatch.setattr(ops, "kernel_backend", lambda: "pallas")
    cfg = get_config("qwen2-0.5b")
    t, s, qmax, blocks, page, max_len = 512, 16, 256, 3841, 16, 2048

    def place(tree):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    params = place(
        jax.eval_shape(lambda: tf.init_params(cfg, jax.random.PRNGKey(0)))
    )
    pools = place(jax.eval_shape(lambda: tf.init_paged_pools(cfg, blocks, page)))
    i32 = lambda *shape: _sds(shape, jnp.int32, one_chip)  # noqa: E731
    meta = tf.RaggedMeta(
        i32(t), i32(t), i32(s, qmax), i32(s, qmax), i32(s), i32(t), i32(t)
    )
    x = _sds((1, t, cfg.d_model), jnp.float32, one_chip)
    fn = jax.jit(
        lambda pps, p, lo, x, pools, tables, pos, meta: tf.run_tokens_paged_at(
            cfg, p, pps, lo, x, pools, tables, pos, meta
        ),
        static_argnums=(0,),
        donate_argnums=(4,),
    )
    compiled = fn.lower(
        8, params, i32(), x, pools, i32(s, max_len // page), i32(1, t), meta
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    nbytes = lambda tree: sum(a.size * 4 for a in jax.tree.leaves(tree))  # noqa: E731
    # the layer weights arrive as arguments, beside the pools
    assert mem.argument_size_in_bytes >= nbytes(params["layers"]) + nbytes(pools)
    peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert peak < V5E_HBM_BYTES, mem


# Ops that move a whole buffer: a relayout, a period slice, or a slice's
# write-back.  Their fusions carry the op in the name
# (``constant_dynamic-slice_fusion``, ``copy_bitcast_fusion``, ...).
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* (\S+?)\(")


def _pool_moves(hlo: str, blocks: int, layer_elems: int):
    """(name, dims) of every copy / dynamic-slice / dynamic-update-slice op
    or fusion whose output carries the pool's block axis and at least one
    pool layer's elements — a pool slice, relayout or write-back.  The
    layer weights' per-segment slices (the stated precision's bf16 operand
    rounding) have no block axis and are not counted."""
    out = []
    for line in hlo.splitlines():
        m = _HLO_OP.match(line)
        if m is None:
            continue
        name, dims, op = m.groups()
        shape = [int(d) for d in dims.split(",") if d]
        if not any(k in op or k in name for k in _MOVES):
            continue
        if blocks in shape and int(np.prod(shape)) >= layer_elems:
            out.append((name, shape))
    return out


@pytest.mark.parametrize("t,s,qmax", [(16, 16, 1), (512, 16, 256)],
                         ids=["decode", "chunk"])
@pytest.mark.parametrize("form", ["whole_pool", "per_segment"])
def test_fused_segment_updates_pool_in_place(one_chip, monkeypatch, form, t,
                                             s, qmax):
    """The fused segment program at qwen2-0.5b's published widths (float32
    storage, bf16 matmul operands as the benchmark states), in the serial
    whole-pool form and the pipelined per-segment form, at a decode and a
    chunk bucket, moves no pool bytes it neither writes nor attends to:

    * the pools keep the chip's row-major tiled layout, which the kernel
      DMAs and the scatter writes;
    * no copy, dynamic-slice or dynamic-update-slice outputs a pool-sized
      buffer (no period slice, no per-layer relayout, no scan copy);
    * the donated pools alias the outputs, and the temporaries stay below
      one layer's K pool beside the segment's bf16 weight operands."""
    from repro.kernels import ops
    from repro.models import transformer as tf

    monkeypatch.setattr(ops, "kernel_backend", lambda: "pallas")
    cfg = get_config("qwen2-0.5b")
    blocks, page, max_len, pps = 3841, 16, 2048, 8

    def place(tree):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    params = place(
        jax.eval_shape(lambda: tf.init_params(cfg, jax.random.PRNGKey(0)))
    )
    pools = jax.eval_shape(lambda: tf.init_paged_pools(cfg, blocks, page))
    if form == "per_segment":
        pools = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((pps,) + a.shape[1:], a.dtype),
            pools,
        )
    pools = place(pools)
    entry = tf.run_tokens_paged_at if form == "whole_pool" else (
        tf.run_tokens_paged_seg
    )
    i32 = lambda *shape: _sds(shape, jnp.int32, one_chip)  # noqa: E731
    meta = tf.RaggedMeta(
        i32(t), i32(t), i32(s, qmax), i32(s, qmax), i32(s), i32(t), i32(t)
    )
    x = _sds((1, t, cfg.d_model), jnp.float32, one_chip)
    fn = jax.jit(
        lambda pps, p, lo, x, pools, tables, pos, meta: entry(
            cfg, p, pps, lo, x, pools, tables, pos, meta
        ),
        static_argnums=(0,),
        donate_argnums=(4,),
    )
    with jax.default_matmul_precision("bfloat16"):
        compiled = fn.lower(
            pps, params, i32(), x, pools, i32(s, max_len // page), i32(1, t),
            meta,
        ).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo

    pool_leaves = jax.tree.leaves(pools)
    for fmt in jax.tree.leaves(compiled.input_formats[0][3]):  # the pools
        assert fmt.layout.major_to_minor == (0, 1, 2, 3), fmt.layout
    layer_elems = int(np.prod(pool_leaves[0].shape[1:]))
    moves = _pool_moves(hlo, blocks, layer_elems)
    assert not moves, moves

    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * 4 for a in pool_leaves)
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    seg_weight_elems = sum(
        a.size // cfg.num_periods * pps for a in jax.tree.leaves(params["layers"])
    )
    assert mem.temp_size_in_bytes < layer_elems * 4 + 2 * seg_weight_elems, mem


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_checkpoint_gather_compiles_at_qwen2_shapes(one_chip, dtype):
    """The incremental-checkpoint staging gather over a qwen2-0.5b pool
    layer (page 16 of 2 KV heads x head_dim 64)."""
    from repro.kernels.kv_checkpoint import checkpoint_gather

    pool = _sds((3841, 16, 2 * 64), dtype, one_chip)
    ids = _sds((8,), jnp.int32, one_chip)
    compiled = jax.jit(checkpoint_gather).lower(pool, ids).compile()
    assert "tpu_custom_call" in compiled.as_text()
