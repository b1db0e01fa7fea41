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
                 rep_sh):
    hd = cfg.resolved_head_dim
    pool = (num_blocks, cfg.num_kv_heads, page, hd)
    return (
        _sds((s, qmax, cfg.num_heads, hd), dtype, q_sh),
        _sds(pool, dtype, pool_sh),
        _sds(pool, dtype, pool_sh),
        _sds((s, max_len // page), jnp.int32, rep_sh),
        _sds((s, qmax), jnp.int32, rep_sh),
        _sds((s,), jnp.int32, rep_sh),
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
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(ragged_paged_attention).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_ragged_kernel_compiles_at_yi34b_heads(topo):
    """yi-34b heads (56 query over 8 KV, head_dim 128) shard_mapped over a
    4-chip ``model`` axis: each chip runs the kernel on its 2 KV heads."""
    mesh = jax.sharding.Mesh(
        np.asarray(topo.devices).reshape(1, 4), ("data", "model")
    )
    heads = NamedSharding(mesh, P(None, None, "model", None))
    pool = NamedSharding(mesh, P(None, "model", None, None))
    rep = NamedSharding(mesh, P())
    cfg = get_config("yi-34b")
    args = _ragged_args(cfg, 8, 64, 1024, 16, 2048, jnp.float32,
                        heads, pool, rep)
    fn = functools.partial(ragged_paged_attention_sharded, mesh=mesh)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    per_chip_pool = 2 * 1024 * (cfg.num_kv_heads // 4) * 16 * 128 * 4
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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_checkpoint_gather_compiles_at_qwen2_shapes(one_chip, dtype):
    """The incremental-checkpoint staging gather over a qwen2-0.5b pool
    (2 KV heads, page 16, head_dim 64)."""
    from repro.kernels.kv_checkpoint import checkpoint_gather

    pool = _sds((3841, 2, 16, 64), dtype, one_chip)
    ids = _sds((8,), jnp.int32, one_chip)
    compiled = jax.jit(checkpoint_gather).lower(pool, ids).compile()
    assert "tpu_custom_call" in compiled.as_text()
