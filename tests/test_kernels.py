"""Pallas kernels (interpret mode) vs pure-jnp oracles: shape/dtype sweeps.

Each kernel executes its real kernel body (python-interpreted grid) and must
match ref.py to float tolerance.  Larger shapes run on TPU only; interpret
mode is slow, so sweeps stay compact but cover GQA groups, ragged tails,
sliding windows, chunk offsets, and dtypes.
"""
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.kv_checkpoint import checkpoint_gather, checkpoint_scatter
from repro.kernels.paged_attention import paged_attention

KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype, i=0):
    return jax.random.normal(jax.random.fold_in(KEY, i), shape).astype(dtype)


def _pool(npages, page, hkv, d, i, dtype=jnp.float32, layers=None):
    """A paged pool in the served layout: (N, page, Hkv·D) per layer, or a
    stack of ``layers`` of them."""
    lead = () if layers is None else (layers,)
    return _rand(lead + (npages, page, hkv * d), dtype, i)


# ------------------------------------------------------------- flash prefill

FLASH_CASES = [
    # b, tq, tk, h, hkv, d, causal, window, q_off, dtype
    (2, 64, 64, 4, 2, 64, True, 0, 0, jnp.float32),
    (1, 96, 224, 4, 4, 32, True, 0, 128, jnp.float32),  # chunked prefill
    (2, 64, 64, 8, 2, 64, True, 48, 0, jnp.float32),  # sliding window
    (1, 80, 80, 2, 2, 128, False, 0, 0, jnp.float32),  # encoder
    (1, 70, 70, 4, 1, 64, True, 0, 0, jnp.float32),  # MQA + ragged tail
    (1, 64, 64, 4, 2, 64, True, 0, 0, jnp.bfloat16),
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_matches_ref(case):
    b, tq, tk, h, hkv, d, causal, sw, qo, dtype = case
    q = _rand((b, tq, h, d), dtype, 1)
    k = _rand((b, tk, hkv, d), dtype, 2)
    v = _rand((b, tk, hkv, d), dtype, 3)
    out = flash_attention(
        q, k, v, causal=causal, sliding_window=sw, q_offset=qo,
        block_q=32, block_k=32, interpret=True,
    )
    want = ref.flash_attention_ref(
        q, k, v, causal=causal, sliding_window=sw, q_offset=qo
    )
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                 want.astype(jnp.float32)))) < tol


# ------------------------------------------------------------- paged decode

PAGED_CASES = [
    # b, h, hkv, d, page, npages, m
    (3, 8, 2, 64, 16, 32, 4),
    (2, 4, 4, 32, 8, 16, 6),
    (1, 16, 1, 128, 32, 8, 2),  # MQA
]


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_attention_matches_ref(case):
    b, h, hkv, d, page, npages, m = case
    q = _rand((b, h, d), jnp.float32, 4)
    kp = _pool(npages, page, hkv, d, 5)
    vp = _pool(npages, page, hkv, d, 6)
    key = jax.random.fold_in(KEY, 7)
    # random non-overlapping page assignment with ragged lengths
    perm = jax.random.permutation(key, npages)[: b * m].reshape(b, m)
    lens = jax.random.randint(jax.random.fold_in(KEY, 8), (b,), 1, m * page)
    used = (lens + page - 1) // page
    tables = jnp.where(jnp.arange(m)[None, :] < used[:, None], perm, -1)
    out = paged_attention(q, kp, vp, tables, lens, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, tables, lens)
    assert float(jnp.max(jnp.abs(out - want))) < 2e-5


@pytest.mark.slow  # the parametrized PAGED_CASES (fast) pin the kernel
@settings(max_examples=10, deadline=None)
@given(
    b=st.integers(1, 3),
    g=st.sampled_from([1, 2, 4]),
    hkv=st.sampled_from([1, 2]),
    page=st.sampled_from([8, 16]),
    m=st.integers(1, 4),
)
def test_paged_attention_property(b, g, hkv, page, m):
    h, d, npages = hkv * g, 32, 24
    q = _rand((b, h, d), jnp.float32, 10)
    kp = _pool(npages, page, hkv, d, 11)
    vp = _pool(npages, page, hkv, d, 12)
    key = jax.random.fold_in(KEY, 13)
    perm = jax.random.permutation(key, npages)[: b * m].reshape(b, m)
    lens = jax.random.randint(jax.random.fold_in(KEY, 14), (b,), 1, m * page + 1)
    out = paged_attention(q, kp, vp, perm, lens, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, perm, lens)
    assert float(jnp.max(jnp.abs(out - want))) < 2e-5


# -------------------------------------------------------------- fused ragged

RAGGED_CASES = [
    # q_lens per sequence (mixed chunks + decodes), h, hkv, d, page, m,
    # (layers in the pool stack, layer the kernel reads)
    ([1, 1, 1], 8, 2, 64, 16, 4, (3, 0)),  # pure decode (q_len = 1), GQA
    ([1, 1, 1], 8, 2, 64, 16, 4, (3, 1)),
    ([1, 1, 1], 8, 2, 64, 16, 4, (3, 2)),
    ([8, 1, 4, 1], 4, 2, 32, 8, 6, (3, 0)),  # mixed chunks + decodes, GQA
    ([8, 1, 4, 1], 4, 2, 32, 8, 6, (3, 1)),
    ([8, 1, 4, 1], 4, 2, 32, 8, 6, (3, 2)),
    ([6, 3], 4, 4, 32, 8, 4, (1, 0)),  # dense (g = 1) ragged chunks
    ([5, 1], 16, 1, 64, 16, 3, (1, 0)),  # MQA
    ([130, 1], 2, 1, 32, 16, 10, (1, 0)),  # Qmax > QUERY_TILE: tiled, padded
]


@pytest.mark.parametrize("case", RAGGED_CASES)
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_ragged_paged_attention_matches_ref(case, softcap, monkeypatch):
    """The fused mixed-batch kernel (interpret mode, through the backend
    dispatcher) vs the jnp oracle: one grid covers prefill chunks and
    decode rows; each sequence's queries sit at the tail of its context
    (the serve-time layout).  The kernel reads layer ``layer`` of a stacked
    pool by its scalar-prefetched index; the oracle gets that layer's
    slice — first, middle and last layer of a three-layer stack.  Padded
    query slots are compared too — the kernel and oracle mask them
    identically via the causal + kv_len bound."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    q_lens, h, hkv, d, page, m, (layers, layer) = case
    s = len(q_lens)
    qmax = max(q_lens)
    npages = s * m
    q = _rand((s, qmax, h, d), jnp.float32, 40)
    kp = _pool(npages, page, hkv, d, 41, layers=layers)
    vp = _pool(npages, page, hkv, d, 42, layers=layers)
    key = jax.random.fold_in(KEY, 43)
    perm = jax.random.permutation(key, npages)[: s * m].reshape(s, m)
    kv_lens = jax.random.randint(
        jax.random.fold_in(KEY, 44), (s,), max(q_lens), m * page + 1
    )
    # queries are the tail of the context; padded slots repeat the last
    # real position (mask-equivalent garbage on both sides)
    ql = jnp.asarray(q_lens)
    j = jnp.arange(qmax)[None, :]
    q_pos = kv_lens[:, None] - ql[:, None] + jnp.minimum(j, ql[:, None] - 1)
    out = ops.ragged_paged_attention(
        q, kp, vp, perm, q_pos, kv_lens, layer=jnp.int32(layer),
        logit_softcap=softcap,
    )
    want = ref.ragged_paged_attention_ref(
        q, kp[layer], vp[layer], perm, q_pos, kv_lens, logit_softcap=softcap
    )
    assert float(jnp.max(jnp.abs(out - want))) < 2e-5


def test_ragged_kernel_decode_degenerates_to_paged_attention():
    """At qmax = 1 the ragged kernel must agree with the decode kernel —
    same pools, tables and lengths, query at position len-1."""
    from repro.kernels.paged_attention import (
        paged_attention, ragged_paged_attention,
    )

    b, h, hkv, d, page, m, npages = 2, 8, 2, 64, 16, 3, 8
    q = _rand((b, h, d), jnp.float32, 50)
    kp = _pool(npages, page, hkv, d, 51)
    vp = _pool(npages, page, hkv, d, 52)
    perm = jax.random.permutation(jax.random.fold_in(KEY, 53), npages)[
        : b * m
    ].reshape(b, m)
    lens = jnp.array([37, 12], jnp.int32)
    dec = paged_attention(q, kp, vp, perm, lens, interpret=True)
    rag = ragged_paged_attention(
        q[:, None], kp[None], vp[None], perm, (lens - 1)[:, None], lens,
        layer=jnp.int32(0), interpret=True,
    )
    assert float(jnp.max(jnp.abs(rag[:, 0] - dec))) < 2e-5


# --------------------------------------------------------- checkpoint gather


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_checkpoint_gather_matches_ref(dtype):
    pool = _pool(32, 16, 2, 64, 20, dtype)
    ids = jnp.array([5, 2, 17, 9, 31], jnp.int32)
    out = checkpoint_gather(pool, ids, interpret=True)
    assert jnp.array_equal(out, ref.checkpoint_gather_ref(pool, ids))


def test_checkpoint_scatter_roundtrip():
    pool = _pool(32, 16, 2, 64, 21)
    ids = jnp.array([3, 8, 1], jnp.int32)
    staged = checkpoint_gather(pool, ids, interpret=True)
    wiped = pool.at[ids].set(0.0)
    restored = checkpoint_scatter(wiped, staged, ids)
    assert jnp.array_equal(restored, pool)


def test_ops_dispatch_ref_backend(monkeypatch):
    # default CPU backend = jnp reference (no pallas); smoke the dispatcher
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    assert ops.kernel_backend() == "ref"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    assert ops.kernel_backend() == "interpret"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    with pytest.raises(ValueError):  # compiled kernels need the chip
        ops.kernel_backend()
    monkeypatch.delenv("REPRO_KERNEL_BACKEND")
    q = _rand((1, 8, 4, 16), jnp.float32, 30)
    k = _rand((1, 8, 2, 16), jnp.float32, 31)
    v = _rand((1, 8, 2, 16), jnp.float32, 32)
    out = ops.flash_attention(q, k, v)
    assert out.shape == q.shape
