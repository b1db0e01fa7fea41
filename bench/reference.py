"""The plain reference: what every model family shares, and the family's
own parts found by the name its configuration file gives.

Straightforward ``jax.numpy`` in float32 with every matmul at precision
HIGHEST.  Each family's equations are ``bench/families/<family>/
reference.py`` (``bench/family.py``): its sizes (``dims``), its weights
(``draw``) and its layers (``hidden``).  Here are the parts they share --
RMSNorm, rotary embeddings, causal attention in blocks of query rows, the
float8 arithmetic of the control -- and the head over the final hidden
states, in row blocks, that turns them into the numbers compared.  None of
it imports anything of the program under test.

It works one sequence at a time, one layer at a time, a block of query
rows at a time and a block of head rows at a time, so a long sequence
never needs its whole score or logit matrix at once.

``lowp=True`` is the control: the same mathematics with every matmul's
operands rounded to float8 (e4m3, absmax scaling per weight matrix and per
activation row) and float32 accumulation -- one precision step below the
bfloat16 arithmetic the configurations state.

Weights are drawn on the device from the run's seed (``draw_jit``), every
parameter nonzero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import family

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
HEAD_ROWS = 256  # rows of the head computed at a time
Q_BLOCK = 256  # query rows of attention computed at a time


def _family(m: dict):
    return family.module(m["family"], "reference")


def dims(cfg: dict) -> dict:
    """The model's sizes from its configuration file, by its family."""
    return family.module(family.check(cfg), "reference").dims(cfg)


def draw(m: dict, key: jax.Array) -> dict:
    """All weights of the model, float32, from a key of the seed (call
    under ``jax.jit`` to draw them on the device in one program)."""
    return _family(m).draw(m, key)


def hidden(m: dict, w: dict, tokens, lowp: bool = False):
    """Final normed hidden states (N, d) of one sequence (no padding
    before its tokens; positions 0..N-1)."""
    return _family(m).hidden(m, w, tokens, lowp)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seed must be non-negative")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def draw_jit(m: dict, seed: int) -> dict:
    """``draw`` as one program on the device; the seed's key is its
    argument, so every seed runs the same compiled program."""
    return jax.jit(functools.partial(draw, m))(seed_key(seed))


# ---------------------------------------------------------------- matmuls
def _round8(x, axis):
    """x rounded to float8 e4m3 with absmax scaling along ``axis`` (None:
    one scale for the tensor), returned as (bf16 values, float32 scale);
    bf16 holds every e4m3 value exactly."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    q = (x / s).astype(F8).astype(jnp.bfloat16)
    return q, s


def _mm(a, b, lowp: bool):
    """a (..., K) @ b (K, N)."""
    if not lowp:
        return jnp.matmul(a, b, precision=HIGHEST)
    qa, sa = _round8(a, -1)
    qb, sb = _round8(b, None)
    out = jnp.matmul(qa, qb, preferred_element_type=jnp.float32)
    return out * sa * sb


def _einsum8(spec, a, b, a_axis, b_axis):
    """The float8 einsum of the control: (result, a's scales, b's scales)."""
    qa, sa = _round8(a, a_axis)
    qb, sb = _round8(b, b_axis)
    out = jnp.einsum(spec, qa, qb, preferred_element_type=jnp.float32)
    return out, sa, sb


# ------------------------------------------------------------- shared parts
def rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, pos, theta):
    """x (N, heads, hd), rotate-half form of HF ``apply_rotary_pos_emb``."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    freqs = pos[:, None].astype(jnp.float32) * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def attention(q, k, v, lowp: bool):
    """Causal GQA. q (N, H, hd), k/v (N, Hkv, hd) -> (N, H*hd), computed
    ``Q_BLOCK`` query rows at a time: each block attends over every key,
    those it may not see masked, so no score tensor is larger than one
    block x N keys x H heads, and each row's arithmetic is that of one
    unblocked pass."""
    n, h, hd = q.shape
    qb = min(Q_BLOCK, n)
    nb = -(-n // qb)
    blocks = jnp.pad(q, ((0, nb * qb - n), (0, 0), (0, 0))).reshape(nb, qb, h, hd)
    starts = jnp.arange(nb, dtype=jnp.int32) * qb
    out = jax.lax.map(lambda a: _attend(a[0], a[1], k, v, lowp), (blocks, starts))
    return out.reshape(nb * qb, h * hd)[:n]


def _attend(q, start, k, v, lowp: bool):
    """Query rows ``start`` .. ``start + len(q)`` of causal GQA over all of
    k/v; query head i uses KV head i // group."""
    n, h, hd = q.shape
    m, kvh = k.shape[:2]
    qg = q.reshape(n, kvh, h // kvh, hd)
    if lowp:
        # q rows scale along hd, k rows along hd; p rows along the keys,
        # v columns along the keys: every scale factors out of its sum
        s, sa, sb = _einsum8("nkgd,mkd->kgnm", qg, k, -1, -1)
        s = s * jnp.transpose(sa, (1, 2, 0, 3)) * sb[:, :, 0].T[:, None, None, :]
    else:
        s = jnp.einsum("nkgd,mkd->kgnm", qg, k, precision=HIGHEST)
    s = s * hd**-0.5
    causal = (start + jnp.arange(n))[:, None] >= jnp.arange(m)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if lowp:
        o, sa, sb = _einsum8("kgnm,mkd->nkgd", p, v, -1, 0)
        o = o * jnp.transpose(sa, (2, 0, 1, 3)) * sb[:, :, None, :]
    else:
        o = jnp.einsum("kgnm,mkd->nkgd", p, v, precision=HIGHEST)
    return o.reshape(n, h * hd)


def _head(w: dict):
    return w["lm_head"] if "lm_head" in w else w["embed"].T


@functools.partial(jax.jit, static_argnums=(0,))
def _gaps(mkey, w, tokens, targets):
    m = dict(mkey)
    x = hidden(m, w, tokens)
    head = _head(w)
    n = x.shape[0]
    nb = -(-n // HEAD_ROWS)
    xb = jnp.pad(x, ((0, nb * HEAD_ROWS - n), (0, 0))).reshape(nb, HEAD_ROWS, -1)
    tb = jnp.pad(targets, (0, nb * HEAD_ROWS - n)).reshape(nb, HEAD_ROWS)

    def block(_, xt):
        xr, tr = xt
        logits = jnp.matmul(xr, head, precision=HIGHEST)
        best = logits.max(axis=-1)
        got = jnp.take_along_axis(logits, jnp.maximum(tr, 0)[:, None], 1)[:, 0]
        return None, (best - got, best)

    _, (gap, best) = jax.lax.scan(block, None, (xb, tb))
    return gap.reshape(-1)[:n], best.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnums=(0,))
def _argmax_lowp(mkey, w, tokens):
    m = dict(mkey)
    x = hidden(m, w, tokens, lowp=True)
    head = _head(w)
    n = x.shape[0]
    nb = -(-n // HEAD_ROWS)
    xb = jnp.pad(x, ((0, nb * HEAD_ROWS - n), (0, 0))).reshape(nb, HEAD_ROWS, -1)

    def block(_, xr):
        return None, jnp.argmax(_mm(xr, head, True), axis=-1).astype(jnp.int32)

    _, top = jax.lax.scan(block, None, xb)
    return top.reshape(-1)[:n]


def _key(m: dict) -> tuple:
    return tuple(sorted(m.items()))


def gaps(m: dict, w: dict, tokens: np.ndarray, targets: np.ndarray,
         pad_to: int) -> tuple:
    """For each position i of ``tokens``: the reference's best logit minus
    its logit of ``targets[i]`` (the token that was put at position i+1),
    and the best logit.  The sequence is padded to ``pad_to`` at its end
    (causal: padding never reaches a real position), so one program serves
    every length."""
    n = len(tokens)
    tok = np.zeros(pad_to, np.int32)
    tok[:n] = tokens
    tgt = np.zeros(pad_to, np.int32)
    tgt[:n] = targets
    gap, best = _gaps(_key(m), w, jnp.asarray(tok), jnp.asarray(tgt))
    return np.asarray(gap)[:n], np.asarray(best)[:n]


def argmax_lowp(m: dict, w: dict, tokens: np.ndarray, pad_to: int) -> np.ndarray:
    """The control's first choice at each position of ``tokens``."""
    n = len(tokens)
    tok = np.zeros(pad_to, np.int32)
    tok[:n] = tokens
    return np.asarray(_argmax_lowp(_key(m), w, jnp.asarray(tok)))[:n]
