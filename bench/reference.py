"""Plain reference of the dense GQA decoder family (Qwen2, Llama-style).

Straightforward ``jax.numpy`` in float32 with every matmul at precision
HIGHEST, written from the published description (HF ``modeling_qwen2`` /
``modeling_llama``): RMSNorm, rotary embeddings (rotate-half form), grouped
query attention with optional QKV bias, SwiGLU, tied or untied head.  It
imports nothing of the program under test.

It works one sequence at a time and one layer at a time (a scan over the
stacked layers), and takes the head in row blocks, so a 2048-token sequence
fits beside nothing else at published widths.

``lowp=True`` is the control: the same mathematics with every matmul's
operands rounded to float8 (e4m3, absmax scaling per weight matrix and per
activation row) and float32 accumulation -- one precision step below the
bfloat16 arithmetic the configurations state.

Weights are drawn here, on the device, from the run's seed (``draw``), with
every parameter nonzero: biases and norm scales included.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
HEAD_ROWS = 256  # rows of the head computed at a time


def dims(cfg: dict) -> dict:
    """The model's sizes from its configuration file (HF key names)."""
    heads = cfg["num_attention_heads"]
    d = {
        "layers": cfg["num_hidden_layers"],
        "d_model": cfg["hidden_size"],
        "head_dim": cfg["hidden_size"] // heads,
        "heads": heads,
        "kv_heads": cfg["num_key_value_heads"],
        "d_ff": cfg["intermediate_size"],
        "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"],
        "theta": cfg["rope_theta"],
        "qkv_bias": cfg["architecture"]["qkv_bias"],
        "tied": cfg["tie_word_embeddings"],
    }
    if d["heads"] % d["kv_heads"]:
        raise ValueError(f"{cfg['name']}: query heads not a multiple of KV heads")
    return d


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seed must be non-negative")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _shapes(m: dict) -> dict:
    L, d, hd = m["layers"], m["d_model"], m["head_dim"]
    h, kv, f, v = m["heads"], m["kv_heads"], m["d_ff"], m["vocab"]
    s = {
        "embed": (v, d),
        "final_norm": (d,),
        "ln1": (L, d),
        "ln2": (L, d),
        "wq": (L, d, h * hd),
        "wk": (L, d, kv * hd),
        "wv": (L, d, kv * hd),
        "wo": (L, h * hd, d),
        "w_gate": (L, d, f),
        "w_up": (L, d, f),
        "w_down": (L, f, d),
    }
    if m["qkv_bias"]:
        s.update(bq=(L, h * hd), bk=(L, kv * hd), bv=(L, kv * hd))
    if not m["tied"]:
        s["lm_head"] = (d, v)
    return s


def _scale(name: str, m: dict) -> tuple:
    """(mean, std) of each parameter: fan-in scaled matrices, norm scales
    around 1, small biases -- none of them zero."""
    d, f, hd = m["d_model"], m["d_ff"], m["head_dim"]
    if name in ("ln1", "ln2", "final_norm"):
        return 1.0, 0.1
    if name in ("bq", "bk", "bv"):
        return 0.0, 0.1
    if name == "embed":
        return 0.0, 0.02 if m["tied"] else 1.0
    if name == "wo":
        return 0.0, (m["heads"] * hd) ** -0.5
    if name == "w_down":
        return 0.0, f**-0.5
    return 0.0, d**-0.5


def draw(m: dict, key: jax.Array) -> dict:
    """All weights, float32, from a key of the seed (call under
    ``jax.jit`` to draw them on the device in one program)."""
    shapes = _shapes(m)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        mean, std = _scale(name, m)
        out[name] = mean + std * jax.random.normal(k, shape, jnp.float32)
    return out


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


# ------------------------------------------------------------------ layers
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
    """Causal GQA. q (N, H, hd), k/v (N, Hkv, hd) -> (N, H*hd)."""
    n, h, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    qg = q.reshape(n, kvh, g, hd)  # query head i uses KV head i // g
    scale = hd**-0.5
    if lowp:
        # q rows scale along hd, k rows along hd; p rows along the keys,
        # v columns along the keys: every scale factors out of its sum
        s, sa, sb = _einsum8("nkgd,mkd->kgnm", qg, k, -1, -1)
        s = s * jnp.transpose(sa, (1, 2, 0, 3)) * sb[:, :, 0].T[:, None, None, :]
    else:
        s = jnp.einsum("nkgd,mkd->kgnm", qg, k, precision=HIGHEST)
    s = s * scale
    causal = jnp.tril(jnp.ones((n, n), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if lowp:
        o, sa, sb = _einsum8("kgnm,mkd->nkgd", p, v, -1, 0)
        o = o * jnp.transpose(sa, (2, 0, 1, 3)) * sb[:, :, None, :]
    else:
        o = jnp.einsum("kgnm,mkd->nkgd", p, v, precision=HIGHEST)
    return o.reshape(n, h * hd)


def layer(m: dict, x, lw: dict, pos, lowp: bool):
    hd = m["head_dim"]
    n = x.shape[0]
    h = rmsnorm(x, lw["ln1"], m["eps"])
    q = _mm(h, lw["wq"], lowp)
    k = _mm(h, lw["wk"], lowp)
    v = _mm(h, lw["wv"], lowp)
    if m["qkv_bias"]:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    q = rope(q.reshape(n, m["heads"], hd), pos, m["theta"])
    k = rope(k.reshape(n, m["kv_heads"], hd), pos, m["theta"])
    v = v.reshape(n, m["kv_heads"], hd)
    x = x + _mm(attention(q, k, v, lowp), lw["wo"], lowp)
    h = rmsnorm(x, lw["ln2"], m["eps"])
    gate = jax.nn.silu(_mm(h, lw["w_gate"], lowp))
    return x + _mm(gate * _mm(h, lw["w_up"], lowp), lw["w_down"], lowp)


_LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
               "w_down", "bq", "bk", "bv")


def hidden(m: dict, w: dict, tokens, lowp: bool = False):
    """Final normed hidden states (N, d) of one sequence (no padding
    before its tokens; positions 0..N-1)."""
    n = tokens.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    x = jnp.take(w["embed"], tokens, axis=0)
    stacked = {k: w[k] for k in _LAYER_KEYS if k in w}

    def body(x, lw):
        return layer(m, x, lw, pos, lowp), None

    x, _ = jax.lax.scan(body, x, stacked)
    return rmsnorm(x, w["final_norm"], m["eps"])


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
