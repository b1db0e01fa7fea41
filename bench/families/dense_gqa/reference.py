"""Plain reference of the dense GQA decoder family (Qwen2, Llama-style).

Straightforward ``jax.numpy`` in float32 with every matmul at precision
HIGHEST, written from the published description (HF ``modeling_qwen2`` /
``modeling_llama``): RMSNorm, rotary embeddings (rotate-half form), grouped
query attention with optional QKV bias, SwiGLU, tied or untied head.  It
imports nothing of the program under test; the parts every family shares
are ``bench/reference.py``'s.

Weights are drawn on the device from a key of the run's seed (``draw``),
with every parameter nonzero: biases and norm scales included.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import reference as ref


def dims(cfg: dict) -> dict:
    """The model's sizes from its configuration file (HF key names)."""
    heads = cfg["num_attention_heads"]
    d = {
        "family": cfg["family"],
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


def layer(m: dict, x, lw: dict, pos, lowp: bool):
    hd = m["head_dim"]
    n = x.shape[0]
    h = ref.rmsnorm(x, lw["ln1"], m["eps"])
    q = ref._mm(h, lw["wq"], lowp)
    k = ref._mm(h, lw["wk"], lowp)
    v = ref._mm(h, lw["wv"], lowp)
    if m["qkv_bias"]:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    q = ref.rope(q.reshape(n, m["heads"], hd), pos, m["theta"])
    k = ref.rope(k.reshape(n, m["kv_heads"], hd), pos, m["theta"])
    v = v.reshape(n, m["kv_heads"], hd)
    x = x + ref._mm(ref.attention(q, k, v, lowp), lw["wo"], lowp)
    h = ref.rmsnorm(x, lw["ln2"], m["eps"])
    gate = jax.nn.silu(ref._mm(h, lw["w_gate"], lowp))
    return x + ref._mm(gate * ref._mm(h, lw["w_up"], lowp), lw["w_down"], lowp)


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
    return ref.rmsnorm(x, w["final_norm"], m["eps"])
