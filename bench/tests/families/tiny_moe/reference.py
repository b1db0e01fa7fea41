"""Plain reference of a test family: dense GQA attention and a Mixtral-style
mixture-of-experts FFN in every layer, written from HF ``modeling_mixtral``.

The router's logits over all experts, the top k of them, a softmax over the
k chosen, and the sum of those experts' SwiGLU outputs weighted by it.
Every expert is evaluated on every token; the unchosen ones weigh 0.
Untied head, no biases.  It imports nothing of the program under test.
No configuration of the benchmark uses this family: it shows that one
joins by its files alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import reference as ref


def dims(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    return {
        "family": cfg["family"],
        "layers": cfg["num_hidden_layers"],
        "d_model": cfg["hidden_size"],
        "head_dim": cfg["hidden_size"] // heads,
        "heads": heads,
        "kv_heads": cfg["num_key_value_heads"],
        "d_ff": cfg["intermediate_size"],
        "experts": cfg["num_local_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"],
        "theta": cfg["rope_theta"],
    }


def _shapes(m: dict) -> dict:
    L, d, hd, f, e = m["layers"], m["d_model"], m["head_dim"], m["d_ff"], m["experts"]
    h, kv, v = m["heads"], m["kv_heads"], m["vocab"]
    return {
        "embed": (v, d), "final_norm": (d,), "lm_head": (d, v),
        "ln1": (L, d), "ln2": (L, d),
        "wq": (L, d, h * hd), "wk": (L, d, kv * hd), "wv": (L, d, kv * hd),
        "wo": (L, h * hd, d),
        "router": (L, d, e),
        "w_gate": (L, e, d, f), "w_up": (L, e, d, f), "w_down": (L, e, f, d),
    }


def draw(m: dict, key: jax.Array) -> dict:
    shapes = _shapes(m)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        if name in ("ln1", "ln2", "final_norm"):
            mean, std = 1.0, 0.1
        elif name == "embed":
            mean, std = 0.0, 1.0
        else:
            mean, std = 0.0, shape[-2] ** -0.5  # fan-in
        out[name] = mean + std * jax.random.normal(k, shape, jnp.float32)
    return out


def moe(m: dict, h, lw: dict, lowp: bool):
    n = h.shape[0]
    logits = ref._mm(h, lw["router"], lowp)
    top, idx = jax.lax.top_k(logits, m["top_k"])
    weight = jnp.zeros_like(logits).at[jnp.arange(n)[:, None], idx].set(
        jax.nn.softmax(top, axis=-1))
    out = jnp.zeros_like(h)
    for e in range(m["experts"]):
        gate = jax.nn.silu(ref._mm(h, lw["w_gate"][e], lowp))
        y = ref._mm(gate * ref._mm(h, lw["w_up"][e], lowp), lw["w_down"][e], lowp)
        out = out + weight[:, e:e + 1] * y
    return out


def layer(m: dict, x, lw: dict, pos, lowp: bool):
    hd, n = m["head_dim"], x.shape[0]
    h = ref.rmsnorm(x, lw["ln1"], m["eps"])
    q = ref.rope(ref._mm(h, lw["wq"], lowp).reshape(n, m["heads"], hd), pos, m["theta"])
    k = ref.rope(ref._mm(h, lw["wk"], lowp).reshape(n, m["kv_heads"], hd), pos, m["theta"])
    v = ref._mm(h, lw["wv"], lowp).reshape(n, m["kv_heads"], hd)
    x = x + ref._mm(ref.attention(q, k, v, lowp), lw["wo"], lowp)
    return x + moe(m, ref.rmsnorm(x, lw["ln2"], m["eps"]), lw, lowp)


def hidden(m: dict, w: dict, tokens, lowp: bool = False):
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = jnp.take(w["embed"], tokens, axis=0)
    stacked = {k: v for k, v in w.items()
               if k not in ("embed", "final_norm", "lm_head")}

    def body(x, lw):
        return layer(m, x, lw, pos, lowp), None

    x, _ = jax.lax.scan(body, x, stacked)
    return ref.rmsnorm(x, w["final_norm"], m["eps"])
