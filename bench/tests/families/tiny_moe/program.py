"""The test family as the program takes it: a ``moe`` ModelConfig with an
expert FFN in every layer, and the reference's weights in its layout."""
from __future__ import annotations


def model_config(cfg: dict, m: dict):
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=cfg["name"],
        arch_type="moe",
        source=cfg["source"],
        num_layers=m["layers"],
        d_model=m["d_model"],
        num_heads=m["heads"],
        num_kv_heads=m["kv_heads"],
        head_dim=m["head_dim"],
        d_ff=m["d_ff"],
        vocab_size=m["vocab"],
        activation="swiglu",
        num_experts=m["experts"],
        experts_per_token=m["top_k"],
        tie_embeddings=False,
        rope_theta=float(m["theta"]),
        norm_eps=float(m["eps"]),
        safepoint_interval=cfg["architecture"]["safepoint_interval"],
    )


def program_params(m: dict, w: dict) -> dict:
    L, d, hd = m["layers"], m["d_model"], m["head_dim"]
    h, kv = m["heads"], m["kv_heads"]
    return {
        "embed": w["embed"],
        "final_norm": w["final_norm"],
        "lm_head": w["lm_head"],
        "layers": {
            "0": {
                "norm1": w["ln1"],
                "norm2": w["ln2"],
                "mixer": {
                    "wq": w["wq"].reshape(L, d, h, hd),
                    "wk": w["wk"].reshape(L, d, kv, hd),
                    "wv": w["wv"].reshape(L, d, kv, hd),
                    "wo": w["wo"].reshape(L, h, hd, d),
                },
                "ffn": {k: w[k] for k in ("router", "w_gate", "w_up", "w_down")},
            }
        },
    }
