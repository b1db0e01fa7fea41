"""The dense GQA family as the program takes it: its ``ModelConfig`` and
the reference's weights in the program's layout.  The program is imported
inside the functions only, so loading this file touches nothing of it."""
from __future__ import annotations


def model_config(cfg: dict, m: dict):
    """The program's ``ModelConfig`` for this configuration file, of sizes
    ``m`` (``reference.dims``)."""
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=cfg["name"],
        arch_type="dense",
        source=cfg["source"],
        num_layers=m["layers"],
        d_model=m["d_model"],
        num_heads=m["heads"],
        num_kv_heads=m["kv_heads"],
        head_dim=m["head_dim"],
        d_ff=m["d_ff"],
        vocab_size=m["vocab"],
        activation="swiglu",
        qkv_bias=m["qkv_bias"],
        tie_embeddings=m["tied"],
        rope_theta=float(m["theta"]),
        norm_eps=float(m["eps"]),
        safepoint_interval=cfg["architecture"]["safepoint_interval"],
    )


def program_params(m: dict, w: dict) -> dict:
    """``reference.draw``'s weights in the program's layout (one period per
    layer, heads split out of the projection columns)."""
    L, d, hd = m["layers"], m["d_model"], m["head_dim"]
    h, kv = m["heads"], m["kv_heads"]
    mixer = {
        "wq": w["wq"].reshape(L, d, h, hd),
        "wk": w["wk"].reshape(L, d, kv, hd),
        "wv": w["wv"].reshape(L, d, kv, hd),
        "wo": w["wo"].reshape(L, h, hd, d),
    }
    if m["qkv_bias"]:
        mixer.update(
            bq=w["bq"].reshape(L, h, hd),
            bk=w["bk"].reshape(L, kv, hd),
            bv=w["bv"].reshape(L, kv, hd),
        )
    params = {
        "embed": w["embed"],
        "final_norm": w["final_norm"],
        "layers": {
            "0": {
                "norm1": w["ln1"],
                "norm2": w["ln2"],
                "mixer": mixer,
                "ffn": {"w_up": w["w_up"], "w_gate": w["w_gate"],
                        "w_down": w["w_down"]},
            }
        },
    }
    if "lm_head" in w:
        params["lm_head"] = w["lm_head"]
    return params
