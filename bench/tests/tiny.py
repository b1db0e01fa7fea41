"""A configuration and mixes small enough for the CPU, shaped like the
benchmark's own (same keys, same generator, same engine path)."""
from __future__ import annotations

import copy
import json
import os

from bench import traffic as gen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(tied: bool = True, bias: bool = True) -> dict:
    with open(os.path.join(BENCH, "configs", "qwen2-0.5b.json")) as f:
        cfg = json.load(f)
    cfg.update(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=4, vocab_size=512,
        tie_word_embeddings=tied,
    )
    cfg["architecture"] = dict(cfg["architecture"], qkv_bias=bias,
                               safepoint_interval=2)
    cfg["serving"] = {"max_model_len": 128, "block_size": 16,
                      "kv_blocks": 48, "host_kv_blocks": 96}
    cfg["knee_req_per_s"] = 5.0
    cfg["check"] = dict(cfg["check"], min_tokens=40)
    return cfg


def mix(offline: bool = True) -> dict:
    """online-chat at a small size; with ``offline``, beside a backlog that
    never drains, as a co-serving mix would have it."""
    m = copy.deepcopy(gen.load_mix("online-chat"))
    m["online"]["prompt"] = {"median": 32, "sigma": 0.8, "min": 8, "max": 64}
    m["online"]["output"] = {"median": 8, "sigma": 0.5, "min": 4, "max": 16}
    if offline:
        m["offline"] = dict(
            pool=256, waiting=4, preroll_s=0.5,
            prompt={"median": 48, "sigma": 0.5, "min": 16, "max": 96},
            output={"median": 8, "sigma": 0.5, "min": 4, "max": 16},
        )
    return m
