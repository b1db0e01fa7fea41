"""Edge-coverage units: API frontends, act-sharding no-op guarantees,
request lifecycle, config pattern machinery, sampling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_configs, get_config
from repro.core.request import Phase, Priority, Request
from repro.distributed.act_sharding import (
    constrain_block_input,
    constrain_heads,
    constrain_residual,
    model_axis_size,
)
from repro.models.config import INPUT_SHAPES, shape_applicable
from repro.models.sampling import SamplingParams, sample


def test_act_sharding_noops_without_context():
    """Model code must be distribution-agnostic: constraints are identity
    when no mesh context is installed (CPU tests / real engine)."""
    x = jnp.ones((2, 8, 16))
    assert constrain_residual(x) is x
    assert constrain_block_input(x, weight_bytes=10**9) is x
    q = jnp.ones((2, 8, 4, 16))
    assert constrain_heads(q) is x or constrain_heads(q) is q
    assert model_axis_size() == 0


def test_request_lifecycle_and_metrics():
    r = Request(Priority.ONLINE, prompt_len=10, max_new_tokens=3,
                arrival_time=1.0)
    assert r.kv_target == 10  # fresh: whole prompt
    r.num_prefilled = 10
    r.record_token(2.0)
    assert r.ttft == 1.0
    assert r.kv_target == 10  # g=1: last token fed by decode itself
    r.record_token(2.1)
    r.record_token(2.3)
    assert r.phase == Phase.FINISHED
    assert r.tpots() == pytest.approx([0.1, 0.2], abs=1e-9)
    r2 = Request(Priority.OFFLINE, prompt_len=5, max_new_tokens=5)
    r2.num_prefilled = 5
    r2.record_token(0.0)
    r2.on_preempt(recoverable_tokens=4)
    assert r2.phase == Phase.PREEMPTED and r2.num_prefilled == 0
    assert r2.prefill_remaining == 5  # p + g - 1 = 5 tokens of device state


def test_layer_patterns():
    jamba = get_config("jamba-1.5-large-398b")
    pat = jamba.layer_pattern()
    assert len(pat) == 8
    assert [s.mixer for s in pat].count("attn") == 1
    assert [s.ffn for s in pat].count("moe") == 4  # every other layer
    vlm = get_config("llama-3.2-vision-11b")
    assert [s.mixer for s in vlm.layer_pattern()].count("cross_attn") == 1
    assert vlm.num_periods == 8
    mamba = get_config("mamba2-1.3b")
    assert mamba.pattern_period == 1 and mamba.has_ssm_state
    assert not mamba.has_kv_cache


def test_shape_applicability_matrix():
    """16 skips expected across the 40-combo matrix, per the assignment."""
    skips = []
    for name, cfg in all_configs().items():
        if name == "llama-2-7b":
            continue
        for sname, shape in INPUT_SHAPES.items():
            ok, why = shape_applicable(cfg, shape)
            if not ok:
                skips.append((name, sname))
    assert len(skips) == 8  # per mesh; x2 meshes = 16 artifacts
    assert ("hubert-xlarge", "decode_32k") in skips
    assert ("hubert-xlarge", "long_500k") in skips
    assert ("mamba2-1.3b", "long_500k") not in skips
    assert ("jamba-1.5-large-398b", "long_500k") not in skips
    assert ("mixtral-8x22b", "long_500k") not in skips  # SWA ring buffer
    assert ("command-r-plus-104b", "long_500k") in skips


def test_sampling_greedy_and_temperature():
    logits = jnp.asarray([[0.0, 5.0, 1.0], [3.0, 0.0, 0.0]])
    out = sample(logits, SamplingParams(temperature=0.0), jax.random.PRNGKey(0))
    assert out.tolist() == [1, 0]
    # top-k truncation keeps only the argmax at k=1 even with temperature
    out2 = sample(
        logits, SamplingParams(temperature=1.0, top_k=1), jax.random.PRNGKey(1)
    )
    assert out2.tolist() == [1, 0]


def test_reduced_configs_are_smoke_sized():
    for name, cfg in all_configs().items():
        r = cfg.reduced()
        assert r.d_model <= 512
        assert (r.num_experts or 0) <= 4
        assert r.num_layers <= 2 * max(1, cfg.pattern_period)
        assert r.num_periods >= 1  # pattern still divides


def test_stream_handle_incremental_poll():
    from repro.serving.api import StreamHandle

    r = Request(Priority.ONLINE, prompt_len=4, max_new_tokens=3,
                prompt=np.arange(4, dtype=np.int32))
    h = StreamHandle(r)
    assert h.poll() == []
    r.output_tokens.extend([7, 8])
    assert h.poll() == [7, 8]
    assert h.poll() == []
    r.output_tokens.append(9)
    assert h.poll() == [9]


@pytest.mark.parametrize("platform,kind,expect", [
    ("cpu", "cpu", "tpu-v5e"),  # off the chip: the planning target
    ("tpu", "TPU v5 lite", "tpu-v5e"),
    ("tpu", "TPU v9 imaginary", None),  # unknown chip: an error
])
def test_hardware_for_keys_peaks_by_device_kind(platform, kind, expect):
    from types import SimpleNamespace

    from repro.core.profiler import hardware_for

    dev = SimpleNamespace(platform=platform, device_kind=kind)
    if expect is None:
        with pytest.raises(ValueError, match="no peak table"):
            hardware_for(dev)
    else:
        assert hardware_for(dev).name == expect
