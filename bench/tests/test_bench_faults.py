"""A whole run of the harness without the chip, at a small size: sound, it
comes out correct; with the timed path broken underneath, ``correct``
comes out false; and the control (the float8 reference in the program's
place) fails the limit.

The faults a one-chip serving cell can have: a token altered where it is
produced (the sampler), a step that leaves its state unchanged (the KV
write of the fused batch dropped), half of the work left out (attention
over half of each sequence's context).  No exchange between chips exists
on one chip.
"""
import functools
import json

import jax
import numpy as np
import pytest

from bench import check, reference
from bench import run as bench_run
from bench import program
from bench.tests import tiny

CELL = {"name": "qwen2-0.5b.online-chat", "config": "tiny", "traffic": "tiny-coserve",
        "chips": 1, "why": "test"}


@pytest.fixture
def small_run(monkeypatch, tmp_path):
    """``run.main`` on the tiny configuration: no chip check, no persistent
    cache, a small calibration grid and no warm-up (compiles in the window
    cost only time here)."""
    from repro.core.profiler import CalibrationGrid
    from repro.serving.real_engine import RealEngine

    spec = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    cfg = tiny.config()
    monkeypatch.setattr(bench_run, "load_cell",
                        lambda name: (spec, CELL, cfg, tiny.mix()))
    monkeypatch.setattr(bench_run, "configure_jax", bench_run.set_precision)
    monkeypatch.setattr(bench_run, "SHAPES_DIR", str(tmp_path))
    monkeypatch.setattr(program, "warm_up",
                        lambda eng, n, **kw: {"fused_shapes": 0,
                                                    "sampler_shapes": 0})
    grid = CalibrationGrid(chunk_sizes=(8, 32), decode_buckets=(1, 4),
                           token_buckets=(), repeats=1, warmup=0)
    monkeypatch.setattr(RealEngine, "calibrate",
                        functools.partialmethod(RealEngine.calibrate, grid))
    was = jax.config.jax_default_matmul_precision
    jax.clear_caches()
    yield lambda seed: bench_run.main(
        ["--workload", CELL["name"], "--seed", str(seed), "--seconds", "2",
         "--trace", "0"], require_chip=False)
    jax.config.update("jax_default_matmul_precision", was)
    jax.clear_caches()


def test_sound_run_is_correct(small_run, capsys):
    res = small_run(2**33 + 3)
    out = capsys.readouterr()
    assert res["correct"], res
    assert json.loads(out.out.strip().splitlines()[-1]) == res
    assert list(res)[-1] == "check"
    assert out.err.strip().splitlines()[-1].startswith("check ")
    assert set(res["metrics"]) >= {"setup_s", "online_tpot_p50_ms"}


def _alter_tokens(sample):
    def altered(logits, params, key):
        tok = sample(logits, params, key)
        return jax.numpy.where(tok % 3 == 0, (tok + 1) % logits.shape[-1], tok)
    return altered


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_context"])
def test_broken_timed_path_is_not_correct(small_run, fault, monkeypatch):
    from repro.kernels import ops
    from repro.kvcache import cache_ops
    from repro.serving import real_engine

    if fault == "token_altered":
        monkeypatch.setattr(real_engine, "sample", _alter_tokens(real_engine.sample))
    elif fault == "state_unchanged":
        monkeypatch.setattr(cache_ops, "write_ragged",
                            lambda kp, vp, k, v, row, off: (kp, vp))
    else:
        inner = ops.ragged_paged_attention

        def half(q, kp, vp, tables, q_pos, kv_lens, **kw):
            return inner(q, kp, vp, tables, q_pos, (kv_lens + 1) // 2, **kw)

        monkeypatch.setattr(ops, "ragged_paged_attention", half)
    res = small_run(2**33 + 4)
    assert not res["correct"], res


def test_control_fails_the_limit(small_run):
    """The control at this size: the float8 reference's first choices lie
    farther below the float32 reference's best than the limit allows."""
    cfg = tiny.config()
    m = reference.dims(cfg)
    w = reference.draw_jit(m, 9)
    rng = np.random.default_rng(9)
    picked = [(i, rng.integers(0, m["vocab"], 40).astype(np.int32),
               list(rng.integers(0, m["vocab"], 40))) for i in range(4)]
    lowp = check.served_gaps(m, w, picked, 128, lowp_targets=True)
    assert max(float(g.max()) for g in lowp) > cfg["check"]["max_logit_gap"]
