"""§6.4.2 — preemptible-worker efficiency, measured on REAL execution.

Measures (CPU, reduced model — ratios are the point, and the safepoint check
itself is pure host-side work identical to production):
  * per-safepoint check cost (paper: 988us via torch barrier; ours is a
    host-side flag poll — the TPU dispatch boundary needs no barrier),
  * instrumentation overhead: segmented decode (``run_segment_paged_at``
    dispatches on ``RealEngine``) vs monolithic decode,
  * preemption response latency: flag set -> abort observed.

Usage: PYTHONPATH=src python -m benchmarks.run --only safepoint
Output: ``safepoint_*`` CSV rows (check cost us, overhead ratio, response
latency ms)."""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.request import Priority, Request
from repro.models import transformer as tf
from repro.serving.real_engine import RealEngine

from .common import row


def main() -> list:
    cfg = get_config("llama-2-7b").reduced(num_layers=8, safepoint_interval=2)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    def submit_offline(eng, n=4):
        for s in range(n):
            eng.submit(Request(
                Priority.OFFLINE, 32, 16,
                prompt=rng.integers(0, cfg.vocab_size, 32).astype(np.int32)))

    # -- instrumented engine (safepoints armed in offline mode) ------------
    eng = RealEngine(cfg, params)
    submit_offline(eng)
    t0 = time.perf_counter()
    eng.run()
    t_instrumented = time.perf_counter() - t0
    checks = eng.safepoints.stats.checks
    check_us = eng.safepoints.stats.mean_check_us

    # -- uninstrumented -----------------------------------------------------
    from repro.serving.real_engine import RealEngineConfig

    eng2 = RealEngine(cfg, params,
                      eng_cfg=RealEngineConfig(enable_safepoints=False))
    submit_offline(eng2)
    t0 = time.perf_counter()
    eng2.run()
    t_plain = time.perf_counter() - t0

    # -- preemption response latency ----------------------------------------
    eng3 = RealEngine(cfg, params)
    submit_offline(eng3, n=6)
    for _ in range(3):
        eng3.step()
    t0 = time.perf_counter()
    eng3.flag.set()
    while eng3.safepoints.stats.preemptions == 0:
        if not eng3.step():
            break
    t_respond = time.perf_counter() - t0

    overhead_pct = 100.0 * (t_instrumented - t_plain) / max(1e-9, t_plain)
    return [
        row("safepoint_check_cost_us", check_us,
            f"n_checks={checks} (paper: 988us incl. torch barrier)"),
        row("safepoint_instrumentation_overhead_pct", overhead_pct * 1000,
            f"instrumented_s={t_instrumented:.3f};plain_s={t_plain:.3f}"
            f" (paper: ~4% at K=8)"),
        row("preemption_response_ms", t_respond * 1e3 * 1e3,
            f"aborts={eng3.safepoints.stats.preemptions} (paper: 5.41ms)"),
    ]


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    for r in main():
        print(r)
