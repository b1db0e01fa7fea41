"""Paged backend microbench (DESIGN.md §8), measured on REAL execution.

Measures on CPU with a reduced model (the ratios and trace counts are the
point; the TPU path runs identical code with Pallas kernels):
  * decode step latency: paged shared pool vs contiguous stacked caches,
    across batch sizes,
  * jit retraces across a draining batch (sizes B..1): bucketed paged
    shapes vs per-size contiguous shapes,
  * preempt->resume cost on the paged pool (pure table edits + O(block)
    restores) vs the contiguous extract/slice path.

Usage: PYTHONPATH=src python -m benchmarks.paged_decode_bench
Output: ``paged_*``/``contig_*`` CSV rows (``name,us_per_call,derived``),
including ``*_retraces`` counts from ``decode_trace_count``.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.request import Priority, Request
from repro.models import transformer as tf
from repro.serving.real_engine import RealEngine, RealEngineConfig

from .common import row


def _engine(backend: str, **eng_kw) -> RealEngine:
    cfg = get_config("llama-2-7b").reduced(num_layers=4)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    return RealEngine(
        cfg, params,
        eng_cfg=RealEngineConfig(backend=backend, enable_safepoints=False,
                                 **eng_kw),
    )


def _submit(eng: RealEngine, n: int, gen: int, plen: int = 64) -> list:
    rng = np.random.default_rng(0)
    reqs = [
        Request(
            Priority.OFFLINE, prompt_len=plen, max_new_tokens=gen,
            prompt=rng.integers(0, eng.cfg.vocab_size, plen).astype(np.int32),
        )
        for _ in range(n)
    ]
    for r in reqs:
        eng.submit(r)
    return reqs


def _timed_run(eng: RealEngine) -> float:
    t0 = time.perf_counter()
    eng.run()
    return time.perf_counter() - t0


def main() -> list:
    out = []
    # -- decode wall time + retraces across a draining batch ---------------
    for backend in ("paged", "contiguous"):
        eng = _engine(backend)
        # staggered gens -> decode batch shrinks 8..1 as requests finish
        reqs = _submit(eng, 8, gen=8)
        for i, r in enumerate(reqs):
            r.max_new_tokens = 8 + 2 * i
        dt = _timed_run(eng)
        out.append(
            row(
                f"drain_{backend}",
                1e6 * dt / max(1, eng.steps),
                # the paged leg serves the fused ragged path (§12), so its
                # retraces are fused-segment programs; contiguous keeps the
                # decode-program count
                f"decode_retraces={eng.decode_trace_count};"
                f"fused_retraces={getattr(eng, 'fused_trace_count', 0)}",
            )
        )
    # -- preempt/resume cost ----------------------------------------------
    for backend in ("paged", "contiguous"):
        eng = _engine(backend, num_device_blocks=14)
        reqs = _submit(eng, 3, gen=24, plen=40)
        for _ in range(8):
            eng.step()
        rng = np.random.default_rng(1)
        t0 = time.perf_counter()
        for s in range(2):
            eng.on_online_arrival(
                Request(
                    Priority.ONLINE, prompt_len=60, max_new_tokens=8,
                    prompt=rng.integers(0, eng.cfg.vocab_size, 60).astype(
                        np.int32
                    ),
                )
            )
        eng.run()
        dt = time.perf_counter() - t0
        npre = sum(r.num_preemptions for r in reqs)
        out.append(
            row(
                f"preempt_resume_{backend}",
                1e6 * dt / max(1, npre),
                f"preemptions={npre}",
            )
        )
    return out


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    for line in main():
        print(line)
