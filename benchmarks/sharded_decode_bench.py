"""Tensor-parallel paged serving microbench (DESIGN.md §11).

Measures the sharded RealEngine on virtual CPU devices (the ratios, retrace
counts and preempt/resume costs are the point; a TPU slice runs the
identical code path with the shard_mapped Pallas kernel):

  * decode step latency across a draining batch at mesh sizes 1/2/4,
    with ``decode_trace_count`` retraces (bucketing must stay mesh-
    independent — sharding adds no jit cache keys),
  * preempt -> resume cost on the sharded pool (table edits + O(block)
    replicated-host restores scattered into per-shard heads).

Usage: PYTHONPATH=src python -m benchmarks.sharded_decode_bench [--devices 4]
Output: ``tp<N>_*`` CSV rows (``name,us_per_call,derived``) in the same
format as ``paged_decode_bench``.

The virtual-device override must precede the first jax import, so this
module sets XLA_FLAGS itself and imports jax lazily inside ``main``.
"""
from __future__ import annotations

import argparse
import os
import time


def main() -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--devices", type=int, default=4,
                    help="virtual CPU devices to create (mesh sizes sweep "
                         "the powers of two up to this)")
    args, _ = ap.parse_known_args()

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.request import Priority, Request
    from repro.launch.mesh import make_serving_mesh
    from repro.models import transformer as tf
    from repro.serving.real_engine import RealEngine, RealEngineConfig

    from .common import row

    cfg = get_config("llama-2-7b").reduced(num_layers=4)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))

    def engine(tp: int, **eng_kw) -> RealEngine:
        return RealEngine(
            cfg, params,
            eng_cfg=RealEngineConfig(
                backend="paged", enable_safepoints=False,
                mesh=make_serving_mesh(tp), **eng_kw,
            ),
        )

    def submit(eng: RealEngine, n: int, gen: int, plen: int = 64) -> list:
        rng = np.random.default_rng(0)
        reqs = [
            Request(
                Priority.OFFLINE, prompt_len=plen, max_new_tokens=gen,
                prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            )
            for _ in range(n)
        ]
        for r in reqs:
            eng.submit(r)
        return reqs

    mesh_sizes = [t for t in (1, 2, 4) if t <= len(jax.devices())]
    out = []
    baseline = None
    for tp in mesh_sizes:
        # -- decode wall time + retraces across a draining batch -----------
        eng = engine(tp)
        reqs = submit(eng, 8, gen=8)
        for i, r in enumerate(reqs):
            r.max_new_tokens = 8 + 2 * i
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        us = 1e6 * dt / max(1, eng.steps)
        tokens = [r.output_tokens for r in reqs]
        if baseline is None:
            baseline = tokens
        assert tokens == baseline, f"tp={tp} diverged from tp=1 tokens"
        out.append(
            row(
                f"tp{tp}_drain", us,
                f"fused_retraces={eng.fused_trace_count};"
                f"decode_retraces={eng.decode_trace_count};"
                f"prefill_retraces={eng.prefill_trace_count}",
            )
        )
        # -- preempt/resume cost -------------------------------------------
        eng = engine(tp, num_device_blocks=14)
        reqs = submit(eng, 3, gen=24, plen=40)
        for _ in range(8):
            eng.step()
        rng = np.random.default_rng(1)
        t0 = time.perf_counter()
        for _ in range(2):
            eng.on_online_arrival(
                Request(
                    Priority.ONLINE, prompt_len=60, max_new_tokens=8,
                    prompt=rng.integers(0, cfg.vocab_size, 60).astype(
                        np.int32
                    ),
                )
            )
        eng.run()
        dt = time.perf_counter() - t0
        npre = sum(r.num_preemptions for r in reqs)
        out.append(
            row(
                f"tp{tp}_preempt_resume", 1e6 * dt / max(1, npre),
                f"preemptions={npre}",
            )
        )
    return out


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    for line in main():
        print(line)
