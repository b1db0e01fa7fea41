"""Fused mixed-batch execution bench (DESIGN.md §12/§13), on REAL execution.

Measures three engine legs on an identical deterministic co-serving
workload (offline drain + online bursts, `slo_aware=False` so scheduling is
wall-clock independent and every engine executes the same iteration plans):

  * ``split``  — per-family dispatches (the differential oracle),
  * ``fused``  — one ragged dispatch per K-layer segment (DESIGN.md §12),
  * ``fused_pipelined`` — the fused path with the async host/device
    pipeline on (DESIGN.md §13): iteration N+1 is planned and built while
    N runs on device, sampling is an async readback.

Per leg it reports:

  * tokens/s over the timed pass (pass 1 warms every jit bucket; pass 2
    re-submits the same shapes, so the timed pass is compile-free),
  * device dispatches of the jitted model programs per engine
    (`RealEngine.dispatches`) and jit trace counts,
  * per-iteration latency p50/p99,
  * host-gap p50/p99 (fused legs; bucket midpoints of the engine's
    ``host_gap`` histogram): per-iteration device-idle time — the
    serial host span (sample readback, commit, plan, batch build) during
    which the device queue is empty, which the pipeline exists to kill,
  * byte-identical greedy tokens across all legs (hard assert — a kernel
    or pipeline regression fails this bench loudly).

Usage: PYTHONPATH=src python -m benchmarks.fused_batch_bench [--smoke]
           [--out BENCH_fused_batch.json] [--assert-pipeline-gap]
Output: key=value lines + a machine-readable JSON (default
``BENCH_fused_batch.json``) so the perf trajectory is tracked in-repo;
``--smoke`` runs a tiny config for CI, and ``--assert-pipeline-gap`` makes
the run fail fast if the pipelined leg's median host gap is not below the
serial fused leg's (the regression the CI smoke job guards).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.request import Priority, Request
from repro.core.scheduler import SchedulerConfig
from repro.models import transformer as tf
from repro.serving.metrics import Histogram
from repro.serving.real_engine import RealEngine, RealEngineConfig


def _workload(cfg, smoke: bool):
    """Deterministic mixed ON/OFF trace: (offline jobs, online bursts).

    Online bursts are (inject_at_step, [jobs]) — injected mid-drain so a
    co-served prefix (online decodes + offline prefill chunks in one plan)
    actually occurs, the composition the fused path exists to serve.
    """
    rng = np.random.default_rng(0)

    def mk(prio, plen, gen):
        return Request(
            prio, prompt_len=plen, max_new_tokens=gen,
            prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
        )

    if smoke:
        offline = [mk(Priority.OFFLINE, 40, 6 + 2 * i) for i in range(3)]
        bursts = [(2, [mk(Priority.ONLINE, 48, 4) for _ in range(2)])]
    else:
        offline = [mk(Priority.OFFLINE, 64, 12 + 2 * i) for i in range(6)]
        bursts = [
            (3, [mk(Priority.ONLINE, 48, 6) for _ in range(2)]),
            (9, [mk(Priority.ONLINE, 24, 8) for _ in range(2)]),
        ]
    return offline, bursts


def _drive(eng: RealEngine, offline, bursts):
    """Run one pass; returns (tokens emitted, per-iteration seconds)."""
    for r in offline:
        eng.submit(r)
    pending = sorted(bursts, key=lambda b: b[0])
    base = eng.steps
    iters = []
    while True:
        while pending and eng.steps - base >= pending[0][0]:
            for r in pending.pop(0)[1]:
                eng.on_online_arrival(r)
        t0 = time.perf_counter()
        alive = eng.step()
        iters.append(time.perf_counter() - t0)
        if not alive and not pending:
            break
    reqs = offline + [r for _, burst in bursts for r in burst]
    outs = [list(r.output_tokens) for r in reqs]
    return outs, sum(len(o) for o in outs), iters


def _bench(cfg, params, smoke: bool, fused: bool, pipeline: bool = False):
    eng = RealEngine(
        cfg, params,
        sched_cfg=SchedulerConfig(
            chunk_size=32, slo_aware=False, offline_batch_tokens=4096
        ),
        eng_cfg=RealEngineConfig(
            backend="paged", fused_batch=fused, pipeline=pipeline
        ),
    )
    # pass 1 warms every jit bucket; pass 2 re-submits identically-shaped
    # fresh requests (same seed, same prompts), so the timed pass is
    # compile-free — the steady-state serving regime
    _drive(eng, *_workload(cfg, smoke))
    d0 = dict(eng.dispatches)
    steps0 = eng.steps
    # the timed pass's host gaps alone
    eng.host_gap = Histogram(eng.host_gap.name, eng.host_gap.bounds)
    t0 = time.perf_counter()
    outs, ntok, iters = _drive(eng, *_workload(cfg, smoke))
    dt = time.perf_counter() - t0
    iters_ms = np.asarray(iters) * 1e3
    stats = {
        "tokens_per_s": round(ntok / dt, 2),
        "wall_s": round(dt, 4),
        "tokens": ntok,
        "iterations": eng.steps - steps0,
        "dispatches": {
            k: eng.dispatches[k] - d0[k] for k in eng.dispatches
        },
        "iter_p50_ms": round(float(np.percentile(iters_ms, 50)), 3),
        "iter_p99_ms": round(float(np.percentile(iters_ms, 99)), 3),
        "trace_counts": {
            "fused": eng.fused_trace_count,
            "prefill": eng.prefill_trace_count,
            "decode": eng.decode_trace_count,
            "pipeline": eng.pipeline_trace_count,
        },
    }
    if eng.host_gap.count:  # fused legs only (the split path never samples gaps)
        stats["host_gap_p50_ms"] = round(eng.host_gap.percentile(50) * 1e3, 3)
        stats["host_gap_p99_ms"] = round(eng.host_gap.percentile(99) * 1e3, 3)
    if pipeline:
        stats["pipeline_discards"] = eng.pipeline_discards
    return outs, stats


def main(
    smoke: bool = False,
    out: str = "BENCH_fused_batch.json",
    assert_pipeline_gap: bool = False,
) -> dict:
    cfg = get_config("llama-2-7b").reduced(
        num_layers=2 if smoke else 4
    )
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    outs_f, fused = _bench(cfg, params, smoke, fused=True)
    outs_p, fused_pipelined = _bench(
        cfg, params, smoke, fused=True, pipeline=True
    )
    outs_s, split = _bench(cfg, params, smoke, fused=False)
    assert outs_f == outs_s, (
        "fused path diverged from split path — kernel regression"
    )
    assert outs_p == outs_f, (
        "pipelined path diverged from serial fused path — "
        "speculation/deferred-token regression"
    )
    result = {
        "bench": "fused_batch",
        "model": cfg.name,
        "num_layers": cfg.num_layers,
        "num_segments": tf.num_segments(cfg),
        "smoke": smoke,
        "identical_tokens": True,
        "fused": fused,
        "fused_pipelined": fused_pipelined,
        "split": split,
        "speedup": round(
            fused["tokens_per_s"] / max(split["tokens_per_s"], 1e-9), 3
        ),
        "pipeline_speedup": round(
            fused_pipelined["tokens_per_s"]
            / max(split["tokens_per_s"], 1e-9),
            3,
        ),
    }
    with open(out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    for side in ("fused", "fused_pipelined", "split"):
        r = result[side]
        nd = sum(r["dispatches"].values())
        gap = (
            f" gap_p50_ms={r['host_gap_p50_ms']} "
            f"gap_p99_ms={r['host_gap_p99_ms']}"
            if "host_gap_p50_ms" in r
            else ""
        )
        print(
            f"{side}: tokens_per_s={r['tokens_per_s']} "
            f"dispatches={nd} iters={r['iterations']} "
            f"p50_ms={r['iter_p50_ms']} p99_ms={r['iter_p99_ms']}{gap}"
        )
    print(
        f"speedup={result['speedup']} "
        f"pipeline_speedup={result['pipeline_speedup']} "
        f"identical_tokens=True out={out}"
    )
    if assert_pipeline_gap:
        on = fused_pipelined["host_gap_p50_ms"]
        off = fused["host_gap_p50_ms"]
        assert on < off, (
            f"pipeline-on median host gap ({on}ms) is not below "
            f"pipeline-off ({off}ms) — the overlap regressed"
        )
        print(f"pipeline_gap_ok: on_p50={on}ms < off_p50={off}ms")
    return result


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config for CI smoke")
    ap.add_argument("--out", default="BENCH_fused_batch.json")
    ap.add_argument(
        "--assert-pipeline-gap", action="store_true",
        help="fail if the pipelined leg's median host gap is not below "
             "the serial fused leg's",
    )
    args = ap.parse_args()
    main(
        smoke=args.smoke, out=args.out,
        assert_pipeline_gap=args.assert_pipeline_gap,
    )
