"""Wall-clock co-serving benchmark — the paper's §6 experiment on REAL
execution (DESIGN.md §10).

What it measures: replays a ``loadgen`` trace (ON/OFF phased bursts by
default, or a gamma process) through ``CoServingRuntime`` driving
``RealEngine``'s paged backend, after an on-device calibration pass
(``RealEngine.calibrate``) fits the latency profile that ``calc_budget``
schedules against.  A deterministic 3-arrival "burst probe" lands inside
the initial offline prefill wave — the paper's burst-into-harvest moment —
so the run always exercises Algorithm 2's mid-iteration abort path.

SLOs default to multiples of *measured* single-iteration times
(``--ttft-scale`` x one online chunk, ``--tpot-scale`` x one decode
iteration), i.e. they are aggressive on purpose: the point is to watch the
runtime preempt offline work at real safepoints to protect them.  Pass
absolute ``--ttft``/``--tpot`` to override.

Usage:
  PYTHONPATH=src python -m benchmarks.coserve_wallclock_bench [--duration 3]

Expected output format (key=value lines, wall-clock seconds/tokens):
  calibrated model=<arch> ... t_chunk_ms=... t_decode_ms=...
  slo ttft_ms=... tpot_ms=...
  p99_ttft_ms=... p99_tpot_ms=... ttft_attainment=... tpot_attainment=...
  throughput_tok_s=... online_tok_s=... offline_tok_s=...
  preemptions=<evictions> safepoint_aborts=<Alg.2 mid-iteration aborts>
  preemption_latency_ms=<mean flag->abort latency, - if none>
On CPU this runs the reduced model through the jnp oracle kernels; on TPU
the identical code path dispatches the Pallas kernels.
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default="llama-2-7b")
    ap.add_argument("--trace", choices=["onoff", "gamma"], default="onoff")
    ap.add_argument("--duration", type=float, default=3.0)
    ap.add_argument("--rate", type=float, default=6.0)
    ap.add_argument("--cv", type=float, default=1.0)  # gamma trace only
    ap.add_argument("--on", type=float, default=0.6)
    ap.add_argument("--off", type=float, default=1.2)
    ap.add_argument("--offline", type=int, default=10)
    ap.add_argument("--online-prompt", type=int, default=24)
    ap.add_argument("--online-new", type=int, default=6)
    # prompts straddle the chunk size so every prefill wave spans several
    # length buckets -> several dispatches -> several safepoint boundaries
    ap.add_argument("--offline-prompt", type=int, default=40)
    ap.add_argument("--offline-new", type=int, default=20)
    ap.add_argument("--ttft", type=float, default=None, help="absolute SLO (s)")
    ap.add_argument("--tpot", type=float, default=None)
    ap.add_argument("--ttft-scale", type=float, default=1.5)
    ap.add_argument("--tpot-scale", type=float, default=3.0)
    # tensor-parallel mesh size (DESIGN.md §11).  The default 1-device mesh
    # runs the mesh-aware code path (placement, constraints) on any machine
    # and must behave identically to mesh-free serving — the safepoint-abort
    # guarantee below holds on it unchanged.
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: short duration, small offline pool, "
                         "single-repeat calibration grid")
    ap.add_argument("--assert-metrics", action="store_true",
                    help="scrape the metrics registry mid-replay and fail "
                         "unless gauges are live, counters monotone, and "
                         "the final surface matches ServiceMetrics")
    ap.add_argument("--inject-faults", action="store_true",
                    help="seeded fault injection during the replay "
                         "(DESIGN.md §16): request-scoped dispatch faults "
                         "plus allocator/host-pool degradation faults; "
                         "asserts the engine survives and recovers")
    ap.add_argument("--fault-seed", type=int, default=0)
    args = ap.parse_args()

    import threading

    import jax

    from repro.configs import get_config
    from repro.core.faults import FaultInjector, RuntimeHealth
    from repro.core.profiler import BatchShape, CalibrationGrid
    from repro.core.scheduler import SchedulerConfig
    from repro.core.slo import SLO
    from repro.launch.mesh import make_serving_mesh
    from repro.models import transformer as tf
    from repro.serving import loadgen
    from repro.serving.real_engine import RealEngine, RealEngineConfig
    from repro.serving.runtime import CoServingRuntime

    grid = None
    if args.smoke:
        args.duration = min(args.duration, 1.0)
        args.offline = min(args.offline, 6)
        # same bucket coverage the auto-derived grid warms (chunk_size=32,
        # max_prefill_batch=4, max_batch_seqs=8 below) so the replay still
        # never compiles mid-run, but one timed repeat and one context depth
        grid = CalibrationGrid(
            chunk_sizes=(8, 16, 32),
            prefill_batches=(1, 2, 4),
            decode_buckets=(1, 2, 4, 8),
            ctx_fractions=(0.25,),
            token_buckets=(64, 128),
            repeats=1,
            warmup=1,
        )

    cfg = get_config(args.arch).reduced(num_layers=4, safepoint_interval=1)
    params = tf.init_params(cfg, jax.random.PRNGKey(args.seed))
    sched_cfg = SchedulerConfig(
        chunk_size=32, slo_aware=True, avg_ctx_estimate=64, max_batch_seqs=8
    )
    # contiguous-fallback archs (SSM/SWA/cross-attn) cannot shard — run
    # them mesh-free as before; --tp > 1 on such an arch fails loudly in
    # RealEngine with the paged-backend requirement
    mesh = (
        make_serving_mesh(args.tp)
        if args.tp > 1 or tf.supports_paged(cfg)
        else None
    )
    # --inject-faults: a seeded schedule of request-scoped dispatch faults
    # plus block-manager degradation faults, all landing inside the first
    # ~120 engine iterations (the ON/OFF drain).  Faults are injected into
    # the engine; the assertions below check the runtime absorbed them
    # (DESIGN.md §16).
    faults = None
    if args.inject_faults:
        faults = FaultInjector.seeded(
            args.fault_seed,
            {
                "dispatch": {"n": 2, "window": 24, "scope": "request"},
                "alloc.grow": {"n": 2, "window": 40},
                "host.checkpoint": {"n": 2, "window": 20},
                "host.swap_out": {"n": 1, "window": 8},
            },
        )

    eng = RealEngine(
        cfg,
        params,
        sched_cfg=sched_cfg,
        # the fused path (DESIGN.md §12) safepoints between the 4 K-layer
        # segment dispatches of EVERY pure-offline iteration — prefill
        # waves included; max_prefill_batch=4 keeps the split-path twin
        # (fused_batch=False) exposing >=1 prefill-group boundary too
        eng_cfg=RealEngineConfig(
            max_model_len=128, num_device_blocks=256, block_size=16,
            max_prefill_batch=4, mesh=mesh, faults=faults,
        ),
    )

    t0 = time.perf_counter()
    prof = eng.calibrate(grid)
    t_chunk = prof.iter_time(
        BatchShape(
            prefill_tokens=32,
            prefill_attn_tokens=32 * 16.0,
            prefill_ctx_end=32,
            num_seqs=1,
        )
    )
    t_dec = prof.iter_time(
        BatchShape(decode_tokens=8, decode_ctx=8 * 64, num_seqs=8)
    )
    print(
        f"calibrated model={cfg.name} backend={jax.default_backend()} "
        f"tp={args.tp} calibration_s={time.perf_counter() - t0:.1f} "
        f"t_chunk_ms={t_chunk * 1e3:.1f} t_decode_ms={t_dec * 1e3:.1f}"
    )

    slo = SLO(
        ttft=args.ttft if args.ttft is not None else args.ttft_scale * t_chunk,
        tpot=args.tpot if args.tpot is not None else args.tpot_scale * t_dec,
    )
    eng.sched.slo = slo
    print(f"slo ttft_ms={slo.ttft * 1e3:.0f} tpot_ms={slo.tpot * 1e3:.0f}")

    # ---- trace ------------------------------------------------------------
    offline = loadgen.make_offline_batch(
        args.offline,
        loadgen.LengthSpec(args.offline_prompt, args.offline_new, 0.5, 0.3),
        np.random.default_rng(args.seed + 1),
    )
    if args.trace == "onoff":
        times = loadgen.onoff_arrivals(
            args.rate, args.on, args.off, args.duration,
            np.random.default_rng(args.seed + 2),
        )
        times = [t + 0.4 for t in times]
    else:
        times = loadgen.gamma_arrivals(
            args.rate, args.cv, args.duration,
            np.random.default_rng(args.seed + 2), start=0.4,
        )
    # deterministic burst probe into the initial offline prefill wave (the
    # first dispatch boundary is its earliest possible delivery point)
    times = [0.02, 0.03, 0.04] + times
    online = loadgen.make_online_requests(
        times,
        loadgen.LengthSpec(args.online_prompt, args.online_new, 0.2, 0.2),
        np.random.default_rng(args.seed + 3),
    )
    loadgen.attach_prompts(
        online + offline, cfg.vocab_size, np.random.default_rng(args.seed + 4)
    )

    # ---- replay -----------------------------------------------------------
    rt = CoServingRuntime(eng)

    # --assert-metrics: scrape the registry from another thread while the
    # replay runs — exactly what a production scraper does (DESIGN.md §15).
    snaps: list = []
    scrape_stop = threading.Event()

    def scrape() -> None:
        while not scrape_stop.is_set():
            snaps.append(rt.registry.snapshot())
            time.sleep(0.05)

    scraper = None
    if args.assert_metrics:
        scraper = threading.Thread(target=scrape, daemon=True)
        scraper.start()

    m = rt.replay(online + offline)

    if scraper is not None:
        scrape_stop.set()
        scraper.join(timeout=2.0)

    print(
        f"p99_ttft_ms={m.p99_ttft * 1e3:.0f} p99_tpot_ms={m.p99_tpot * 1e3:.0f} "
        f"ttft_attainment={m.ttft_slo_attainment:.2f} "
        f"tpot_attainment={m.tpot_slo_attainment:.2f}"
    )
    print(
        f"throughput_tok_s={m.throughput_tokens_per_s:.0f} "
        f"online_tok_s={m.online_throughput:.0f} "
        f"offline_tok_s={m.offline_throughput:.0f} "
        f"finished={m.num_finished}/{len(online) + len(offline)} "
        f"duration_s={rt.duration:.1f}"
    )
    lat = rt.stats.preemption_latencies
    print(
        f"preemptions={m.num_preemptions} "
        f"safepoint_aborts={rt.stats.safepoint_aborts} "
        f"preemption_latency_ms="
        f"{np.mean(lat) * 1e3:.0f}" if lat else
        f"preemptions={m.num_preemptions} "
        f"safepoint_aborts={rt.stats.safepoint_aborts} "
        f"preemption_latency_ms=-"
    )
    if rt.stats.safepoint_aborts == 0:
        print(
            "warning: no safepoint abort observed — SLO too loose for this "
            "substrate? (try --ttft-scale 1.0 or a denser --rate)"
        )

    # ---- metrics surface (DESIGN.md §15) ---------------------------------
    final = rt.registry.snapshot()
    print(
        "metrics "
        f"iterations_total={final['iterations_total']:.0f} "
        f"aborted_iterations_total={final['aborted_iterations_total']:.0f} "
        f"safepoint_checks_total={final['safepoint_checks_total']:.0f} "
        f"queue_depth_online={final['queue_depth_online']:.0f} "
        f"queue_depth_offline={final['queue_depth_offline']:.0f}"
    )
    print(
        "metrics "
        f"slo_ttft_attainment={final['slo_ttft_attainment']:.3f} "
        f"slo_tpot_attainment={final['slo_tpot_attainment']:.3f} "
        f"pool_occupancy={final['pool_occupancy']:.3f} "
        f"prefix_cache_hit_rate={final['prefix_cache_hit_rate']:.3f} "
        f"calibration_drift={final.get('calibration_drift', 0.0):.2f}"
    )

    if faults is not None:
        print(
            "faults "
            f"injected={faults.injected} pending={faults.pending} "
            f"requests_failed={rt.stats.requests_failed} "
            f"degraded_transitions={rt.stats.degraded_transitions} "
            f"health={rt.health.name}"
        )
        # the engine core survived every injected fault (DESIGN.md §16)
        assert rt.health != RuntimeHealth.FAILED, (
            f"engine went FAILED under injection: {rt.health}"
        )
        assert faults.injected >= 1, "no scheduled fault fired"
        # >=1 request-scoped recovery: the dispatch faults land inside the
        # first 24 iterations, well within the replay drain
        assert rt.stats.requests_failed >= 1, (
            "no request-scoped fault recovered "
            f"(fired: {faults.fired})"
        )
        assert rt.stats.requests_failed == len(rt.failed)
        for r in rt.failed:
            assert r.error is not None, f"failed request {r} lacks its error"
        # accounting closes: every submitted request finished, failed, or
        # was rejected at admission — none lost
        total = len(online) + len(offline)
        assert (
            m.num_finished + len(rt.failed) + rt.stats.rejected == total
        ), (
            f"requests lost: finished={m.num_finished} "
            f"failed={len(rt.failed)} rejected={rt.stats.rejected} "
            f"of {total}"
        )
        # pool invariants hold after recovery (no leaked/double-freed blocks)
        eng.blocks.check_invariants()
        # the metrics surface reflects the faults
        assert final["faults_injected_total"] == faults.injected
        assert final["requests_failed_total"] == rt.stats.requests_failed
        assert final["engine_health"] < RuntimeHealth.FAILED
        print("inject-faults OK")

    if args.assert_metrics:
        # liveness: at least one mid-replay scrape saw the engine running
        # (iterations strictly between 0 and the final count)
        finals = final["iterations_total"]
        assert finals > 0, "no iterations recorded in the registry"
        live = [
            s for s in snaps
            if 0 < s.get("iterations_total", 0) < finals
        ]
        assert live, (
            f"no live mid-replay scrape: {len(snaps)} snapshots, "
            f"final iterations_total={finals:.0f}"
        )
        # counters monotone across successive scrapes (snapshot has no
        # consistent cross-metric cut, but each counter alone is monotone)
        mono_keys = [
            k for k in final
            if k.endswith("_total") or k.endswith("_count") or k.endswith("_sum")
        ]
        prev: dict = {}
        for s in snaps + [final]:
            for k in mono_keys:
                if k in s and k in prev:
                    assert s[k] >= prev[k] - 1e-12, (
                        f"counter {k} went backwards: {prev[k]} -> {s[k]}"
                    )
            prev = {**prev, **s}
        # abort gauges consistent with runtime stats (satellite: every
        # abort records exactly one preemption latency)
        assert final["aborted_iterations_total"] == rt.stats.safepoint_aborts
        assert (
            len(rt.stats.preemption_latencies) == rt.stats.safepoint_aborts
        ), (
            f"{rt.stats.safepoint_aborts} aborts but "
            f"{len(rt.stats.preemption_latencies)} preemption latencies"
        )
        # SLO attainment gauges match ServiceMetrics exactly (the
        # incremental SLOTracker consumes the same TTFT/TPOT values that
        # summarize() recomputes)
        assert abs(final["slo_ttft_attainment"] - m.ttft_slo_attainment) < 1e-9
        assert abs(final["slo_tpot_attainment"] - m.tpot_slo_attainment) < 1e-9
        # the replay drained: waiting queues empty, nothing truncated
        assert final["queue_depth_online"] == 0
        assert final["queue_depth_offline"] == 0
        assert not rt.stats.steps_exhausted
        print(f"assert-metrics OK ({len(snaps)} scrapes, {len(live)} live)")


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
