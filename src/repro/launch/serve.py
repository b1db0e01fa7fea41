"""Serving launcher — the paper's kind of driver.

Three modes:
  sim       — run the full-scale config under the calibrated discrete-event
              cost model (policy evaluation; used by the benchmarks).
  real      — calibrate, then run the real-execution engine (true JAX
              compute; single-threaded: submissions happen up front, then
              the engine drains).
  wallclock — full serving stack (DESIGN.md §10): calibrate the engine's
              measured latency profile, run the engine loop on a background
              thread via CoServingRuntime, and drive the streaming/batch
              Frontend from this (the API) thread against the wall clock,
              printing ServiceMetrics at the end.

``real`` and ``wallclock`` serve the architecture at its published widths
(on a TPU) or, with ``--reduced``, a CPU-sized variant of the same family;
both build the engine through ``build_engine``, as ``chip_smoke.py`` does.
They exit non-zero when the runtime ends FAILED or any request failed.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch llama-2-7b --mode sim \
      --duration 120 --rate 2 --offline 500
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --mode real \
      --reduced --online 4 --offline 8 --max-new 16
  PYTHONPATH=src python -m repro.launch.serve --arch llama-2-7b \
      --mode wallclock --reduced --duration 3 --rate 4 --offline 8
  python -m repro.launch.serve --arch qwen2-0.5b --mode wallclock  # on a TPU
"""
from __future__ import annotations

import argparse
import sys

import numpy as np


def run_sim(args) -> None:
    from repro.configs import get_config
    from repro.core.profiler import A100_40G, TPU_V5E
    from repro.core.scheduler import SchedulerConfig
    from repro.core.slo import SLO
    from repro.serving import loadgen
    from repro.serving.engine import EngineConfig, SimEngine

    hw = TPU_V5E if args.hw == "v5e" else A100_40G
    eng = SimEngine(
        get_config(args.arch), SLO(args.ttft, args.tpot),
        SchedulerConfig(), EngineConfig(), hw=hw, tp=args.tp,
    )
    rng = np.random.default_rng(args.seed)
    times = loadgen.gamma_arrivals(args.rate, args.cv, args.duration, rng)
    eng.submit(loadgen.make_online_requests(
        times, loadgen.LengthSpec(args.prompt_len, args.max_new), rng))
    eng.submit(loadgen.make_offline_batch(
        args.offline, loadgen.LengthSpec(2 * args.prompt_len, 2 * args.max_new),
        np.random.default_rng(args.seed + 1)))
    m = eng.run(args.duration)
    print(f"arch={args.arch} hw={hw.name} tp={args.tp}")
    print(f"p99 TTFT {m.p99_ttft*1e3:.0f} ms   p99 TPOT {m.p99_tpot*1e3:.1f} ms")
    print(f"throughput {m.throughput_tokens_per_s:.0f} tok/s "
          f"(online {m.online_throughput:.0f}, offline {m.offline_throughput:.0f})")
    print(f"SLO attainment: TTFT {m.ttft_slo_attainment:.3f} "
          f"TPOT {m.tpot_slo_attainment:.3f}; preemptions {m.num_preemptions}; "
          f"free discards {eng.ckpt.stats.free_discards}")


def _serving_mesh(tp: int):
    """tp>1 -> a 1×tp tensor-parallel mesh (DESIGN.md §11); tp=1 -> None
    (plain single-device execution, also the path for contiguous-fallback
    archs which cannot shard)."""
    if tp <= 1:
        return None
    from repro.launch.mesh import make_serving_mesh

    return make_serving_mesh(tp)


def build_engine(arch: str, *, reduced: bool, seed: int, eng_cfg,
                 sched_cfg=None, reduce_kw=None, num_layers=None):
    """The one construction path of every real-execution entry point
    (``--mode real``/``wallclock`` and ``chip_smoke.py``): the named
    architecture at its published widths — cut to its first
    ``num_layers`` layers when given, widths untouched — or, the explicit
    CPU choice, its ``ModelConfig.reduced(**reduce_kw)`` variant.  Weights
    are drawn from ``seed`` by one jitted program, so every mesh size gets
    the same bits; on a serving mesh they are drawn straight into their
    replicated placement, so no chip ever holds a second copy.  Returns
    the ``RealEngine``; its ``cfg`` and ``params`` are the model served."""
    import dataclasses
    import functools

    import jax

    from repro.configs import get_config
    from repro.models import transformer as tf
    from repro.serving.real_engine import RealEngine

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced(**(reduce_kw or {}))
    elif jax.default_backend() != "tpu":
        raise SystemExit(
            f"{arch} at published widths needs a TPU (JAX found "
            f"{jax.default_backend()}); pass --reduced to run on CPU"
        )
    if num_layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    placement = None
    if eng_cfg.mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        placement = NamedSharding(eng_cfg.mesh, PartitionSpec())
    init = jax.jit(
        functools.partial(tf.init_params, cfg), out_shardings=placement
    )
    params = init(jax.random.PRNGKey(seed))
    return RealEngine(cfg, params, sched_cfg=sched_cfg, eng_cfg=eng_cfg)


def _exit_on_failure(failed: int, health=None, fatal=None) -> None:
    """Entry-point verdict: a FAILED runtime or any failed request exits
    non-zero, whatever metrics were printed before."""
    if fatal is not None and fatal.traceback_text:
        print(fatal.traceback_text, file=sys.stderr)
    dead = health is not None and health.name == "FAILED"
    if failed or dead:
        print(f"serving failed: runtime FAILED={dead} "
              f"failed_requests={failed}", file=sys.stderr)
        sys.exit(1)


def run_real(args) -> None:
    import jax

    from repro.core.request import Phase
    from repro.serving.api import Frontend
    from repro.serving.real_engine import RealEngineConfig

    eng = build_engine(
        args.arch, reduced=args.reduced, seed=args.seed,
        eng_cfg=RealEngineConfig(
            # size the KV capacity to the requested lengths, or admission
            # control rejects the default workload (longest job below is
            # prompt_len // 4 prompt tokens + max_new generated)
            max_model_len=max(256, args.prompt_len // 4 + args.max_new),
            mesh=_serving_mesh(args.tp),
        ),
    )
    cfg = eng.cfg
    print("calibrating (also warms every jit bucket serving will hit)...")
    eng.calibrate()
    fe = Frontend(eng)
    rng = np.random.default_rng(args.seed)

    streams = [
        fe.stream(
            rng.integers(0, cfg.vocab_size, args.prompt_len // 8).astype(np.int32),
            args.max_new,
        )
        for _ in range(args.online)
    ]
    job = fe.submit_batch(
        [rng.integers(0, cfg.vocab_size, args.prompt_len // 4).astype(np.int32)
         for _ in range(args.offline)],
        max_new_tokens=args.max_new,
    )
    eng.run()
    print(f"arch={cfg.name} — real execution on {jax.default_backend()}")
    for i, h in enumerate(streams):
        print(f"stream {i}: {h.poll()}")
    print(f"batch job done={job.done} progress={job.progress:.0%}")
    print(f"engine steps={eng.steps} preemptions="
          f"{sum(r.num_preemptions for r in eng.sched.all_requests())} "
          f"ckpt_blocks={eng.ckpt.stats.blocks_checkpointed}")
    reqs = [h.request for h in streams] + job.requests
    _exit_on_failure(sum(r.phase != Phase.FINISHED for r in reqs))


def _metrics_server(registry, port: int, health_cb=None):
    """Serve ``MetricsRegistry.render_text`` over HTTP (stdlib only) from a
    daemon thread — the ``--metrics-port`` text endpoint (DESIGN.md §15).
    Snapshots never block the engine thread, so scraping under load is
    safe by construction.

    With ``health_cb`` (``CoServingRuntime.check_health``), ``GET /health``
    reports the runtime's health state machine (DESIGN.md §16): 200 for
    HEALTHY/DEGRADED (degraded still serves), 503 for FAILED — the shape a
    load balancer's probe wants.  Every other path serves the metrics."""
    import http.server
    import threading

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 (stdlib API name)
            if self.path.rstrip("/") == "/health" and health_cb is not None:
                health, age = health_cb()
                body = (
                    f"health {health.name}\nheartbeat_age_seconds {age:.3f}\n"
                ).encode()
                code = 503 if health.name == "FAILED" else 200
            else:
                body = registry.render_text().encode()
                code = 200
            self.send_response(code)
            self.send_header("Content-Type", "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):  # quiet access log
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", port), Handler)
    threading.Thread(
        target=srv.serve_forever, name="metrics-http", daemon=True
    ).start()
    return srv


def run_wallclock(args) -> None:
    """Calibrated wall-clock co-serving: engine thread + API thread, with
    the gateway surface live — per-token streaming consumers, bounded
    admission with the selected backpressure policy, and the metrics
    registry (printable with ``--metrics``, scrapable with
    ``--metrics-port``)."""
    import threading
    import time

    import jax

    from repro.core.profiler import BatchShape
    from repro.core.scheduler import SchedulerConfig
    from repro.core.slo import SLO
    from repro.serving import loadgen
    from repro.serving.api import Frontend, QueueFull, QueueTimeout
    from repro.serving.real_engine import RealEngineConfig
    from repro.serving.runtime import CoServingRuntime, ServingConfig

    eng = build_engine(
        args.arch, reduced=args.reduced, seed=args.seed,
        reduce_kw=dict(num_layers=4, safepoint_interval=1),
        sched_cfg=SchedulerConfig(
            chunk_size=32, slo_aware=True, avg_ctx_estimate=64,
            max_batch_seqs=8,
        ),
        eng_cfg=RealEngineConfig(
            max_model_len=128, num_device_blocks=256, max_prefill_batch=4,
            mesh=_serving_mesh(args.tp),
        ),
    )
    cfg = eng.cfg
    print("calibrating (also warms every jit bucket serving will hit)...")
    prof = eng.calibrate()
    t_chunk = prof.iter_time(BatchShape(
        prefill_tokens=32, prefill_attn_tokens=512.0, prefill_ctx_end=32,
        num_seqs=1,
    ))
    eng.sched.slo = SLO(ttft=args.ttft or 3 * t_chunk, tpot=args.tpot)

    rt = CoServingRuntime(
        eng,
        serving=ServingConfig(
            policy=args.backpressure,
            max_queued_online=args.max_queued_online,
            max_queued_offline=args.max_queued_offline,
            queue_timeout_s=args.queue_timeout,
        ),
    )
    fe = Frontend(rt, clock=rt.now)
    srv = _metrics_server(rt.registry, args.metrics_port,
                          health_cb=rt.check_health) \
        if args.metrics_port else None
    if srv is not None:
        print(f"metrics endpoint: http://127.0.0.1:{args.metrics_port}/ "
              f"(health: http://127.0.0.1:{args.metrics_port}/health)")
    rng = np.random.default_rng(args.seed)
    arrivals = loadgen.gamma_arrivals(args.rate, args.cv, args.duration, rng)
    # per-token streaming consumers: one thread per stream iterates its
    # TokenChannel (blocking, lossless) and tallies what it received
    streamed: list = []
    consumers: list = []

    def consume(handle) -> None:
        streamed.append(sum(1 for _tok in handle))

    rt.start()
    shed = 0
    streams = []
    try:
        job = fe.submit_batch(
            [rng.integers(0, cfg.vocab_size, args.prompt_len // 16)
             .astype(np.int32) for _ in range(args.offline)],
            max_new_tokens=args.max_new // 4,
        )
        for t in arrivals:  # the API thread replays the online trace live
            while True:
                gap = t - rt.now()
                if gap <= 0:
                    break
                time.sleep(min(0.005, gap))
            try:
                h = fe.stream(
                    rng.integers(0, cfg.vocab_size, args.prompt_len // 32)
                    .astype(np.int32),
                    args.max_new // 8,
                )
            except (QueueFull, QueueTimeout):
                shed += 1  # intentional load shedding, not an error
                continue
            streams.append(h)
            th = threading.Thread(target=consume, args=(h,), daemon=True)
            th.start()
            consumers.append(th)
    finally:
        rt.stop(drain=True)
    for th in consumers:
        th.join(timeout=5.0)
    m = rt.metrics()
    print(f"arch={cfg.name} wall-clock on {jax.default_backend()}")
    print(f"online streams={len(streams)} finished="
          f"{sum(1 for h in streams if h.finished)} shed={shed} "
          f"policy={args.backpressure}; batch done={job.done}")
    print(f"tokens streamed per-token: {sum(streamed)} "
          f"(generated {sum(len(h.request.output_tokens) for h in streams)})")
    print(f"p99 TTFT {m.p99_ttft * 1e3:.0f} ms   p99 TPOT "
          f"{m.p99_tpot * 1e3:.1f} ms   attainment "
          f"{m.ttft_slo_attainment:.2f}/{m.tpot_slo_attainment:.2f}")
    print(f"throughput {m.throughput_tokens_per_s:.0f} tok/s "
          f"(online {m.online_throughput:.0f}, offline "
          f"{m.offline_throughput:.0f}); safepoint aborts "
          f"{rt.stats.safepoint_aborts}; preemptions {m.num_preemptions}")
    if args.metrics:
        print("--- metrics ---")
        print(rt.registry.render_text(), end="")
    if srv is not None:
        srv.shutdown()
    health, _age = rt.check_health()
    _exit_on_failure(
        len(rt.failed) + sum(not h.finished for h in streams)
        + (0 if job.done else 1),
        health, rt.fatal,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-2-7b")
    ap.add_argument("--mode", choices=["sim", "real", "wallclock"],
                    default="sim")
    ap.add_argument("--duration", type=float, default=120.0)
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--cv", type=float, default=1.0)
    ap.add_argument("--offline", type=int, default=500)
    ap.add_argument("--online", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=1024)
    ap.add_argument("--max-new", type=int, default=128)
    # default TTFT: 1.5 s for sim/real; wallclock derives it from the
    # calibration pass when the flag is not given
    ap.add_argument("--ttft", type=float, default=None)
    ap.add_argument("--tpot", type=float, default=0.110)
    ap.add_argument("--hw", choices=["v5e", "a100"], default="v5e")
    # sim: chips in the cost model; real/wallclock: tensor-parallel mesh
    # size for the paged backend (needs >= tp visible devices, §11)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    # real/wallclock: serve the CPU-sized variant of the architecture
    ap.add_argument("--reduced", action="store_true",
                    help="serve ModelConfig.reduced() (the CPU choice) "
                         "instead of the published widths")
    # wallclock gateway surface (DESIGN.md §15)
    ap.add_argument("--backpressure",
                    choices=["queue-with-timeout", "reject-fast"],
                    default="queue-with-timeout",
                    help="ingress policy: block-to-deadline (503) or "
                         "reject at capacity (429)")
    ap.add_argument("--max-queued-online", type=int, default=64)
    ap.add_argument("--max-queued-offline", type=int, default=256)
    ap.add_argument("--queue-timeout", type=float, default=2.0,
                    help="queue-with-timeout deadline (s)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the metrics registry at the end of the run")
    ap.add_argument("--metrics-port", type=int, default=0,
                    help="serve the metrics registry as text on "
                         "127.0.0.1:PORT while running")
    args = ap.parse_args()
    if args.ttft is None and args.mode != "wallclock":
        args.ttft = 1.5
    if args.mode != "sim":
        from repro.launch import compile_cache

        compile_cache.enable()
    {"sim": run_sim, "real": run_real, "wallclock": run_wallclock}[args.mode](
        args
    )


if __name__ == "__main__":
    main()
