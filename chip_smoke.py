#!/usr/bin/env python3
"""Smoke test of the co-serving path on a TPU — the quickest proof that the
system still starts on the chip.

    python3 chip_smoke.py          # one chip
    python3 chip_smoke.py --mesh   # four chips: the tensor-parallel phase only

One chip: qwen2-0.5b at its published widths (24 layers, d_model 896, 14
query over 2 KV heads of 64, d_ff 4864, vocab 151936; float32, weights
drawn from a seed) is built through ``serve.build_engine`` with a 2048-token
``max_model_len`` and ~1.5 GB of KV pages, calibrated, and served through
``Frontend`` -> ``CoServingRuntime`` -> ``RealEngine`` (fused ragged batches
over the paged pool, Pallas ``ragged_paged_attention``) with the SLO-aware
scheduler: 4 online streams beside one offline batch job.  Every request
must finish with its token count and the runtime must end HEALTHY.  Then
the logits of one prompt — prefill and a few decode steps through the
engine's own fused programs and paged pool — are compared with the plain
float32 forward (``transformer.forward_full`` at matmul precision
"highest").

``--mesh``: yi-34b at published widths cut to 2 of its 60 layers (56 query
over 8 KV heads of 128, d_ff 20480, vocab 64000; ~2.03 B parameters, 8.1 GB
float32 replicated per chip) serves the same requests on a 4-chip serving
mesh and on one chip; greedy tokens must match, and each KV-pool shard must
sit on its own chip holding 8/4 heads.

Without a TPU the script exits non-zero before building anything.  Every
failed check exits non-zero.  The last line of standard output is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when all passed.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# ---- the one-chip cell ----------------------------------------------------
ARCH = "qwen2-0.5b"
MAX_MODEL_LEN = 2048
BLOCK_SIZE = 16
NUM_BLOCKS = 3840  # x 16 tokens x 24.6 KB/token (f32 KV, 24 layers) = 1.51 GB
CHUNK = 256
ONLINE_PROMPTS = (256, 512, 768, 1024)
ONLINE_NEW = 32
OFFLINE_PROMPTS = (1024,) * 8
OFFLINE_NEW = 64
LOGIT_PROMPT = 512
LOGIT_STEPS = 4
# Logit tolerances, as max |served - reference| / max |reference|:
# * matmul precision "highest" on both sides (the Pallas kernel's dots
#   follow it): the paged path is the same float32 mathematics as the
#   reference in another summation order (chunked online softmax, another
#   matmul tiling), worth ~1e-5 relative; 2e-3 leaves two orders of room,
#   while a wrong page, mask, head or position errs by O(1).
# * the served precision (TPU default: float32 matmuls take one bf16 pass,
#   operands rounded to 2^-9 relative, float32 accumulation) over 24 layers
#   of K >= 896 contractions: 5e-2.
TOL_HIGHEST = 2e-3
TOL_SERVED = 5e-2

# ---- the four-chip phase (--mesh) ----------------------------------------
MESH_ARCH = "yi-34b"
MESH_LAYERS = 2  # cut from 60; every width as published
MESH_TP = 4
MESH_PROMPTS = ((200, 16), (96, 16), (150, 16), (64, 16))  # (prompt, new)


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu():
    """Exit non-zero, before any work, unless JAX's first device is a TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        sys.exit(2)
    return jax.devices()


def import_repro():
    """Make the repository's ``src`` importable; exit non-zero without it."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro.launch.serve  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repository is not beside this script: {e}",
              file=sys.stderr)
        sys.exit(2)


class CompileCounter:
    """Backend compiles (count, seconds), from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def build(arch=ARCH, *, reduced=False, seed=0, max_model_len=MAX_MODEL_LEN,
          num_blocks=NUM_BLOCKS, chunk=CHUNK, mesh=None, num_layers=None):
    """The engine under test, through the entry points' construction path."""
    from repro.core.scheduler import SchedulerConfig
    from repro.launch.serve import build_engine
    from repro.serving.real_engine import RealEngineConfig

    return build_engine(
        arch, reduced=reduced, seed=seed, num_layers=num_layers,
        sched_cfg=SchedulerConfig(
            chunk_size=chunk, max_batch_seqs=16, slo_aware=True,
            avg_ctx_estimate=max_model_len // 2,
        ),
        eng_cfg=RealEngineConfig(
            max_model_len=max_model_len, block_size=BLOCK_SIZE,
            num_device_blocks=num_blocks, num_host_blocks=2 * num_blocks,
            max_prefill_batch=4, mesh=mesh,
        ),
    )


def segment_has_kernel(eng) -> bool:
    """Whether the fused segment program, lowered at a decode shape with the
    engine's live arguments, dispatches the Pallas kernel."""
    import numpy as np

    from repro.models import transformer as tf

    toks, tables, positions, meta, _li = eng._fused_inputs(
        eng._build_ragged([(1, 0, None, None)])
    )
    lo, pps = tf.segment_spans(eng.cfg)[0]
    x = tf.embed(eng.cfg, eng.params, toks[None])
    text = eng._fused_segment_jit.lower(
        pps, eng.params, np.int32(lo), x, eng.pools, tables, positions, meta
    ).as_text()
    return "tpu_custom_call" in text


def serve(eng, *, online=ONLINE_PROMPTS, online_new=ONLINE_NEW,
          offline=OFFLINE_PROMPTS, offline_new=OFFLINE_NEW, seed=0,
          arrival_gap_s=0.25, timeout_s=600.0) -> dict:
    """Serve one offline batch job and, arriving while it runs, the online
    streams, through ``Frontend`` on a threaded ``CoServingRuntime``;
    check every request finished with its token count and health."""
    import numpy as np

    from repro.core.faults import RuntimeHealth
    from repro.core.request import Phase
    from repro.serving.api import Frontend
    from repro.serving.runtime import CoServingRuntime

    rng = np.random.default_rng(seed)
    vocab = eng.cfg.vocab_size

    def prompt(n):
        return rng.integers(0, vocab, n).astype(np.int32)

    rt = CoServingRuntime(eng)
    fe = Frontend(rt, clock=rt.now)
    outs = {}
    t0 = time.perf_counter()
    rt.start()
    try:
        job = fe.submit_batch([prompt(n) for n in offline], offline_new)
        streams = []
        for n in online:
            time.sleep(arrival_gap_s)  # arrive while offline work runs
            streams.append(fe.stream(prompt(n), online_new))

        def consume(i, h):
            outs[i] = list(h)  # per-token, blocking, lossless

        threads = [
            threading.Thread(target=consume, args=(i, h), daemon=True)
            for i, h in enumerate(streams)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=timeout_s)
    finally:
        rt.stop(drain=True, timeout=timeout_s)
    seconds = time.perf_counter() - t0
    health, _age = rt.check_health()
    if rt.fatal is not None:
        print(rt.fatal.traceback_text, file=sys.stderr)
    check(health == RuntimeHealth.HEALTHY, f"runtime ended {health.name}")
    check(not rt.failed, f"{len(rt.failed)} requests failed")
    for i, h in enumerate(streams):
        check(len(outs.get(i, ())) == online_new,
              f"stream {i} delivered {len(outs.get(i, ()))}/{online_new}")
    for r in job.requests:
        check(r.phase == Phase.FINISHED and r.num_generated == offline_new,
              f"offline request {r.request_id} ended {r.phase.name} with "
              f"{r.num_generated}/{offline_new} tokens")
    reqs = [h.request for h in streams] + job.requests
    return {
        "requests": len(reqs),
        "online": len(streams),
        "offline": len(job.requests),
        "prompt_tokens": sum(r.prompt_len for r in reqs),
        "generated_tokens": sum(len(r.output_tokens) for r in reqs),
        "safepoint_aborts": rt.stats.safepoint_aborts,
        "preemptions": sum(r.num_preemptions for r in reqs),
        "engine_steps": eng.steps,
        "seconds": seconds,
    }


def paged_logits(eng, prompt, steps: int):
    """Logits of the prompt's last token, then of ``steps`` greedy decode
    steps, each computed by the engine's fused segment programs over its
    paged pool (a sequence the block manager allocates and frees)."""
    import numpy as np

    sid = -1  # never a request id
    eng.blocks.register_seq(sid)
    try:
        eng.blocks.grow(sid, len(prompt) + steps)
        table = eng._block_table(sid)
        items = [(len(prompt), 0, np.asarray(prompt, np.int32), table)]
        toks, rows = list(prompt), []
        for _ in range(steps + 1):
            logits, _aborted = eng._dispatch_fused(
                *eng._fused_inputs(eng._build_ragged(items)),
                preemptible=False,
            )
            row = np.asarray(logits[0], np.float32)
            rows.append(row)
            toks.append(int(row.argmax()))
            items = [(1, len(toks) - 1, np.asarray(toks[-1:], np.int32),
                      table)]
    finally:
        eng.blocks.free_seq(sid)
    return np.stack(rows), toks


def logit_errors(eng, *, prompt_len=LOGIT_PROMPT, steps=LOGIT_STEPS,
                 seed=1) -> dict:
    """max |paged - reference| / max |reference| over the prompt's last
    position and every decode step: with the served precision, and with
    matmul precision "highest" on both sides."""
    import jax
    import numpy as np

    from repro.models import transformer as tf

    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, eng.cfg.vocab_size, prompt_len).astype(np.int32)
    out = {}
    for name, precision in (("served", None), ("highest", "highest")):
        with jax.default_matmul_precision(precision):
            got, toks = paged_logits(eng, prompt, steps)
        with jax.default_matmul_precision("highest"):
            ref, _, _ = tf.forward_full(
                eng.cfg, eng.params, jax.numpy.asarray(toks[:-1])[None]
            )
        ref = np.asarray(ref[0, prompt_len - 1 :], np.float32)
        out[name] = float(np.abs(got - ref).max() / np.abs(ref).max())
    return out


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_one_chip(devices) -> None:
    from repro.kernels import ops

    compiles = CompileCounter()
    t0 = time.perf_counter()
    eng = build()
    cfg = eng.cfg
    print(f"model={cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads}x{cfg.resolved_head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype=float32 "
          f"kv_pool_blocks={NUM_BLOCKS}x{BLOCK_SIZE} "
          f"max_model_len={MAX_MODEL_LEN} build_s={time.perf_counter() - t0:.3f}")
    backend = ops.kernel_backend()
    print(f"kernel_backend={backend}")
    check(backend == "pallas", f"kernel backend {backend!r}, not pallas")
    has_kernel = segment_has_kernel(eng)
    print(f"segment_hlo_tpu_custom_call={has_kernel}")
    check(has_kernel, "fused segment program has no tpu_custom_call")

    t0 = time.perf_counter()
    eng.calibrate()
    print(f"calibrate_s={time.perf_counter() - t0:.3f} "
          f"compiles={compiles.count} compile_s={compiles.seconds:.3f}")

    stats = serve(eng)
    print("served " + " ".join(f"{k}={v}" for k, v in stats.items()))
    print(f"compiles={compiles.count} compile_s={compiles.seconds:.3f} "
          f"fused_programs={eng.fused_trace_count}")

    errs = logit_errors(eng)
    print(f"logit_rel_err_highest={errs['highest']:.3e} (tol {TOL_HIGHEST}) "
          f"logit_rel_err_served={errs['served']:.3e} (tol {TOL_SERVED})")
    check(errs["highest"] <= TOL_HIGHEST, "logits off the float32 reference")
    check(errs["served"] <= TOL_SERVED,
          "served-precision logits off the reference")
    print(f"peak_bytes_in_use={peak_bytes(devices[0])}")


def mesh_tokens(tp: int, *, reduced=False, seed=0, max_model_len=512,
                num_blocks=256) -> tuple:
    """Serve ``MESH_PROMPTS`` (first half offline, second half online) on a
    ``tp``-chip serving mesh, single-threaded so every run plans identical
    batches; returns (tokens per request, the engine)."""
    import numpy as np

    from repro.core.request import Priority, Request
    from repro.launch.mesh import make_serving_mesh

    eng = build(
        MESH_ARCH, reduced=reduced, seed=seed, num_layers=None if reduced
        else MESH_LAYERS, max_model_len=max_model_len,
        num_blocks=num_blocks, chunk=128,
        mesh=make_serving_mesh(tp) if tp > 1 else None,
    )
    rng = np.random.default_rng(seed + 7)
    reqs = [
        Request(
            Priority.OFFLINE if i < len(MESH_PROMPTS) // 2 else Priority.ONLINE,
            prompt_len=n, max_new_tokens=new,
            prompt=rng.integers(0, eng.cfg.vocab_size, n).astype(np.int32),
        )
        for i, (n, new) in enumerate(MESH_PROMPTS)
    ]
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r, (_n, new) in zip(reqs, MESH_PROMPTS):
        check(len(r.output_tokens) == new,
              f"tp={tp}: request {r.request_id} made "
              f"{len(r.output_tokens)}/{new} tokens")
    return [list(r.output_tokens) for r in reqs], eng


def pool_shards_ok(eng, tp: int) -> bool:
    """Every pool leaf has one shard per chip, each with the lanes of
    Hkv/tp whole heads."""
    import jax

    lanes = eng.cfg.num_kv_heads // tp * eng.cfg.resolved_head_dim
    for leaf in jax.tree.leaves(eng.pools):
        shards = leaf.addressable_shards
        if len({s.device for s in shards}) != tp:
            return False
        if any(s.data.shape[-1] != lanes for s in shards):
            return False
    return True


def run_mesh(devices, tp=MESH_TP, **sizes) -> None:
    """tp chips, then one chip, on the same requests (``sizes`` pass to
    ``mesh_tokens``)."""
    check(len(devices) >= tp, f"--mesh needs {tp} chips, found {len(devices)}")
    compiles = CompileCounter()
    t0 = time.perf_counter()
    toks_tp, eng = mesh_tokens(tp, **sizes)
    cfg = eng.cfg
    print(f"model={cfg.name} layers={cfg.num_layers} (of 60 published) "
          f"d_model={cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads}"
          f"x{cfg.resolved_head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"dtype=float32")
    sharded = pool_shards_ok(eng, tp)
    print(f"tp={tp} served_s={time.perf_counter() - t0:.3f} "
          f"pool_shards_per_chip_ok={sharded} "
          f"peak_bytes_in_use_chip0={peak_bytes(devices[0])}")
    check(sharded, "KV pool is not sharded one head group per chip")
    # the engine's jitted closures refer back to it, so only the cycle
    # collector frees its weights (8.1 GB per chip) before the next leg
    del eng
    gc.collect()
    t0 = time.perf_counter()
    toks_one, _eng = mesh_tokens(1, **sizes)
    print(f"tp=1 served_s={time.perf_counter() - t0:.3f} "
          f"compiles={compiles.count} compile_s={compiles.seconds:.3f}")
    same = toks_tp == toks_one
    print(f"tokens_tp{tp}_equal_tp1={same} "
          f"tokens={sum(len(t) for t in toks_tp)}")
    check(same, f"tp={tp} tokens differ from one chip: {toks_tp} vs {toks_one}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help=f"run only the {MESH_TP}-chip tensor-parallel phase")
    args = ap.parse_args(argv)
    devices = require_tpu()
    import_repro()
    from repro.launch import compile_cache

    print(f"compile_cache={compile_cache.enable()}")
    try:
        if args.mesh:
            run_mesh(devices)
        else:
            run_one_chip(devices)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
    dev = devices[0]
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }))


if __name__ == "__main__":
    main()
