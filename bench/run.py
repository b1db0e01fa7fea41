#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``, which names its model family,
``bench/families/<family>/``) and a traffic mix
(``bench/traffic/<mix>.json``).  The run builds the program's engine with
weights drawn from the seed, calibrates it, warms up every program the
traffic can dispatch, and serves the mix through the program's own path --
``Frontend`` -> ``CoServingRuntime`` (its engine thread) -> ``RealEngine``
(fused ragged batches over the paged pool, Pallas ragged paged attention)
-- for ``--seconds``, open loop.  Every online request sent in the window
is followed to its end.  Then the program is freed and every request the
window finished is compared with the plain reference (``bench/check.py``).

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (each read by ``bench/metrics/<name>.py``) from a run
that also records a profiler trace of the window's last seconds, or of
as many seconds from the last online arrival where that comes earlier,
so the traced stretch always holds work.  The last
line of standard output is one JSON object; the numbers compared, with
their limits, are the last lines of standard error and the last key of
that object.  Without a TPU, or with fewer chips than the cell asks for,
the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check, costs, family, program, reference  # noqa: E402
from bench import trace as tracing  # noqa: E402
from bench import traffic as gen  # noqa: E402

BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")  # fixed: part of the cache key
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# the plans each cell's runs met, warmed up for by the next run (beside the
# compilation cache: both hold what this checkout has already compiled)
SHAPES_DIR = os.path.join(CACHE_DIR, "bench-plans")
TRACE_SECONDS = 5.0  # the traced part of the window (``CellRun.serve``)
DRAIN_S = 60.0  # how long past the window online requests are followed
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    """Progress on standard error, stamped with seconds since start."""
    print(f"[{time.perf_counter() - T_PROCESS:8.1f}s] {msg}", file=sys.stderr,
          flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> tuple:
    spec = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = load_config(cell["config"])
    mix = gen.load_mix(cell["traffic"])
    return spec, cell, cfg, mix


def load_config(name: str) -> dict:
    """``bench/configs/<name>.json``; an error where it names no model
    family, or one without its files (``bench/family.py``)."""
    cfg = load_json(BENCH, "configs", f"{name}.json")
    family.check(cfg)
    return cfg


def configure_jax(cfg: dict) -> None:
    """Process-wide settings, before anything is traced: the stated matmul
    precision and the persistent compilation cache at a fixed path inside
    the checkout."""
    import jax

    set_precision(cfg)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def set_precision(cfg: dict) -> None:
    """The configuration's matmul precision as JAX's global setting, so
    every thread -- the runtime's engine thread too -- traces alike (the
    context manager form would hold for the calling thread only)."""
    import jax

    jax.config.update("jax_default_matmul_precision", cfg["precision"]["matmul"])


def devices_or_exit(chips: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(2)
    return devs


class CompileCounter:
    """Programs lowered (each is then compiled or read from the persistent
    cache), and the seconds spent compiling."""

    def __init__(self):
        import jax

        self.count = 0
        self.compile_s = 0.0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.count += 1
            self.names.append(kw.get("fun_name", "?"))
        elif event == BACKEND_COMPILE_EVENT:
            self.compile_s += duration


class Measurement:
    """What the window produced; the per-layer readers take this."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1


def traced_stretch(online, seconds: float) -> tuple:
    """(start, end) of the traced stretch, in seconds after the window
    opens: the window's last ``TRACE_SECONDS``, or as many from the last
    online arrival where that comes earlier -- a window whose end is quiet
    would leave the trace with no device work."""
    span = min(TRACE_SECONDS, seconds)
    start = min(seconds - span, max((r.due for r in online), default=0.0))
    return start, start + span


class CellRun:
    """Set-up, window, drain and read-out of one run of a cell."""

    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float,
                 traced: bool = False, rate: float = None,
                 record: str = None):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.record = record  # file of the plans earlier runs met
        self.seconds = float(seconds)
        self.traced = traced
        self.m = reference.dims(cfg)
        self.traffic = gen.generate(mix, cfg, self.m["vocab"], seed,
                                    self.seconds, rate=rate)
        self.offline_reqs = []
        self.trace_state = "off"
        self.trace_t = [None, None]
        self.fatal = None

    # ------------------------------------------------------------ set-up
    def setup(self, compiles: "CompileCounter" = None) -> dict:
        from repro.serving.api import Frontend
        from repro.serving.runtime import CoServingRuntime

        t = time.perf_counter()
        self.eng = program.build_engine(self.cfg, self.seed)
        t_build = time.perf_counter() - t
        log(f"built in {t_build:.1f}s")
        t = time.perf_counter()
        self.eng.calibrate()
        t_cal = time.perf_counter() - t
        log(f"calibrated in {t_cal:.1f}s; token cap {program.token_cap(self.eng)}"
            + (f"; {compiles.count} programs, {compiles.compile_s:.1f}s compiling"
               if compiles else ""))
        t = time.perf_counter()
        warm = program.warm_up(self.eng, self.mix["warm_max_seqs"],
                               seen=self.seen_before(), log=log)
        t_warm = time.perf_counter() - t
        log(f"warmed up in {t_warm:.1f}s: {warm}"
            + (f"; {compiles.count} programs, {compiles.compile_s:.1f}s compiling"
               if compiles else ""))
        self.rt = CoServingRuntime(self.eng)
        self.fe = Frontend(self.rt, clock=self.rt.now)
        self.rec = program.Recorder(self.eng, time.perf_counter)
        self.rec.hook = self._trace_hook
        return {"build_s": t_build, "calibrate_s": t_cal, "warmup_s": t_warm,
                **warm, "token_cap": program.token_cap(self.eng)}

    def seen_before(self) -> list:
        if self.record is None or not os.path.exists(self.record):
            return []
        with open(self.record) as f:
            return json.load(f)

    def remember(self) -> None:
        """Add the plans this run met to the record the next run of the
        cell in this checkout warms up for."""
        if self.record is None:
            return
        old = {program.bucket(lens): lens for lens in self.seen_before()}
        new = {**self.rec.seen, **old}
        os.makedirs(os.path.dirname(self.record), exist_ok=True)
        tmp = self.record + ".tmp"
        with open(tmp, "w") as f:
            json.dump(sorted(new.values()), f)
        os.replace(tmp, self.record)

    # ------------------------------------------------------------ window
    def _trace_hook(self) -> None:
        """Engine thread, between iterations (the serial engine has read
        its tokens back, so the device is drained): start the profiler at
        ``trace_at``, stop it at the first iteration boundary after
        ``trace_end``."""
        import jax

        now = time.perf_counter()
        if self.trace_state == "pending" and now >= self.trace_at:
            jax.profiler.start_trace(TRACE_DIR)
            self.trace_t[0] = now
            self.trace_state = "on"
        elif self.trace_state == "on" and now >= self.trace_end:
            self.trace_t[1] = now
            jax.profiler.stop_trace()
            self.trace_state = "done"

    def _submit_offline(self, i: int) -> None:
        prompt, new = self.traffic.offline[i]
        job = self.fe.submit_batch([prompt], new)
        self.offline_reqs.extend(job.requests)

    def _generate(self) -> None:
        """The load generator (its own thread): online requests at their
        due times, open loop; the offline queue kept at ``waiting``."""
        from repro.core.request import Phase

        tr = self.traffic
        online, i, j, k = tr.online, 0, 0, 0
        while True:
            now = time.perf_counter()
            if i < len(online) and now >= self.t0 + online[i].due:
                r = online[i]
                r.sent = now - self.t0
                try:
                    r.handle = self.fe.stream(r.prompt, r.max_new)
                except Exception as e:  # refused: counts as a miss
                    r.error = repr(e)
                i += 1
                continue
            if now >= self.t1:
                break
            if tr.offline:
                while k < len(self.offline_reqs) and (
                    self.offline_reqs[k].phase != Phase.WAITING
                ):
                    k += 1
                while len(self.offline_reqs) - k < tr.waiting and j < len(tr.offline):
                    self._submit_offline(j)
                    j += 1
            nxt = self.t0 + online[i].due if i < len(online) else self.t1
            time.sleep(min(0.002, max(0.0, nxt - time.perf_counter())))

    def serve(self, compiles: CompileCounter) -> None:
        self.rt.start()
        t = time.perf_counter()
        self.t0 = t + self.traffic.preroll_s
        self.t1 = self.t0 + self.seconds
        if self.traced:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            a, b = traced_stretch(self.traffic.online, self.seconds)
            self.trace_at, self.trace_end = self.t0 + a, self.t0 + b
            self.trace_state = "pending"
        self.offset = t - self.rt.now()  # runtime clock -> perf_counter
        gen_thread = threading.Thread(target=self._generate, name="bench-load")
        gen_thread.start()
        while time.perf_counter() < self.t0:
            time.sleep(0.001)
        self.compiles_before = compiles.count
        self.counters0 = self._counters()
        log(f"window open: {len(self.traffic.online)} online requests due "
            f"over {self.seconds}s")
        gen_thread.join()
        self.counters1 = self._counters()
        self.compiles_in_window = compiles.count - self.compiles_before
        names = compiles.names[self.compiles_before:compiles.count]
        log(f"window closed: {self.compiles_in_window} compiles in it "
            f"{sorted(set(names))[:12]}")
        deadline = self.t1 + DRAIN_S
        while time.perf_counter() < deadline and self.rt.fatal is None:
            if all(self._settled(r) for r in self.traffic.online):
                break
            time.sleep(0.01)
        self.t_drained = time.perf_counter()
        log("online requests settled; stopping the runtime")
        self.rt.stop(drain=False, timeout=120.0)
        if self.trace_state == "on":
            import jax

            self.trace_t[1] = time.perf_counter()
            jax.profiler.stop_trace()
            self.trace_state = "done"
        self.fatal = self.rt.fatal

    def _counters(self) -> dict:
        eng = self.eng
        return {"measured_s": eng.measured_iter_seconds,
                "predicted_s": eng.predicted_iter_seconds,
                "steps": eng.steps}

    @staticmethod
    def _settled(r) -> bool:
        from repro.core.request import Phase

        if r.handle is None:
            return r.error is not None
        return r.handle.request.phase in (Phase.FINISHED, Phase.FAILED)

    # ---------------------------------------------------------- read-out
    def online_times(self, slo: dict) -> dict:
        """Per online request: TTFT from its due time and mean time per
        output token, on the host clock; a request refused, failed or not
        finished by the drain deadline misses both limits and counts with
        the time it had waited."""
        from repro.core.request import Phase

        end = self.t_drained
        ttft, tpot, met, failed = [], [], 0, 0
        for r in self.traffic.online:
            due = self.t0 + r.due
            req = r.handle.request if r.handle is not None else None
            ok = req is not None and req.phase == Phase.FINISHED
            if req is not None and req.first_token_time is not None:
                first = req.first_token_time + self.offset
                ttft.append(first - due)
                times = req.token_times
                if ok and len(times) >= 2:
                    tpot.append((times[-1] - times[0]) / (len(times) - 1))
                else:
                    tpot.append((end - first) / max(1, len(times) - 1))
            else:
                ttft.append(end - due)
                tpot.append(end - due)
            if not ok:
                failed += 1
            elif ttft[-1] <= slo["ttft_s"] and tpot[-1] <= slo["tpot_s"]:
                met += 1
        return {"ttft": ttft, "tpot": tpot, "met": met, "failed": failed,
                "n": len(self.traffic.online)}

    def measurement(self, times: dict) -> Measurement:
        rec = self.rec
        return Measurement(
            t0=self.t0, t1=self.t1, seconds=self.seconds, m=self.m,
            online=self.traffic.online, times=times, steps=rec.steps,
            progress=rec.progress, pool_used=rec.pool_used,
            preempted=rec.preempted, counters=(self.counters0, self.counters1),
            abort_latencies=list(self.rt.stats.preemption_latencies),
            coserve=bool(self.traffic.offline), trace=None,
        )

    def finished(self) -> list:
        """(request id, prompt, served tokens) of every finished request,
        and of every resumed one that has served tokens: what it served
        before the runtime stopped is final, and holds the tokens computed
        after its resume."""
        from repro.core.request import Phase

        reqs = [r.handle.request for r in self.traffic.online
                if r.handle is not None] + self.offline_reqs
        resumed = self.resumed_ids()
        return [(q.request_id, np.asarray(q.prompt, np.int32),
                 list(q.output_tokens)) for q in reqs
                if (q.phase == Phase.FINISHED
                    and len(q.output_tokens) == q.max_new_tokens)
                or (q.request_id in resumed and len(q.output_tokens) > 1)]

    def resumed_ids(self) -> set:
        reqs = [r.handle.request for r in self.traffic.online
                if r.handle is not None] + self.offline_reqs
        return self.rec.aborted_ids | {
            q.request_id for q in reqs if q.num_preemptions > 0
        }

    def free_program(self) -> int:
        """Drop the engine and everything holding it (its jitted closures
        refer back to it, so only the cycle collector frees its arrays);
        returns the bytes of device arrays still alive."""
        import jax

        for name in ("rec", "fe", "rt", "eng"):
            if hasattr(self, name):
                delattr(self, name)
        gc.collect()
        return sum(a.nbytes for a in jax.live_arrays())


def percentile(xs, p) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), p))


def read_reader(name: str):
    """``bench/metrics/<name>.py``, or the file of the name's part before
    its first dot (``step_mfu.online`` -> ``step_mfu.py``)."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(BENCH, "metrics", f"{stem}.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} in bench/metrics")


def metrics_for(spec: dict, cell: dict, kind: str) -> list:
    return [
        m for m in spec[kind]
        if "workloads" not in m or cell["name"] in m["workloads"]
    ]


def end_to_end(spec, cell, run: CellRun, times: dict, setup_s: float) -> dict:
    offline = sum(n for t, r, n in run.rec.progress
                  if not r.is_online and run.t0 <= t <= run.t1)
    values = {
        "online_ttft_p50_ms": percentile(times["ttft"], 50) * 1e3,
        "online_tpot_p50_ms": percentile(times["tpot"], 50) * 1e3,
        "online_ttft_p95_ms": percentile(times["ttft"], 95) * 1e3,
        "online_tpot_p95_ms": percentile(times["tpot"], 95) * 1e3,
        "online_slo_attainment": 100.0 * times["met"] / max(1, times["n"]),
        "offline_tokens_per_s": offline / run.seconds,
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_for(spec, cell, "end_to_end")}


def per_layer(spec, cell, meas: Measurement) -> dict:
    out = {}
    for m in metrics_for(spec, cell, "per_layer"):
        v = read_reader(m["name"])(meas)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def compare(run: CellRun, cfg: dict, finished: list, resumed: set) -> dict:
    """The reference over every finished request; returns the numbers
    compared."""
    picked = check.compared(finished)
    w = reference.draw_jit(run.m, run.seed)
    gaps = check.served_gaps(run.m, w, picked, cfg["serving"]["max_model_len"])
    del w
    tokens = sum(len(g) for g in gaps)
    resumed_tokens = sum(len(g) for g, p in zip(gaps, picked) if p[0] in resumed)
    return {
        "picked": picked,
        "gaps": gaps,
        "max_logit_gap": max((float(g.max()) for g in gaps), default=float("nan")),
        "tokens": tokens,
        "resumed_tokens": resumed_tokens,
        "requests": len(picked),
    }


def main(argv=None, require_chip: bool = True) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec, cell, cfg, mix = load_cell(args.workload)
    configure_jax(cfg)
    if require_chip:
        devs = devices_or_exit(cell["chips"])
    else:
        import jax

        devs = jax.devices()
    program.import_program()
    compiles = CompileCounter()
    slo = load_json(BENCH, "slo.json")

    run = CellRun(cfg, mix, args.seed, args.seconds, traced=bool(args.trace),
                  record=os.path.join(SHAPES_DIR, f"{cell['name']}.json"))
    info = run.setup(compiles)
    run.serve(compiles)
    setup_s = run.t0 - T_PROCESS
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[: cell["chips"]])
    times = run.online_times(slo)
    meas = run.measurement(times)
    breakdown = None
    if args.trace:
        data = tracing.load(tracing.find(TRACE_DIR))
        red = tracing.reduce(data, run.trace_t[1] - run.trace_t[0])
        meas.trace = red
        meas.trace_t = tuple(run.trace_t)
        meas.peaks = costs.peaks(devs[0].device_kind)
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        metrics = per_layer(spec, cell, meas)
    else:
        metrics = end_to_end(spec, cell, run, times, setup_s)
    finished, resumed = run.finished(), run.resumed_ids()
    points = dict(run.rec.preempt_points)
    steps = len(run.rec.steps)
    shapes = {(program.pow2(sum(i[0] for i in items)), program.pow2(len(items)),
               program.pow2(max(i[0] for i in items)))
              for _t0, _t1, items, _a in run.rec.steps}
    aborts, preempts = run.rt.stats.safepoint_aborts, sum(
        n for _t, n in run.rec.preempted)
    fatal = run.fatal
    run.remember()
    live = run.free_program()
    log("program freed; comparing with the reference")
    cmp = compare(run, cfg, finished, resumed)
    log("compared")

    lim = cfg["check"]
    numbers = {
        "max_logit_gap": {"value": cmp["max_logit_gap"],
                          "limit": lim["max_logit_gap"]},
        "tokens_compared": {"value": cmp["tokens"], "limit": lim["min_tokens"]},
        "online_unfinished": {"value": times["failed"], "limit": 0},
    }
    correct = (
        fatal is None
        and cmp["tokens"] >= lim["min_tokens"]
        and cmp["max_logit_gap"] <= lim["max_logit_gap"]
        and times["failed"] == 0
    )
    print(f"setup build_s={info['build_s']:.3f} calibrate_s={info['calibrate_s']:.3f} "
          f"warmup_s={info['warmup_s']:.3f} fused_shapes={info['fused_shapes']} "
          f"sampler_shapes={info['sampler_shapes']} token_cap={info['token_cap']} "
          f"setup_s={setup_s:.3f}")
    print(f"window seconds={run.seconds} rate_req_per_s={run.traffic.rate:.4f} "
          f"online={times['n']} met={times['met']} failed={times['failed']} "
          f"ttft_p95_ms={percentile(times['ttft'], 95) * 1e3:.1f} "
          f"tpot_p95_ms={percentile(times['tpot'], 95) * 1e3:.1f} "
          f"steps={steps} safepoint_aborts={aborts} preemptions={preempts} "
          f"compiles_in_window={run.compiles_in_window} "
          f"offline_submitted={len(run.offline_reqs)}")
    print(f"plans distinct_buckets={len(shapes)} "
          f"max_seqs={max((s for _t, s, _q in shapes), default=0)} "
          f"max_tokens={max((t for t, _s, _q in shapes), default=0)}")
    print(f"memory peak_bytes_in_use={peak} live_bytes_after_free={live}")
    print(f"check requests={cmp['requests']} tokens_compared={cmp['tokens']} "
          f"resumed_tokens_compared={cmp['resumed_tokens']}")
    for (rid, prompt, toks), gap in zip(cmp["picked"], cmp["gaps"]):
        bad = np.nonzero(gap > lim["max_logit_gap"])[0]
        print(f"check request={rid} prompt={len(prompt)} tokens={len(toks)} "
              f"max_gap={float(gap.max()):.5f} "
              f"first_over_limit={int(bad[0]) if len(bad) else None} "
              f"preempted_at={points.get(rid, [])}")
    if fatal is not None:
        print(fatal.traceback_text, file=sys.stderr)
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"], "memory_peak_bytes": peak}
    if args.trace:
        device["busy_s"] = meas.trace["busy_s"]
        device["window_s"] = meas.trace["window_s"]
    result = {"correct": bool(correct), "attempted": times["n"],
              "failed": times["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    for k, v in numbers.items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return result


if __name__ == "__main__":
    main()
