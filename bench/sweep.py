#!/usr/bin/env python3
"""The online knee of a configuration: one sweep of fixed rates.

    python3 bench/sweep.py --config <config> --traffic online-chat \\
        --requests 50 --seed 1 --rates 0.5,1,1.5 [--warm-seqs 32]

One process builds, calibrates and warms the engine once, then serves the
mix's online requests at each rate, ascending, for as long as ``--requests``
arrivals take, open loop, draining between rates; rate k draws its traffic
from seed + k.  Prints, per rate, the share of requests meeting both
limits, the tails, the TTFT of the window's first and last thirds (a
growing queue shows as a later third far above the first) and the programs
compiled inside the window.  Last comes the knee R*: the highest rate at
which it and every lower rate had at least 90% of requests meeting both
limits and every request finished.  It is written into the configuration
file by hand.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import program  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench import traffic as gen  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="online-chat")
    ap.add_argument("--requests", type=int, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--warm-seqs", type=int, default=None)
    args = ap.parse_args(argv)
    cfg = bench_run.load_config(args.config)
    mix = gen.load_mix(args.traffic)
    bench_run.configure_jax(cfg)
    bench_run.devices_or_exit(1)
    program.import_program()
    compiles = bench_run.CompileCounter()
    slo = bench_run.load_json(bench_run.BENCH, "slo.json")
    if args.warm_seqs:
        mix = dict(mix, warm_max_seqs=args.warm_seqs)
    rates = sorted(float(r) for r in args.rates.split(","))
    run = bench_run.CellRun(cfg, mix, args.seed, args.requests / rates[0],
                            rate=rates[0])
    print(json.dumps({"setup": run.setup(compiles)}), flush=True)
    knee, holding = None, True
    for k, rate in enumerate(rates):
        # each rate draws its own prompts: the same seed and count would
        # resend the last rate's, and the prefix cache would serve them
        run.seconds = args.requests / rate
        run.traffic = gen.generate(mix, cfg, run.m["vocab"], args.seed + k,
                                   run.seconds, rate=rate)
        run.offline_reqs = []
        run.serve(compiles)
        t = run.online_times(slo)
        n = t["n"]
        holding = holding and t["met"] >= 0.9 * n and t["failed"] == 0
        if holding:
            knee = rate
        third = max(1, n // 3)
        print(json.dumps({
            "rate": rate, "requests": n,
            "attainment_pct": 100.0 * t["met"] / n,
            "failed": t["failed"],
            "ttft_p95_ms": float(np.percentile(t["ttft"], 95)) * 1e3,
            "tpot_p95_ms": float(np.percentile(t["tpot"], 95)) * 1e3,
            "ttft_first_third_ms": float(np.mean(t["ttft"][:third])) * 1e3,
            "ttft_last_third_ms": float(np.mean(t["ttft"][-third:])) * 1e3,
            "compiles_in_window": run.compiles_in_window,
            "steps": run.counters1["steps"] - run.counters0["steps"],
        }), flush=True)
    print(json.dumps({"knee_req_per_s": knee}), flush=True)


if __name__ == "__main__":
    main()
