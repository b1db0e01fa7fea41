#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's and the control's.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3 \\
        [--rate <req/s>]

One process builds, calibrates and warms up the cell's engine once, as
``bench/run.py`` does.  Then, for each seed: the engine takes that seed's
weights, serves that seed's traffic for one window at the cell's rate (or
``--rate``) and drains it, and over every request it finished the
reference gives the program's number (the widest gap below the
reference's best logit of a served token) and the control's (the same gap
of the token that the float8 reference puts first at each of those
positions).  The program stays alive beside the reference: these
readings report no memory peak.  A window that leaves online requests
unfinished (its line's ``online_unfinished``) hands them to the next
seed's window.  The benchmark's own runs never run the
control.  Prints one line per seed and a JSON summary last.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import check, program, reference  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench import traffic as gen  # noqa: E402


def readings(run: "bench_run.CellRun", seed: int, rate: float,
             compiles) -> dict:
    cfg = run.cfg
    run.seed = seed
    run.eng.params = None
    run.eng.params = program.weights(cfg, seed)
    run.traffic = gen.generate(run.mix, cfg, run.m["vocab"], seed,
                               run.seconds, rate=rate)
    run.offline_reqs = []
    run.serve(compiles)
    times = run.online_times(bench_run.load_json(bench_run.BENCH, "slo.json"))
    picked = check.compared(run.finished())
    w = reference.draw_jit(run.m, seed)
    pad = cfg["serving"]["max_model_len"]
    served = check.served_gaps(run.m, w, picked, pad)
    lowp = check.served_gaps(run.m, w, picked, pad, lowp_targets=True)
    del w
    return {
        "seed": seed,
        "rate_req_per_s": rate,
        "online": times["n"],
        "online_unfinished": times["failed"],
        "compiles_in_window": run.compiles_in_window,
        "requests": len(picked),
        "tokens": sum(len(g) for g in served),
        "program_max_gap": max((float(g.max()) for g in served), default=None),
        "control_max_gap": max((float(g.max()) for g in lowp), default=None),
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rate", type=float, default=None)
    args = ap.parse_args(argv)
    _spec, cell, cfg, mix = bench_run.load_cell(args.workload)
    bench_run.configure_jax(cfg)
    bench_run.devices_or_exit(cell["chips"])
    program.import_program()
    compiles = bench_run.CompileCounter()
    seeds = [int(s) for s in args.seeds.split(",")]
    rate = gen.online_rate(mix, cfg) if args.rate is None else args.rate
    run = bench_run.CellRun(cfg, mix, seeds[0], args.seconds, rate=rate)
    print(json.dumps({"setup": run.setup(compiles)}), flush=True)
    out = []
    for seed in seeds:
        r = readings(run, seed, rate, compiles)
        print(json.dumps(r), flush=True)
        out.append(r)
    print(json.dumps({
        "workload": args.workload,
        "program_max_gap": max(r["program_max_gap"] or 0.0 for r in out),
        "control_min_of_max_gap": min(
            (r["control_max_gap"] for r in out if r["control_max_gap"] is not None),
            default=None),
        "runs": out,
    }))


if __name__ == "__main__":
    main()
