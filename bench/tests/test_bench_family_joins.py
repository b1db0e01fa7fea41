"""A model family of another kind joins the benchmark by new files alone.

A copy of ``bench/`` gains the test family ``tiny_moe``
(``bench/tests/families/tiny_moe/``: dense GQA attention, a Mixtral-style
expert FFN in every layer), a configuration that names it, a mix and a
cell in ``BENCHMARK.json`` -- and no other file changes.  The harness finds
all of it by name; the program serves the family through its fused paged
path, one request preempted and resumed, within rounding of the family's
own reference; a broken expert weight in that reference is caught; and
the per-layer readers price the family's kernels by its ``costs.py``.
"""
import filecmp
import json
import os
import shutil

import numpy as np
import pytest

from bench import check, costs, family, reference
from bench import run as bench_run
from bench import traffic as gen
from bench.tests import tiny
from bench.tests.test_bench_reference import served

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_BENCH = bench_run.BENCH
CELL = "tiny-moe.tiny-short"
SEED = 7


def moe_config() -> dict:
    cfg = tiny.config(tied=False, bias=False)
    cfg.update(name="tiny-moe", family="tiny_moe", intermediate_size=96,
               num_local_experts=4, num_experts_per_tok=2)
    return cfg


@pytest.fixture(scope="module")
def joined(tmp_path_factory):
    """The copy of ``bench/`` with the new family's files added, set as the
    harness's own for the module's tests."""
    root = tmp_path_factory.mktemp("joined")
    bench = root / "bench"
    shutil.copytree(bench_run.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    added = {
        "families/tiny_moe": None,
        "configs/tiny-moe.json": moe_config(),
        "traffic/tiny-short.json": tiny.mix(offline=False),
    }
    shutil.copytree(os.path.join(HERE, "families", "tiny_moe"),
                    bench / "families" / "tiny_moe",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for rel, data in added.items():
        if data is not None:
            (bench / rel).write_text(json.dumps(data))
    spec = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    spec["workloads"] = spec["workloads"] + [
        {"name": CELL, "config": "tiny-moe", "traffic": "tiny-short",
         "chips": 1, "why": "test"}]
    spec["per_layer"] = spec["per_layer"] + [
        {"name": f"{name}.moe", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "test",
         "moves": "online_tpot_p50_ms", "workloads": [CELL]}
        for name in ("ragged_paged_attention_roofline", "step_mfu")]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(bench_run, "ROOT", str(root))
        mp.setattr(bench_run, "BENCH", str(bench))
        mp.setattr(gen, "HERE", str(bench))
        mp.setattr(family, "FAMILIES", str(bench / "families"))
        yield {"root": root, "bench": bench, "added": added}


def _changed(a: str, b: str, rel: str = "") -> set:
    """Files of tree ``b`` that are new or differ from tree ``a``."""
    cmp = filecmp.dircmp(a, b, ignore=["__pycache__"])
    out = {os.path.join(rel, f) for f in cmp.right_only + cmp.diff_files}
    for sub in cmp.common_dirs:
        out |= _changed(os.path.join(a, sub), os.path.join(b, sub),
                        os.path.join(rel, sub))
    return out


def test_only_new_files_are_added(joined):
    assert _changed(REPO_BENCH, str(joined["bench"])) == set(joined["added"])


def test_the_cell_is_found_by_name(joined):
    spec, cell, cfg, mix = bench_run.load_cell(CELL)
    assert cfg["family"] == "tiny_moe" and "offline" not in mix
    m = reference.dims(cfg)
    assert m["family"] == "tiny_moe" and m["experts"] == 4
    assert family.module("tiny_moe", "reference").__file__.startswith(
        str(joined["bench"]))


@pytest.fixture(scope="module")
def sound(joined):
    cfg = bench_run.load_config("tiny-moe")
    picked = served(cfg, seed=SEED)
    m = reference.dims(cfg)
    return cfg, picked, m


def test_served_moe_matches_its_reference(sound):
    cfg, picked, m = sound
    gaps = check.served_gaps(m, reference.draw_jit(m, SEED), picked,
                             cfg["serving"]["max_model_len"])
    assert sum(len(g) for g in gaps) == 12 * len(picked)
    # float32 on the CPU on both sides: only summation order differs
    assert max(float(g.max()) for g in gaps) < 1e-4


def test_broken_expert_weight_is_caught(sound):
    cfg, picked, m = sound
    w = reference.draw_jit(m, SEED)
    w["w_down"] = w["w_down"].at[:, 1].multiply(-1.0)  # expert 1, every layer
    gaps = check.served_gaps(m, w, picked, cfg["serving"]["max_model_len"])
    assert max(float(g.max()) for g in gaps) > 1e-2


def test_readers_price_the_family_kernels(joined):
    spec, cell, cfg, _mix = bench_run.load_cell(CELL)
    m = reference.dims(cfg)
    fam = family.module("tiny_moe", "costs")
    assert costs.kernels(m) is fam.KERNELS
    items = [(24, 0, True, False), (1, 40, True, True)]
    peaks = costs.peaks("TPU v5 lite")
    meas = bench_run.Measurement(
        t0=0.0, t1=1.0, seconds=1.0, m=m, steps=[(0.1, 0.2, items, False)],
        trace={"op_s": {"ragged_paged_attention": 1e-6}, "window_s": 1.0,
               "busy_s": 0.5},
        trace_t=(0.0, 1.0), peaks=peaks,
        counters=({"measured_s": 0.0, "predicted_s": 0.0, "steps": 0},) * 2,
    )
    got = bench_run.per_layer(spec, cell, meas)
    flops, nbytes = fam.attention_cost(m, items)
    least = max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    assert set(got) == {"ragged_paged_attention_roofline.moe", "step_mfu.moe"}
    assert got["ragged_paged_attention_roofline.moe"]["value"] == pytest.approx(
        100.0 * least / 1e-6)
    step = (25 * fam.matmul_flops_per_token(m) + flops + fam.head_flops(m))
    assert costs.step_flops(m, items) == step
    assert got["step_mfu.moe"]["value"] == pytest.approx(
        100.0 * step / peaks["flops_per_s"])
    # the family's own arithmetic: the k chosen experts, not a dense FFN
    dense = dict(m, family="dense_gqa", qkv_bias=False, tied=False)
    assert costs.step_flops(dense, items) != step
