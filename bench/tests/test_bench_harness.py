"""The harness without a chip: traffic, tails, the benchmark file, and
finding a configuration, a mix and a metric by name."""
import json
import os
import re
import shutil

import numpy as np
import pytest

from bench import family
from bench import run as bench_run
from bench import traffic as gen
from bench.tests import tiny

ROOT = bench_run.ROOT
SPEC = bench_run.load_json(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# ------------------------------------------------------------------ traffic
def test_traffic_is_deterministic_per_seed():
    mix, cfg = tiny.mix(), tiny.config()
    a = gen.generate(mix, cfg, 512, 2**33 + 1, 20.0)
    b = gen.generate(mix, cfg, 512, 2**33 + 1, 20.0)
    assert [r.due for r in a.online] == [r.due for r in b.online]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a.online, b.online))
    assert [o[1] for o in a.offline] == [o[1] for o in b.offline]


def test_seeds_share_the_work_in_another_order():
    mix, cfg = tiny.mix(), tiny.config()
    a = gen.generate(mix, cfg, 512, 1, 20.0)
    b = gen.generate(mix, cfg, 512, 2, 20.0)
    gaps = lambda t: sorted(np.round(np.diff([r.due for r in t.online] + [20.0]), 9))
    assert len(a.online) == len(b.online) == round(a.rate * 20)
    assert sorted(len(r.prompt) for r in a.online) == sorted(len(r.prompt) for r in b.online)
    assert sorted(r.max_new for r in a.online) == sorted(r.max_new for r in b.online)
    assert [r.due for r in a.online] != [r.due for r in b.online]
    assert sorted(len(p) for p, _ in a.offline) == sorted(len(p) for p, _ in b.offline)
    assert np.isclose(sum(np.diff([r.due for r in a.online])),
                      sum(np.diff([r.due for r in b.online])), atol=20.0)
    assert len(gaps(a)) == len(gaps(b))


def test_online_arrivals_match_across_mixes():
    """A mix that adds an offline backlog to online-chat keeps the same
    online arrivals for the same seed."""
    cfg = tiny.config()
    on_mix = gen.load_mix("online-chat")
    co_mix = dict(on_mix, offline=tiny.mix()["offline"])
    on = gen.generate(on_mix, cfg, 151936, 77, 30.0)
    co = gen.generate(co_mix, cfg, 151936, 77, 30.0)
    assert [r.due for r in on.online] == [r.due for r in co.online]
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(on.online, co.online))
    assert not on.offline and co.offline


def test_lengths_stay_in_range_and_fit_the_model():
    for name in ("online-chat",):
        mix = gen.load_mix(name)
        for part in ("online", "offline"):
            if part not in mix:
                continue
            p, o = mix[part]["prompt"], mix[part]["output"]
            xs = gen.lognormal_set(p, 1000)
            assert xs.min() >= p["min"] and xs.max() <= p["max"]
            assert np.median(xs) == pytest.approx(p["median"], rel=0.01)
            for cfg in SPEC["configs"]:
                c = bench_run.load_json(ROOT, cfg["file"])
                assert p["max"] + o["max"] <= c["serving"]["max_model_len"]


def test_gamma_gaps_keep_rate_and_burstiness():
    g = gen.gamma_gaps(4.0, 2.0, 4000)
    assert g.sum() == pytest.approx(1000.0)
    assert g.std() / g.mean() == pytest.approx(2.0, rel=0.1)


@pytest.mark.parametrize("seed", [3000001643, 3000001631, 2**40 + 9])
def test_traced_stretch_holds_the_last_arrival(seed):
    """The traced stretch is the window's last seconds, or starts at the
    last online arrival where that is earlier (seed 3000001643's last
    arrival is due 11 s before the window closes: its last 5 s held no
    device work on the chip)."""
    cfg = bench_run.load_config("qwen2-0.5b")
    mix = gen.load_mix("online-chat")
    online = gen.generate(mix, cfg, cfg["vocab_size"], seed, 51.0).online
    last = max(r.due for r in online)
    a, b = bench_run.traced_stretch(online, 51.0)
    assert b - a == bench_run.TRACE_SECONDS and b <= 51.0
    assert a <= last < b or a == 51.0 - bench_run.TRACE_SECONDS
    if last < 51.0 - bench_run.TRACE_SECONDS:
        assert a == last
    assert bench_run.traced_stretch(online, 3.0) == (0.0, 3.0)


# -------------------------------------------------------------------- tails
class _Req:
    def __init__(self, phase, first, times):
        from repro.core.request import Phase

        self.phase = getattr(Phase, phase)
        self.first_token_time = first
        self.token_times = times


class _Handle:
    def __init__(self, req):
        self.request = req


def test_tails_come_from_raw_times_and_failures_miss():
    slo = bench_run.load_json(bench_run.BENCH, "slo.json")
    fake = bench_run.CellRun.__new__(bench_run.CellRun)
    fake.t0, fake.offset, fake.t_drained = 100.0, 100.0, 200.0
    online = []
    for i in range(19):  # due at i, first token 0.1 s later, 5 tokens 20 ms apart
        r = gen.OnlineRequest(float(i), np.zeros(4, np.int32), 5)
        t = [i + 0.1 + 0.02 * k for k in range(5)]
        r.handle = _Handle(_Req("FINISHED", t[0], t))
        online.append(r)
    refused = gen.OnlineRequest(19.0, np.zeros(4, np.int32), 5)
    refused.error = "QueueFull()"
    online.append(refused)
    fake.traffic = gen.Traffic(rate=1.0, online=online)
    t = fake.online_times(slo)
    assert t["n"] == 20 and t["failed"] == 1 and t["met"] == 19
    assert t["ttft"][:19] == pytest.approx([0.1] * 19)
    assert t["tpot"][:19] == pytest.approx([0.02] * 19)
    # the refused request waited from its due time to the drain deadline
    assert t["ttft"][19] == pytest.approx(200.0 - 119.0)
    assert bench_run.percentile(t["ttft"], 95) > 0.1


# ---------------------------------------------------------- BENCHMARK.json
def test_benchmark_file_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in SPEC[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and os.path.exists(os.path.join(ROOT, c["file"]))
        for k in c["reduced"]:
            assert NAME.match(k)
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic", w["traffic"] + ".json"))
    assert 1 <= SPEC["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


def test_every_cell_reports_what_its_metrics_move():
    for w in SPEC["workloads"]:
        cell = {"name": w["name"]}
        e2e = {m["name"] for m in bench_run.metrics_for(SPEC, cell, "end_to_end")}
        layer = bench_run.metrics_for(SPEC, cell, "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        for m in layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
            assert callable(bench_run.read_reader(m["name"]))


def test_configs_match_their_files():
    """Each configuration's file matches its entry and names a model family
    whose three modules exist (``bench/family.py``)."""
    for c in SPEC["configs"]:
        f = bench_run.load_json(ROOT, c["file"])
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert f["reduced"] == c["reduced"]
        assert f["precision"]["matmul"] == "bfloat16"
        name = family.check(f)
        for part in family.PARTS:
            assert os.path.isfile(os.path.join(family.FAMILIES, name, f"{part}.py"))


# ------------------------------------------------- found by name from files
def test_new_config_mix_and_metric_are_found_by_name(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(bench_run.BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    cfg = tiny.config()
    cfg["name"] = "tiny-new"
    (bench / "configs" / "tiny-new.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "short-new.json").write_text(json.dumps(tiny.mix(offline=False)))
    (bench / "metrics" / "new_metric.py").write_text("def read(r):\n    return 42.0\n")
    spec = dict(SPEC)
    spec["workloads"] = [{"name": "tiny-new.short-new", "config": "tiny-new",
                          "traffic": "short-new", "chips": 1, "why": "test"}]
    spec["per_layer"] = [{"name": "new_metric.online", "unit": "x", "better": "lower",
                          "source": "host_clock", "layer": "test",
                          "moves": "online_tpot_p50_ms"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(bench_run, "ROOT", str(tmp_path))
    monkeypatch.setattr(bench_run, "BENCH", str(bench))
    monkeypatch.setattr(gen, "HERE", str(bench))
    monkeypatch.setattr(family, "FAMILIES", str(bench / "families"))
    got_spec, cell, got_cfg, mix = bench_run.load_cell("tiny-new.short-new")
    assert got_cfg["name"] == "tiny-new" and "offline" not in mix
    assert bench_run.read_reader("new_metric.online")(None) == 42.0
    assert bench_run.per_layer(got_spec, cell, None) == {
        "new_metric.online": {"value": 42.0, "unit": "x"}}


def test_every_finished_request_is_compared():
    from bench import check

    finished = [(7, np.arange(3), [1, 2]), (2, np.arange(5), [4]),
                (9, np.arange(2), [5, 6, 7])]
    assert [r[0] for r in check.compared(finished)] == [2, 7, 9]
