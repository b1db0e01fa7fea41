"""The program's spans and the partition of the chip's idle time by them.

A tiny served path runs under the profiler on the CPU -- the runtime's
loop over the engine, one offline request preempted, checkpointed and
resumed, one whose prompt is all cached prefix (a copy-on-write), and an
idle stretch before it arrives -- and every span the program defines must
appear on the loop's thread, nested inside its parent.  The partition
(``bench/metrics/_spans.py``) is checked on hand-made events and on a
window recorded on the chip (``bench/data/``).
"""
import numpy as np
import pytest

from bench import program, reference
from bench import trace as tracing
from bench.metrics import _spans
from bench.tests import tiny

# each span's parent on the test's path (None: a top-level span); a
# drain also runs at the safepoints between a dispatch's segments
PARENT = {
    "runtime.iteration": None,
    "runtime.idle": None,
    "runtime.drain": ("runtime.iteration", "engine.dispatch"),
    "runtime.pump": "runtime.iteration",
    "runtime.publish": "runtime.iteration",
    "engine.step": "runtime.iteration",
    "sched.plan": "engine.step",
    "sched.commit": "engine.step",
    "engine.events": "engine.step",
    "engine.build": "engine.step",
    "engine.put": "engine.step",
    "engine.dispatch": "engine.step",
    "engine.sample": "engine.step",
    "kv.checkpoint": "engine.step",
    "kv.cow": "engine.events",
    "kv.restore": "engine.events",
}
# spans this path never opens: work waiting that nothing can run, and a
# swap-out (a preempted request here is always fully checkpointed)
NOT_ON_PATH = {"runtime.backoff", "kv.swap_out"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Program spans of the served path, the forced victim, and the
    requests served."""
    import jax

    from repro.core.request import Phase, Priority, Request
    from repro.serving.runtime import CoServingRuntime, ManualClock

    cfg = tiny.config()
    eng = program.build_engine(cfg, 3)
    rng = np.random.default_rng(3)
    vocab = reference.dims(cfg)["vocab"]
    prompts = [rng.integers(0, vocab, p).astype(np.int32) for p in (40, 17, 63)]
    reqs = [Request(Priority.OFFLINE, prompt_len=len(p), max_new_tokens=12,
                    prompt=p) for p in prompts]
    # submitted with the preemption, while the first request still runs:
    # its prompt is the first one's two full blocks, all served from the
    # prefix cache, so recomputing its last token writes a shared block
    stem = Request(Priority.OFFLINE, prompt_len=32, max_new_tokens=4,
                   prompt=prompts[0][:32].copy())
    # due long after the others finish: the loop idles until then
    late = Request(Priority.OFFLINE, prompt_len=8, max_new_tokens=2,
                   prompt=prompts[1][:8].copy(), arrival_time=1e5)
    forced = []
    step = eng.step

    def step_then_preempt():
        alive = step()
        if not forced and eng.steps >= 4:
            victims = [r for r in eng.sched.running
                       if r.num_generated and r is not reqs[0]]
            if victims:
                eng.sched._preempt_offline(victims[0])
                forced.append(victims[0])
                rt.submit(stem)
        return alive

    eng.step = step_then_preempt
    rt = CoServingRuntime(eng, clock=ManualClock(auto_tick=1e-4), manual=True)
    log_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(log_dir))
    try:
        rt.replay(reqs + [late])
    finally:
        jax.profiler.stop_trace()
    reqs += [stem, late]
    assert forced and forced[0].num_preemptions == 1
    assert all(r.phase == Phase.FINISHED for r in reqs)
    assert stem.prefix_cached == stem.prompt_len - 1  # all but the last
    assert rt.registry.snapshot()["kv_cow_blocks_total"] > 0
    data = _spans.load(tracing.find(str(log_dir)))
    return data["spans"], forced[0], reqs, rt


def _short(name):
    assert name.startswith(_spans.PREFIX)
    return name[len(_spans.PREFIX):]


def test_every_span_appears_nested_in_its_parent(traced):
    from repro.serving.metrics import SPANS

    spans = traced[0]
    assert {_short(n) for n in SPANS} == set(PARENT) | NOT_ON_PATH
    assert set(_spans.SHARE_OF) | {_spans.PREFIX + k for k in _spans.KV} == set(SPANS)
    seen = {_short(n) for _s, _e, n, _st in spans}
    assert seen == set(PARENT)
    # the innermost span enclosing each one is its parent
    ordered = sorted(spans, key=lambda x: (x[0], -x[1]))
    stack = []
    for s, e, name, _st in ordered:
        while stack and stack[-1][1] <= s:
            stack.pop()
        parent = _short(stack[-1][2]) if stack else None
        want = PARENT[_short(name)]
        assert parent in (want if isinstance(want, tuple) else (want,)), (
            name, parent)
        assert not stack or e <= stack[-1][1], name
        stack.append((s, e, name))


def test_roots_count_steps_on_the_runtime_clock(traced):
    spans, _victim, reqs, rt = traced
    roots = sorted((s, st) for s, _e, n, st in spans if n == _spans.ROOT)
    steps = [st["step_num"] for _s, st in roots]
    t_host = [st["t_host"] for _s, st in roots]
    assert steps == sorted(steps) and steps[-1] == rt.engine.steps
    assert all(b > a for a, b in zip(t_host, t_host[1:]))
    assert t_host[-1] <= rt.now()
    # every engine step that ran a plan is one turn with its tokens
    assert sum(1 for _s, st in roots if st["tokens"]) == rt.engine.steps


def test_checkpoint_and_restore_carry_the_resumed_request(traced):
    spans, victim, _reqs, _rt = traced
    rid = victim.request_id
    ckpt = [st for _s, _e, n, st in spans if n == _spans.PREFIX + "kv.checkpoint"]
    # one id reads back as a number, several as a space-separated string
    assert any(str(rid) in str(st["requests"]).split() for st in ckpt)
    restore = [st for _s, _e, n, st in spans if n == _spans.PREFIX + "kv.restore"]
    assert [st["request"] for st in restore] == [rid]
    assert all(st["blocks"] > 0 for st in ckpt + restore)


# ------------------------------------------------- the partition, by hand
MS = 1_000_000


def _span(s, e, name, **stats):
    return (s * MS, e * MS, _spans.PREFIX + name, stats)


def _turn():
    """One loop turn: 10-20 ms is a device gap inside it."""
    return [
        _span(5, 30, "runtime.iteration", tokens=8),
        _span(6, 29, "engine.step"),
        _span(7, 11, "sched.plan"),
        _span(11, 13, "engine.build"),
        _span(13, 18, "engine.put"),
        _span(19, 29, "engine.dispatch"),
    ]


def test_a_gap_splits_exactly_across_nested_spans():
    ops = [(0, 10 * MS, "fusion.1"), (20 * MS, 30 * MS, "fusion.2")]
    part = _spans.partition(ops, _turn(), 0, 30 * MS)
    # plan 10-11, build 11-13, put 13-18, the step's own 18-19, dispatch 19-20
    assert part == {"idle": 10 * MS, "sched": 1 * MS, "build": 2 * MS,
                    "put": 5 * MS, "runtime": 1 * MS, "launch": 1 * MS}


def test_only_the_engine_lines_program_spans_count():
    frames = [(9 * MS, 19 * MS, "$real_engine.py:1171 _fused_inputs", {}),
              (8 * MS, 20 * MS, "bench.step", {}),
              (12 * MS, 14 * MS, "DevicePutWithSharding", {})]
    other = [_span(0, 40, "runtime.pump")]  # a thread with no loop turn
    line = _spans.engine_line([other, frames + _turn()])
    assert line == _turn()
    ops = [(0, 10 * MS, "fusion.1"), (20 * MS, 30 * MS, "fusion.2")]
    assert _spans.partition(ops, line, 0, 30 * MS)["put"] == 5 * MS


def test_shares_and_unattributed_add_up_to_the_idle_share():
    ops = [(0, 10 * MS, "a"), (20 * MS, 30 * MS, "b"), (40 * MS, 50 * MS, "c")]
    spans = [
        _span(5, 32, "runtime.iteration", tokens=8),
        _span(6, 31, "engine.step"),
        _span(10, 14, "engine.events"),
        _span(11, 13, "kv.restore", request=7, blocks=2),
        _span(14, 16, "engine.build"),
        _span(16, 31, "engine.dispatch"),
        _span(33, 36, "runtime.idle"),
        _span(37, 39, "runtime.iteration", tokens=0),  # an empty plan
        _span(37.5, 38.5, "runtime.publish"),
        _span(55, 60, "runtime.iteration", tokens=3),
        _span(56, 57, "runtime.pump"),
    ]
    spans.append(_span(99, 100, "runtime.drain"))  # the window's end
    got = _spans.shares({"ops": ops, "spans": spans}, 0.1)
    busy = sum(e - s for s, e in tracing.union(ops)) / 1e9
    assert got["idle"] == pytest.approx(100 * (1 - busy / 0.1))
    assert got["sched"] == pytest.approx(2)  # events' own 10-11 and 13-14
    assert got["build"] == pytest.approx(2)
    assert got["put"] == 0
    assert got["launch"] == pytest.approx(5)  # 16-20 and 30-31
    assert got["runtime"] == pytest.approx(7)  # 31-32, 55-60 and 99-100
    assert got["no_work"] == pytest.approx(5)  # runtime.idle, the empty turn
    # kv.restore (2) and the 47 ms under no program span
    assert got["unattributed"] == pytest.approx(49)
    assert sum(got[k] for k in _spans.SHARES) + got["unattributed"] == (
        pytest.approx(got["idle"]))
    assert got["iteration_ms"] == pytest.approx(16)  # median of 27 and 5


def test_no_program_spans_reads_none():
    ops = [(0, 10 * MS, "a")]
    frames = [[(0, 20 * MS, "$run.py:1 main", {}), (1 * MS, 9 * MS, "bench.step", {})]]
    data = {"ops": ops, "spans": _spans.engine_line(frames)}
    assert data["spans"] == [] and _spans.shares(data, 0.02) is None

    class Untraced:
        trace = None

    assert _spans.read(Untraced(), "put") is None


# ------------------------------------------------- on events from the chip
def _recorded_spans():
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "data", "qwen2-0.5b.online-chat.spans.json")
    with open(path) as f:
        rec = json.load(f)
    ops = [(s, e, "op") for s, e in rec["ops"]]
    spans = [(x[0], x[1], _spans.PREFIX + x[2],
              {"tokens": x[3]} if len(x) > 3 else {}) for x in rec["spans"]]
    return ops, spans, rec["window_s"]


def test_partition_on_recorded_chip_events_matches_a_brute_force_sweep():
    """The shares of a traced window recorded on the chip against an
    independent count: every elementary interval between two boundaries
    (an op's or a span's), idle when no op covers it, given to the
    covering span that started last."""
    ops, spans, window_s = _recorded_spans()
    got = _spans.shares({"ops": ops, "spans": spans}, window_s)
    w1 = max(e for _s, e, _n, _st in spans)
    w0 = w1 - window_s * 1e9
    cuts = np.unique(np.clip(np.array(
        [w0, w1] + [t for s, e, _n in ops for t in (s, e)]
        + [t for s, e, _n, _st in spans for t in (s, e)], np.float64), w0, w1))
    a, b = cuts[:-1], cuts[1:]
    mid = (a + b) / 2
    os_, oe = np.array([o[0] for o in ops]), np.array([o[1] for o in ops])
    order = np.argsort(os_)
    os_, oe = os_[order], np.maximum.accumulate(oe[order])
    k = np.searchsorted(os_, mid, side="right") - 1
    idle = (k < 0) | (oe[np.maximum(k, 0)] <= mid)
    share_of = {"sched.plan": "sched", "sched.commit": "sched",
                "engine.events": "sched", "engine.build": "build",
                "engine.put": "put", "engine.dispatch": "launch",
                "engine.sample": "launch", "runtime.idle": "no_work",
                "runtime.backoff": "no_work"}
    ss = np.array([s for s, _e, _n, _st in spans])
    se = np.array([e for _s, e, _n, _st in spans])
    total = {}
    for lo, hi, m in zip(a[idle], b[idle], mid[idle]):
        cover = np.nonzero((ss <= m) & (se >= m))[0]
        if not len(cover):
            label = "unattributed"
        else:
            name = spans[cover[np.argmax(ss[cover])]][2][len(_spans.PREFIX):]
            empty = any(spans[i][2] == _spans.ROOT and not spans[i][3]["tokens"]
                        for i in cover)
            label = ("no_work" if empty else share_of.get(
                name, "unattributed" if name.startswith("kv.") else "runtime"))
        total[label] = total.get(label, 0.0) + (hi - lo)
    pct = {k: 100.0 * v / 1e9 / window_s for k, v in total.items()}
    assert got["idle"] == pytest.approx(sum(pct.values()), abs=1e-6)
    for k in (*_spans.SHARES, "unattributed"):
        assert got[k] == pytest.approx(pct.get(k, 0.0), abs=1e-6), k
    # what the chip showed: the transfers' share leads, every turn ran a plan
    assert got["put"] == max(got[k] for k in _spans.SHARES)
    assert got["no_work"] == 0 and got["iteration_ms"] > 0
