"""The family contract keeps the qwen2 cell's yardstick as it was.

The values pinned here were computed by the benchmark before its dense GQA
code moved into ``bench/families/dense_gqa/``: the weights drawn for a
seed (bitwise), the reference's gaps of one sequence, the operations and
bytes of three plans (exact) and the per-layer readers on one
``Measurement``.  Then: attention in query blocks equals one unblocked
pass, no score tensor spans more than a block of queries, and every
family's reference loads without the program.
"""
import glob
import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from bench import costs, family, reference
from bench import run as bench_run
from bench.tests import tiny

ROOT = bench_run.ROOT
DRAWN = {  # seed -> sha256 of the tiny configuration's weights
    7: "f3fe1eff884b32f83ebbc33f5556c36ba5a55cb6df1dccabbc453d0ad6b4276c",
    2**40 + 3: "1b0d3e579aa8defc7bb435954093ec2d6851ef0e3bd9152cd855b0f3d20fce26",
}
DRAWN_UNTIED_5 = "1ad1801028399312ceb705cc409560ea7a4a051dad9a35a23d7249cf6130432e"
GAPS = [
    0.251551211, 0.288175017, 0.780068874, 0.686076164, 0.278349996,
    0.860240936, 0.225597396, 0.555176079, 0.573636413, 0.491629004,
    0.378118634, 0.450915575, 0.54237026, 0.80030632, 0.307621211,
    0.457768321, 0.788119495, 0.663914025, 0.674171865, 0.231221288,
    0.536061645, 0.327097297, 0.342998952, 0.187878013, 0.38859573,
    0.595200062, 0.495406628, 0.265088111, 0.633482516, 0.485525608,
    0.584872544, 0.800338864, 0.290737867, 0.522023559, 0.490973443,
    0.150607884, 0.499571532, 0.102312684, 0.191682056, 0.431561887,
]
BEST = [
    0.430457473, 0.441718787, 0.530040562, 0.559486687, 0.38117972,
    0.496638894, 0.461393803, 0.566394925, 0.428634405, 0.514119327,
    0.470923513, 0.417857617, 0.562687814, 0.64231807, 0.619379938,
    0.558584154, 0.702142954, 0.546617627, 0.601709723, 0.446002215,
    0.460523814, 0.390610278, 0.453891933, 0.400692016, 0.510506928,
    0.577530921, 0.473709673, 0.532998919, 0.479938626, 0.430704504,
    0.49632591, 0.647354186, 0.368607461, 0.417959154, 0.404508829,
    0.412717611, 0.431707889, 0.477449119, 0.40928334, 0.453682333,
]
LOWP_TOP = [
    4, 305, 109, 109, 109, 476, 109, 109, 281, 378, 303, 188, 109, 109, 109,
    109, 109, 109, 305, 121, 242, 224, 373, 303, 166, 362, 229, 411, 362, 397,
    109, 362, 362, 121, 128, 281, 166, 307, 121, 172,
]
PLANS = {  # plan -> (items, step_flops, attention (flops, bytes)) at qwen2-0.5b
    "decode": ([(1, 500, True, True), (1, 1200, True, True), (1, 37, False, True)],
               3113435136, (149667840, 43278336)),
    "chunk": ([(512, 0, True, False)], 377710706688, (11296309248, 100663296)),
    "mixed": ([(256, 512, True, True), (1, 900, True, True), (1, 64, False, True),
               (97, 0, False, False)], 269469462528, (14595796992, 106070016)),
}
READERS = {
    "ragged_paged_attention_roofline.online": 4.963650853211829,
    "step_mfu.online": 1.5615507648324873,
    "budget_drift": 0.6363636363636362,
    "step_ms": 50.0,
    "device_idle_pct.online": 60.0,
}


def digest(w: dict) -> str:
    h = hashlib.sha256()
    for name in sorted(w):
        a = np.asarray(w[name])
        h.update(name.encode())
        h.update(str(a.shape).encode())
        h.update(a.astype(np.float32).tobytes())
    return h.hexdigest()


def qwen2() -> dict:
    return reference.dims(bench_run.load_config("qwen2-0.5b"))


def golden_sequence():
    m = reference.dims(tiny.config())
    rng = np.random.default_rng(11)
    tokens = rng.integers(0, m["vocab"], 40).astype(np.int32)
    targets = rng.integers(0, m["vocab"], 40).astype(np.int32)
    return m, reference.draw_jit(m, 3), tokens, targets


# --------------------------------------------------- the qwen2 cell, pinned
@pytest.mark.parametrize("seed", sorted(DRAWN))
def test_weights_are_bitwise_the_same(seed):
    m = reference.dims(tiny.config())
    assert m["family"] == "dense_gqa"
    assert digest(reference.draw_jit(m, seed)) == DRAWN[seed]


def test_untied_weights_are_bitwise_the_same():
    m = reference.dims(tiny.config(tied=False, bias=False))
    assert digest(reference.draw_jit(m, 5)) == DRAWN_UNTIED_5


def test_reference_gaps_are_the_same():
    m, w, tokens, targets = golden_sequence()
    gap, best = reference.gaps(m, w, tokens, targets, 64)
    np.testing.assert_allclose(gap, GAPS, rtol=0, atol=1e-6)
    np.testing.assert_allclose(best, BEST, rtol=0, atol=1e-6)
    assert list(reference.argmax_lowp(m, w, tokens, 64)) == LOWP_TOP


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_costs_are_the_same(plan):
    items, flops, attn = PLANS[plan]
    m = qwen2()
    assert costs.step_flops(m, items) == flops
    assert costs.kernel_cost(m, "ragged_paged_attention", items) == attn


def reader_measurement():
    """One ``Measurement`` of the three plans, three times, one aborted."""
    steps, t = [], 10.0
    for i, (items, _f, _a) in enumerate(list(PLANS.values()) * 3):
        steps.append((t, t + 0.03, items, i == 4))
        t += 0.05
    return bench_run.Measurement(
        t0=10.0, t1=10.3, seconds=0.3, m=qwen2(), steps=steps,
        trace={"op_s": {"ragged_paged_attention": 0.0123}, "window_s": 0.5,
               "busy_s": 0.2},
        trace_t=(10.02, 10.4), peaks=costs.peaks("TPU v5 lite"),
        counters=({"measured_s": 1.0, "predicted_s": 2.0, "steps": 3},
                  {"measured_s": 1.7, "predicted_s": 3.1, "steps": 9}),
    )


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_read_the_same(name):
    assert bench_run.read_reader(name)(reader_measurement()) == READERS[name]


def test_roofline_is_silent_for_a_kernel_the_family_does_not_price():
    from bench.metrics import _roofline

    r = reader_measurement()
    r.trace["op_s"]["some_new_kernel"] = 1.0
    assert _roofline.read(r, "some_new_kernel") is None


# ------------------------------------------------------- attention in blocks
@pytest.fixture
def clear_jit():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("lowp", [False, True])
def test_blocked_attention_equals_one_pass(lowp, monkeypatch):
    """Three blocks and a partial one against the whole sequence at once."""
    n, h, kvh, hd = 3 * 8 + 5, 4, 2, 16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (n, h, hd), jnp.float32)
    k = jax.random.normal(kk, (n, kvh, hd), jnp.float32)
    v = jax.random.normal(kv, (n, kvh, hd), jnp.float32)
    whole = np.asarray(reference.attention(q, k, v, lowp))
    monkeypatch.setattr(reference, "Q_BLOCK", 8)
    blocked = np.asarray(reference.attention(q, k, v, lowp))
    np.testing.assert_allclose(blocked, whole, rtol=0, atol=1e-6)


def test_blocked_gaps_equal_the_pinned_ones(monkeypatch, clear_jit):
    """The whole reference with 64 padded positions in blocks of 18 rows."""
    m, w, tokens, targets = golden_sequence()
    monkeypatch.setattr(reference, "Q_BLOCK", 18)
    gap, best = reference.gaps(m, w, tokens, targets, 64)
    np.testing.assert_allclose(gap, GAPS, rtol=0, atol=1e-6)
    np.testing.assert_allclose(best, BEST, rtol=0, atol=1e-6)


def _shapes(jaxpr):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(getattr(v.aval, "shape", ()))
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                if isinstance(sub, ClosedJaxpr):
                    yield from _shapes(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _shapes(sub)


def test_no_score_tensor_spans_more_than_a_block(monkeypatch):
    """No array of the reference has both a query and a key axis of the
    sequence's length: scores are one block of queries x every key."""
    n = 72  # no other axis of the tiny model is 72 long
    m = reference.dims(tiny.config())
    w = reference.draw_jit(m, 1)
    monkeypatch.setattr(reference, "Q_BLOCK", 16)
    jaxpr = jax.make_jaxpr(lambda w, t: reference.hidden(m, w, t))(
        w, jnp.zeros(n, jnp.int32)).jaxpr
    shapes = list(_shapes(jaxpr))
    assert any(s.count(n) == 1 and 16 in s for s in shapes)  # a block's scores
    assert not [s for s in shapes if s.count(n) > 1]
    monkeypatch.setattr(reference, "Q_BLOCK", n)  # unblocked: scores n x n
    jaxpr = jax.make_jaxpr(lambda w, t: reference.hidden(m, w, t))(
        w, jnp.zeros(n, jnp.int32)).jaxpr
    assert [s for s in _shapes(jaxpr) if s.count(n) > 1]


# ------------------------------------------- every family's files, by itself
REFERENCES = sorted(
    glob.glob(os.path.join(bench_run.BENCH, "families", "*", "reference.py"))
    + glob.glob(os.path.join(bench_run.BENCH, "tests", "families", "*",
                             "reference.py")))


@pytest.mark.parametrize(
    "path", REFERENCES, ids=[os.path.relpath(p, ROOT) for p in REFERENCES])
def test_reference_imports_nothing_of_the_program(path):
    """Loaded in a fresh interpreter, a family's reference leaves no module
    of the program in ``sys.modules``."""
    families, name = os.path.split(os.path.dirname(path))
    code = (
        f"import sys; sys.path.insert(0, {ROOT!r})\n"
        "from bench import family\n"
        f"family.FAMILIES = {families!r}\n"
        f"family.module({name!r}, 'reference')\n"
        "bad = sorted(k for k in sys.modules if k.split('.')[0] == 'repro')\n"
        "print('program modules:', bad)\n"
        "sys.exit(1 if bad else 0)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, timeout=300,
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "program modules: []" in out.stdout


def test_a_config_without_a_family_is_an_error(tmp_path, monkeypatch):
    """``load_config`` (and so ``load_cell``) refuses a configuration that
    names no family, or a family without its files."""
    (tmp_path / "configs").mkdir()
    monkeypatch.setattr(bench_run, "BENCH", str(tmp_path))
    cfg = tiny.config()
    del cfg["family"]
    (tmp_path / "configs" / "none.json").write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="names no family"):
        bench_run.load_config("none")
    cfg["family"] = "no_such_family"
    (tmp_path / "configs" / "lost.json").write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="has no reference.py, program.py, costs.py"):
        bench_run.load_config("lost")
