"""The plain reference against the served path, at a small size on the CPU.

The program serves greedy tokens through its paged fused path (prefill
chunks, decodes, one preemption and resume); the reference must put each
served token within rounding of its best logit, and a reference with one
part broken must not.
"""
import numpy as np
import pytest

import jax

from bench import check, program, reference
from bench.tests import tiny


def served(cfg, seed=3, n=4, preempt=True):
    """Greedy tokens of ``n`` requests served by the program's engine, one
    of them preempted mid-decode and resumed; returns the check's
    (request id, prompt, tokens) triples."""
    from repro.core.request import Priority, Request

    eng = program.build_engine(cfg, seed)
    rng = np.random.default_rng(seed)
    vocab = reference.dims(cfg)["vocab"]
    reqs = [
        Request(Priority.OFFLINE, prompt_len=p, max_new_tokens=12,
                prompt=rng.integers(0, vocab, p).astype(np.int32))
        for p in (40, 17, 63, 30)[:n]
    ]
    for r in reqs:
        eng.submit(r)
    for _ in range(4):
        eng.step()
    if preempt:
        victim = next(r for r in eng.sched.running if r.num_generated)
        eng.sched._preempt_offline(victim)
    eng.run()
    assert all(len(r.output_tokens) == 12 for r in reqs)
    if preempt:
        assert any(r.num_preemptions for r in reqs)
    return [(r.request_id, np.asarray(r.prompt), list(r.output_tokens))
            for r in reqs]


@pytest.fixture(scope="module")
def sound():
    cfg = tiny.config()
    picked = served(cfg)
    m = reference.dims(cfg)
    w = reference.draw_jit(m, 3)
    gaps = check.served_gaps(m, w, picked, cfg["serving"]["max_model_len"])
    return cfg, picked, m, gaps


def test_served_path_matches_reference(sound):
    _cfg, picked, _m, gaps = sound
    assert sum(len(g) for g in gaps) == 12 * len(picked)
    # float32 on the CPU on both sides: only summation order differs
    assert max(float(g.max()) for g in gaps) < 1e-4


def test_padding_to_a_bucket_keeps_the_gaps(sound, monkeypatch):
    """Each sequence padded to its power-of-two bucket reads the same gaps
    as padded to the longest the model serves."""
    cfg, picked, m, gaps = sound
    monkeypatch.setattr(check, "MIN_PAD", 16)
    w = reference.draw_jit(m, 3)
    again = check.served_gaps(m, w, picked, cfg["serving"]["max_model_len"])
    assert len({1 << (len(p) + len(t) - 2).bit_length() for _r, p, t in picked}) > 1
    for a, b in zip(gaps, again):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_untied_share_matches_reference():
    """An untied head and no QKV bias (the Llama family's shapes) through
    the same path."""
    cfg = tiny.config(tied=False, bias=False)
    picked = served(cfg, seed=5, n=2, preempt=False)
    m = reference.dims(cfg)
    assert not m["tied"] and not m["qkv_bias"]
    gaps = check.served_gaps(m, reference.draw_jit(m, 5), picked, 128)
    assert max(float(g.max()) for g in gaps) < 1e-4


def _no_rope(x, pos, theta):
    return x


def _wrong_kv_head(q, k, v, lowp):
    # query head i paired with KV head i % kv instead of i // group
    n, h, hd = q.shape
    kvh = k.shape[1]
    qg = q.reshape(n, h // kvh, kvh, hd).transpose(0, 2, 1, 3)
    return _attention(qg.reshape(n, h, hd), k, v, lowp)


_attention = reference.attention


@pytest.mark.parametrize("broken", ["rope_off", "bias_dropped", "wrong_kv_head"])
def test_broken_reference_is_caught(sound, broken, monkeypatch):
    cfg, picked, m, _gaps = sound
    m = dict(m)
    if broken == "rope_off":
        monkeypatch.setattr(reference, "rope", _no_rope)
    elif broken == "bias_dropped":
        m["qkv_bias"] = False
    else:
        monkeypatch.setattr(reference, "attention", _wrong_kv_head)
    jax.clear_caches()
    w = reference.draw_jit(dict(reference.dims(cfg)), 3)
    gaps = check.served_gaps(m, w, picked, cfg["serving"]["max_model_len"])
    jax.clear_caches()
    assert max(float(g.max()) for g in gaps) > 1e-2


@pytest.mark.parametrize("tied", [True, False])
def test_every_parameter_is_drawn_nonzero(tied):
    m = reference.dims(tiny.config(tied=tied, bias=tied))
    w = reference.draw_jit(m, 2**40 + 17)
    expect = {"embed", "final_norm", "ln1", "ln2", "wq", "wk", "wv", "wo",
              "w_gate", "w_up", "w_down"}
    expect |= {"bq", "bk", "bv"} if m["qkv_bias"] else {"lm_head"}
    assert set(w) == expect
    for name, a in w.items():
        a = np.asarray(a)
        assert np.all(a != 0), name
        assert a.std() > 0, name
    # the same seed draws the same weights; another seed others
    again = reference.draw_jit(m, 2**40 + 17)
    other = reference.draw_jit(m, 2**40 + 18)
    assert np.array_equal(np.asarray(again["wq"]), np.asarray(w["wq"]))
    assert not np.array_equal(np.asarray(other["wq"]), np.asarray(w["wq"]))
