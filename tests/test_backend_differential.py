"""Differential token-identity harness across execution backends.

One trace, five executions of RealEngine — they must emit byte-identical
greedy tokens (DESIGN.md §11/§12/§13):

  * ``contiguous``   — per-request stacked caches (the §4 fallback layout),
  * ``split paged``  — shared block pool, per-family dispatches
                       (``fused_batch=False``, the §9 oracle paths),
  * ``fused paged``  — the same pool, every iteration lowered to ONE
                       ragged token batch (prefill chunks + decodes) and
                       dispatched once per K-layer segment (§12),
  * ``pipelined``    — the fused path with the async host/device pipeline
                       on (§13): speculative plan+build of iteration N+1
                       overlapped with N, deferred-token injection, async
                       sampled-token readback,
  * ``sharded fused``— the fused path over a tensor-parallel serving
                       mesh (``launch.mesh.make_serving_mesh``).

The sharded leg uses as many devices as are visible (capped at 4): under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (CI's sharded matrix
job) it genuinely distributes KV heads; on a single real device it
degenerates to a 1-device mesh, which still exercises the whole mesh code
path (placement, constraints, replicated inputs) and must be behaviorally
identical to ``mesh=None``.

Cases sweep the two axes where backends could plausibly diverge:
batch-bucket boundaries (decode batches draining across the power-of-two
buckets, prompt lengths straddling prefill length buckets) and
preempt/resume points (online bursts at different step offsets forcing
eviction + incremental-checkpoint restore mid-generation).
"""
import functools

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.request import Priority, Request
from repro.launch.mesh import make_serving_mesh
from repro.models import transformer as tf
from repro.serving.real_engine import RealEngine, RealEngineConfig


# Reduced configs beyond ``ModelConfig.reduced()``'s defaults: a deeper
# stack whose fused segments run the in-place layer loop over two periods
# (at pool offsets 0 and 2) and a one-period tail segment at offset 4.
# No paged architecture has a layer pattern of more than one position.
VARIANTS = {
    "qwen2-0.5b-deep": ("qwen2-0.5b", dict(num_layers=5, safepoint_interval=2)),
}


@functools.lru_cache(maxsize=None)
def _model(arch: str):
    base, overrides = VARIANTS.get(arch, (arch, {}))
    cfg = get_config(base).reduced(**overrides)
    return cfg, tf.init_params(cfg, jax.random.PRNGKey(0))


def _mkreq(cfg, prio, plen, gen, seed):
    prompt = (
        np.random.default_rng(seed)
        .integers(0, cfg.vocab_size, plen)
        .astype(np.int32)
    )
    return Request(prio, prompt_len=plen, max_new_tokens=gen, prompt=prompt)


def _run(arch, backend, jobs, preempt_step, mesh=None, eng_kw=None):
    """Run one trace; returns (offline outputs, online outputs, requests)."""
    cfg, params = _model(arch)
    eng = RealEngine(
        cfg, params,
        eng_cfg=RealEngineConfig(backend=backend, mesh=mesh, **(eng_kw or {})),
    )
    reqs = [
        _mkreq(cfg, Priority.OFFLINE, plen, gen, seed)
        for seed, (plen, gen) in enumerate(jobs)
    ]
    for r in reqs:
        eng.submit(r)
    online = []
    if preempt_step is not None:
        for _ in range(preempt_step):
            eng.step()
        for s in range(2):
            online.append(_mkreq(cfg, Priority.ONLINE, 60, 8, 100 + s))
            eng.on_online_arrival(online[-1])
    eng.run()
    return [r.output_tokens for r in reqs], [r.output_tokens for r in online], reqs


def _tp(kv_heads: int = 4) -> int:
    """Largest mesh size <= 4 visible devices that divides the KV heads (a
    non-dividing model axis is a construction error, never a silent
    replication)."""
    tp = min(4, len(jax.devices()))
    while kv_heads % tp:
        tp -= 1
    return tp


# (arch, [(prompt_len, max_new), ...], preempt_step, engine kwargs)
CASES = [
    # batch of 3 pads into the 4-bucket; uniform lengths
    ("llama-2-7b", [(40, 8)] * 3, None, {}),
    # 5 requests draining 5..1 across decode buckets {8, 4, 2, 1}; prompts
    # straddle the prefill length buckets (8/16/32)
    ("llama-2-7b", [(40, 12), (24, 10), (40, 8), (10, 6), (40, 4)], None, {}),
    # online burst mid-decode under block pressure: eviction + IC restore
    ("llama-2-7b", [(40, 16)] * 3, 6, dict(num_device_blocks=14)),
    # same burst landing during the prefill wave
    ("llama-2-7b", [(40, 16)] * 3, 2, dict(num_device_blocks=14)),
    # GQA arch (4Q/2KV heads, groups of 2): the sharded leg splits the 2 KV
    # heads over at most 2 devices, each keeping its whole query group
    ("qwen2-0.5b", [(40, 8), (20, 8)], None, {}),
    ("qwen2-0.5b", [(40, 10), (24, 6), (40, 6), (20, 4)], 4,
     dict(num_device_blocks=14)),
    # multi-period segments: preempt/resume restores and extracts through
    # every pool layer the in-place loop writes
    ("qwen2-0.5b-deep", [(40, 10), (24, 6), (40, 6), (20, 4)], 4,
     dict(num_device_blocks=14)),
]


@pytest.mark.parametrize("arch,jobs,preempt_step,eng_kw", CASES)
def test_backends_emit_identical_tokens(arch, jobs, preempt_step, eng_kw):
    out_c, on_c, _ = _run(arch, "contiguous", jobs, preempt_step,
                          eng_kw=eng_kw)
    out_p, on_p, reqs_p = _run(arch, "paged", jobs, preempt_step,
                               eng_kw=dict(eng_kw, fused_batch=False))
    out_f, on_f, reqs_f = _run(arch, "paged", jobs, preempt_step,
                               eng_kw=eng_kw)
    out_l, on_l, reqs_l = _run(arch, "paged", jobs, preempt_step,
                               eng_kw=dict(eng_kw, pipeline=True))
    mesh = make_serving_mesh(_tp(_model(arch)[0].num_kv_heads))
    out_s, on_s, reqs_s = _run(arch, "paged", jobs, preempt_step,
                               mesh=mesh, eng_kw=eng_kw)
    assert [len(o) for o in out_p] == [g for _, g in jobs]
    assert out_p == out_c, "split paged backend diverged from contiguous"
    assert out_f == out_p, "fused ragged path diverged from split paged"
    assert out_l == out_f, "pipelined engine diverged from serial fused"
    assert out_s == out_f, "sharded fused backend diverged from single-device"
    assert on_l == on_s == on_f == on_p == on_c, (
        "online request tokens diverged"
    )
    if preempt_step is not None:
        # the scenario must actually exercise preempt/resume, identically
        # in all paged legs (the block manager is dispatch-oblivious)
        npre = sum(r.num_preemptions for r in reqs_p)
        assert npre > 0, "preemption scenario did not preempt"
        assert sum(r.num_preemptions for r in reqs_f) == npre
        assert sum(r.num_preemptions for r in reqs_l) == npre
        assert sum(r.num_preemptions for r in reqs_s) == npre


def test_fused_mid_iteration_abort_is_exact():
    """Mid-iteration safepoint abort on the fused path (DESIGN.md §12):
    force the preemption flag at the FIRST safepoint cut inside a
    pure-offline fused iteration — after one K-layer segment has already
    scattered this iteration's KV into the pool — and the run must still
    emit byte-identical tokens: the aborted tokens' pool writes sit at
    uncommitted positions and are rewritten verbatim on re-execution.
    Asserts the abort actually happened and that the aborted iteration
    dispatched fewer segments than a completed one would."""
    cfg, params = _model("llama-2-7b")
    jobs = [(40, 8)] * 3

    def _go(abort_at_step):
        eng = RealEngine(
            cfg, params, eng_cfg=RealEngineConfig(backend="paged")
        )
        reqs = [
            _mkreq(cfg, Priority.OFFLINE, plen, gen, seed)
            for seed, (plen, gen) in enumerate(jobs)
        ]
        for r in reqs:
            eng.submit(r)
        if abort_at_step is not None:
            for _ in range(abort_at_step):
                eng.step()
            eng.arrival_poll = lambda: eng.flag.set()
            before = eng.dispatches["fused_segment"]
            eng.step()
            assert eng.safepoints.stats.preemptions == 1, "no abort happened"
            assert (
                eng.dispatches["fused_segment"] - before
                < tf.num_segments(cfg)
            ), "aborted iteration ran every segment"
            eng.arrival_poll = None
        eng.run()
        return [r.output_tokens for r in reqs]

    assert tf.num_segments(cfg) > 1, "config cannot express a mid-batch cut"
    assert _go(3) == _go(None), "abort changed the emitted tokens"


def test_pipelined_mid_iteration_abort_discards_staged_batch():
    """Safepoint abort on the PIPELINED engine (DESIGN.md §13): the
    aborted iteration is itself a speculatively staged batch — planned and
    host-built while the previous iteration ran on device.  The abort must
    throw it away exactly like the serial engine discards an in-flight
    batch (commit skipped, requests stay schedulable) and must not stage a
    successor, so the next turn replans serially against the post-abort
    scheduler state; the run must still emit byte-identical tokens."""
    cfg, params = _model("llama-2-7b")
    jobs = [(40, 8)] * 3

    def _go(abort_at_step):
        eng = RealEngine(
            cfg, params,
            eng_cfg=RealEngineConfig(backend="paged", pipeline=True),
        )
        reqs = [
            _mkreq(cfg, Priority.OFFLINE, plen, gen, seed)
            for seed, (plen, gen) in enumerate(jobs)
        ]
        for r in reqs:
            eng.submit(r)
        if abort_at_step is not None:
            for _ in range(abort_at_step):
                eng.step()
            # the batch about to dispatch was staged by the previous
            # step's speculation — the abort discards exactly that batch
            assert eng._staged is not None, "pipeline never staged a batch"
            eng.arrival_poll = lambda: eng.flag.set()
            before = eng.dispatches["fused_segment"]
            eng.step()
            assert eng.safepoints.stats.preemptions == 1, "no abort happened"
            assert (
                eng.dispatches["fused_segment"] - before
                < tf.num_segments(cfg)
            ), "aborted iteration ran every segment"
            assert eng._staged is None, "abort path must not speculate"
            eng.arrival_poll = None
        eng.run()
        return [r.output_tokens for r in reqs]

    assert tf.num_segments(cfg) > 1, "config cannot express a mid-batch cut"
    assert _go(3) == _go(None), (
        "pipelined abort changed the emitted tokens"
    )


def _run_shared(arch, stagger=3, mesh=None, eng_kw=None):
    """Shared-prefix trace (DESIGN.md §14): request 0 carries the full
    32-token stem and is submitted first; after ``stagger`` steps (its stem
    blocks are committed to the content index) the rest arrive:

      * req 1 shares 24 stem tokens — diverges MID-block (block 1 of
        bs=16 is half stem, half private), so only block 0 is mapped;
      * req 2 IS the stem (prompt_len an exact block multiple): both
        blocks map, 31 tokens cached, and the recompute of the final
        prompt token fires copy-on-write in the shared tail block;
      * req 3 shares 24 tokens again (second hit on the same chain).

    Returns (tokens per request, engine) — callers compare tokens across
    legs and read the hit/COW counters."""
    cfg, params = _model(arch)
    eng = RealEngine(
        cfg, params,
        eng_cfg=RealEngineConfig(backend="paged", mesh=mesh, **(eng_kw or {})),
    )
    stem = (
        np.random.default_rng(777)
        .integers(0, cfg.vocab_size, 32)
        .astype(np.int32)
    )
    specs = [(40, 8, 32), (40, 8, 24), (32, 8, 32), (40, 6, 24)]
    reqs = []
    for seed, (plen, gen, share) in enumerate(specs):
        prompt = (
            np.random.default_rng(50 + seed)
            .integers(0, cfg.vocab_size, plen)
            .astype(np.int32)
        )
        prompt[:share] = stem[:share]
        reqs.append(
            Request(
                Priority.OFFLINE, prompt_len=plen, max_new_tokens=gen,
                prompt=prompt,
            )
        )
    eng.submit(reqs[0])
    for _ in range(stagger):
        eng.step()
    for r in reqs[1:]:
        eng.submit(r)
    eng.run()
    return [r.output_tokens for r in reqs], eng


@pytest.mark.parametrize("arch,jobs,preempt_step,eng_kw", CASES)
def test_prefix_cache_setting_is_token_invariant(arch, jobs, preempt_step,
                                                 eng_kw):
    """`prefix_cache=True` (the default every leg above already runs under)
    vs `prefix_cache=False` on the fused path, across the existing case
    axes — bucket crossings, preempt/resume, GQA/sharded-pool shapes.
    Sharing may rewire physical block indices but must never change a
    single emitted token."""
    out_on, on_on, _ = _run(arch, "paged", jobs, preempt_step, eng_kw=eng_kw)
    out_off, on_off, _ = _run(
        arch, "paged", jobs, preempt_step,
        eng_kw=dict(eng_kw, prefix_cache=False),
    )
    assert out_on == out_off, "prefix caching changed offline tokens"
    assert on_on == on_off, "prefix caching changed online tokens"


def test_shared_prefix_tokens_identical_across_legs():
    """The sharing-heavy trace (hits + mid-block divergence + COW) must
    emit byte-identical greedy tokens on every execution leg and with
    caching disabled — cached KV reuse and the COW copies are exact."""
    out_off, eng_off = _run_shared(
        "llama-2-7b", eng_kw=dict(prefix_cache=False)
    )
    out_s, eng_s = _run_shared("llama-2-7b", eng_kw=dict(fused_batch=False))
    out_f, eng_f = _run_shared("llama-2-7b")
    out_p, eng_p = _run_shared("llama-2-7b", eng_kw=dict(pipeline=True))
    out_m, _ = _run_shared("llama-2-7b", mesh=make_serving_mesh(_tp()))
    assert out_s == out_off, "split paged leg diverged under sharing"
    assert out_f == out_off, "fused leg diverged under sharing"
    assert out_p == out_off, "pipelined leg diverged under sharing"
    assert out_m == out_off, "sharded leg diverged under sharing"
    assert eng_off.blocks.prefix_hits == 0
    for eng in (eng_s, eng_f, eng_p):
        assert eng.blocks.prefix_hits == 3, "trace must hit the index 3x"
        assert eng.blocks.prefix_tokens_saved == 16 + 31 + 16
        assert eng.blocks.cow_copies >= 1, "block-aligned prompt must COW"
        assert eng.cow_dispatches >= 1, "COW never reached the device"


def test_shared_prefix_mid_iteration_abort_is_exact():
    """Safepoint abort landing on an iteration whose COW copies already
    ran on device: the aborted divergent writes sit in the exclusively
    owned copy and are rewritten verbatim on re-execution — tokens must
    not change, and the index must never have published aborted work
    (commit_prefix runs only on committed iterations)."""
    cfg, params = _model("llama-2-7b")

    def _go(abort):
        eng = RealEngine(cfg, params, eng_cfg=RealEngineConfig(backend="paged"))
        stem = (
            np.random.default_rng(777)
            .integers(0, cfg.vocab_size, 32)
            .astype(np.int32)
        )
        first = _mkreq(cfg, Priority.OFFLINE, 40, 8, 50)
        first.prompt[:32] = stem
        twin = Request(
            Priority.OFFLINE, prompt_len=32, max_new_tokens=8,
            prompt=stem.copy(),
        )
        eng.submit(first)
        for _ in range(3):
            eng.step()
        eng.submit(twin)
        if abort:
            # the next step plans the twin's COW + suffix chunk; abort it
            eng.arrival_poll = lambda: eng.flag.set()
            eng.step()
            assert eng.safepoints.stats.preemptions == 1, "no abort happened"
            eng.arrival_poll = None
        eng.run()
        assert eng.blocks.prefix_hits == 1
        return [first.output_tokens, twin.output_tokens]

    assert _go(True) == _go(False), "abort over a COW changed tokens"


def test_sharded_pool_is_actually_sharded():
    """On a multi-device mesh the MHA pool must shard its KV-head axis
    (the memory win tensor parallelism exists for), each shard holding
    Hkv/tp whole heads of the head-major page lanes; a 1-device mesh
    leaves it whole."""
    cfg, params = _model("llama-2-7b")
    tp = _tp()
    eng = RealEngine(
        cfg, params,
        eng_cfg=RealEngineConfig(backend="paged", mesh=make_serving_mesh(tp)),
    )
    spec = eng.pools["0"]["k"].sharding.spec
    if tp > 1:
        assert spec[3] == "model", spec
        shard = next(iter(eng.pools["0"]["k"].addressable_shards))
        assert shard.data.shape[3] == (
            cfg.num_kv_heads // tp * cfg.resolved_head_dim
        )
    else:
        assert all(s is None for s in spec)


def test_mesh_must_divide_kv_heads():
    """A model axis that does not divide the KV heads is refused when the
    engine is built, before anything is placed on a device."""
    from jax.sharding import AbstractMesh

    cfg, params = _model("qwen2-0.5b")  # 2 KV heads
    with pytest.raises(ValueError, match="do not divide"):
        RealEngine(
            cfg, params,
            eng_cfg=RealEngineConfig(
                mesh=AbstractMesh((1, 3), ("data", "model"))
            ),
        )


def test_mesh_requires_paged_backend():
    cfg, params = _model("llama-2-7b")
    with pytest.raises(ValueError):
        RealEngine(
            cfg, params,
            eng_cfg=RealEngineConfig(
                backend="contiguous", mesh=make_serving_mesh(1)
            ),
        )


def test_sharded_calibration_runs():
    """calibrate() on a mesh: probes replicate, timings cover the sharded
    dispatches, and the fitted profile installs as the scheduler's latency
    model (DESIGN.md §11 — calibration on a mesh)."""
    from repro.core.profiler import BatchShape, CalibrationGrid

    cfg, params = _model("llama-2-7b")
    eng = RealEngine(
        cfg, params,
        eng_cfg=RealEngineConfig(
            backend="paged", mesh=make_serving_mesh(_tp())
        ),
    )
    prof = eng.calibrate(
        CalibrationGrid(
            chunk_sizes=(8,), decode_buckets=(1, 2), ctx_fractions=(0.25,),
            repeats=1, swap_block_counts=(1,),
        )
    )
    assert eng.sched.model is prof
    t = prof.iter_time(BatchShape(decode_tokens=2, decode_ctx=64, num_seqs=2))
    assert t > 0.0
