"""Paged execution backend (shared block pool + block-table attention):

 * backend capability matrix (paged for plain causal KV, fallback otherwise)
 * pool-ops roundtrips (chunked scatter, per-block extract/restore)
 * token parity: paged engine vs contiguous engine, uninterrupted
 * token identity on the paged pool under forced preemption + IC restore,
   and under blocking swap-out preemption
 * decode jit recompilation bounded by the batch-bucket count (split path)
 * fused-path jit recompilation bounded by the ragged bucket triple
   (DESIGN.md §12) and one dispatch per K-layer segment per iteration
 * pipelined-engine twin of the fused retrace guard (DESIGN.md §13):
   pinned fused/pipeline trace counts plus monotone host-gap counters
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.request import Priority, Request
from repro.core.scheduler import SchedulerConfig
from repro.kvcache import cache_ops
from repro.models import transformer as tf
from repro.serving.real_engine import RealEngine, RealEngineConfig

CFG = get_config("llama-2-7b").reduced()
PARAMS = tf.init_params(CFG, jax.random.PRNGKey(0))


def mkreq(prio, plen, gen, seed):
    prompt = (
        np.random.default_rng(seed)
        .integers(0, CFG.vocab_size, plen)
        .astype(np.int32)
    )
    return Request(prio, prompt_len=plen, max_new_tokens=gen, prompt=prompt)


def _run(backend, gens=(24, 24, 24), eng_kw=None, sched=None, disturb=False):
    eng = RealEngine(
        CFG, PARAMS,
        sched_cfg=sched,
        eng_cfg=RealEngineConfig(backend=backend, **(eng_kw or {})),
    )
    reqs = [mkreq(Priority.OFFLINE, 40, g, s) for s, g in enumerate(gens)]
    for r in reqs:
        eng.submit(r)
    if disturb:
        for _ in range(8):
            eng.step()
        for s in range(2):
            eng.on_online_arrival(mkreq(Priority.ONLINE, 60, 8, 100 + s))
    eng.run()
    return eng, [r.output_tokens for r in reqs], reqs


# --------------------------------------------------------------- capability


def test_backend_capability_matrix():
    assert tf.supports_paged(get_config("llama-2-7b").reduced())
    assert tf.supports_paged(get_config("olmoe-1b-7b").reduced())
    assert not tf.supports_paged(get_config("mamba2-1.3b").reduced())
    assert not tf.supports_paged(get_config("mixtral-8x22b").reduced())  # SWA
    assert not tf.supports_paged(get_config("llama-3.2-vision-11b").reduced())
    assert not tf.supports_paged(get_config("hubert-xlarge").reduced())


def test_forcing_paged_on_unsupported_arch_raises():
    cfg = get_config("mamba2-1.3b").reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(1))
    with pytest.raises(ValueError):
        RealEngine(cfg, params, eng_cfg=RealEngineConfig(backend="paged"))


def test_fallback_engine_has_no_pools():
    cfg = get_config("mamba2-1.3b").reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(1))
    eng = RealEngine(cfg, params)
    assert not eng.paged and not hasattr(eng, "pools")


# ----------------------------------------------------------------- pool ops


def test_write_paged_chunk_matches_append_order():
    """Multi-token scatter lands tokens exactly where one-at-a-time appends
    would."""
    key = jax.random.PRNGKey(2)
    bs, nblk, hkv, d = 4, 8, 2, 16
    k_pool = jnp.zeros((nblk, bs, hkv * d))
    v_pool = jnp.zeros((nblk, bs, hkv * d))
    tables = jnp.array([[5, 2, 7, -1], [1, 6, -1, -1]], jnp.int32)
    k_new = jax.random.normal(key, (2, 6, hkv, d))
    v_new = jax.random.normal(jax.random.fold_in(key, 1), (2, 6, hkv, d))
    offsets = jnp.array([3, 0], jnp.int32)  # seq0 appends at 3.., seq1 at 0..
    positions = offsets[:, None] + jnp.arange(6)[None, :]
    kc, vc = cache_ops.write_paged_chunk(
        k_pool, v_pool, k_new, v_new, tables, positions
    )
    ka, va = k_pool, v_pool
    for t in range(6):
        ka, va = cache_ops.append_paged(
            ka, va, k_new[:, t], v_new[:, t], tables, offsets + t
        )
    assert jnp.array_equal(kc, ka) and jnp.array_equal(vc, va)


def test_scatter_drops_writes_through_padding():
    """Writes addressed through -1 table entries (or beyond the table) must
    be dropped, never aliased onto a real block."""
    k_pool = jnp.zeros((4, 2, 4))
    v_pool = jnp.zeros((4, 2, 4))
    tables = jnp.array([[2, -1]], jnp.int32)
    ones = jnp.ones((1, 1, 1, 4))
    # token at position 3 -> padded column 1 -> dropped
    kc, vc = cache_ops.write_paged_chunk(
        k_pool, v_pool, ones, ones, tables, jnp.array([[3]], jnp.int32)
    )
    assert float(jnp.max(jnp.abs(kc))) == 0.0
    # decode append through a -1 column likewise drops
    ka, va = cache_ops.append_paged(
        k_pool, v_pool, ones[:, 0], ones[:, 0], tables,
        jnp.array([2], jnp.int32),
    )
    assert float(jnp.max(jnp.abs(ka))) == 0.0
    # position 5 is beyond the 2-wide table entirely -> dropped
    kc, _ = cache_ops.write_paged_chunk(
        k_pool, v_pool, ones, ones, tables, jnp.array([[5]], jnp.int32)
    )
    assert float(jnp.max(jnp.abs(kc))) == 0.0


def test_max_model_len_not_multiple_of_block_size():
    """Table width must cover ceil(max_model_len / block_size) blocks.
    (8-token gens: the 40+8 = 48-token sequences span 3 blocks, plenty to
    catch a floored width, at a fraction of the default-config runtime.)"""
    _, ref, _ = _run("paged", gens=(8, 8, 8))
    eng, out, _ = _run("paged", gens=(8, 8, 8), eng_kw=dict(max_model_len=100))
    assert eng._table_width == 7  # ceil(100/16), not floor
    assert out == ref


def test_extract_write_block_roundtrip():
    pool = jax.random.normal(jax.random.PRNGKey(3), (8, 2, 64))
    blk = cache_ops.extract_block(pool, 5)
    wiped = cache_ops.write_block(pool, 5, jnp.zeros_like(blk))
    assert float(jnp.max(jnp.abs(wiped[5]))) == 0.0
    restored = cache_ops.write_block(wiped, 5, blk)
    assert jnp.array_equal(restored, pool)


def test_paged_attention_ref_softcap():
    """Pallas kernel (interpret) matches the oracle with logit softcapping."""
    from repro.kernels.paged_attention import paged_attention

    key = jax.random.PRNGKey(4)
    q = jax.random.normal(key, (2, 4, 32))
    kp = jax.random.normal(jax.random.fold_in(key, 1), (8, 8, 2 * 32))
    vp = jax.random.normal(jax.random.fold_in(key, 2), (8, 8, 2 * 32))
    tables = jnp.array([[0, 3, 6], [1, 4, -1]], jnp.int32)
    lens = jnp.array([20, 11], jnp.int32)
    out = paged_attention(
        q, kp, vp, tables, lens, logit_softcap=30.0, interpret=True
    )
    want = cache_ops.paged_attention_ref(
        q, kp, vp, tables, lens, logit_softcap=30.0
    )
    assert float(jnp.max(jnp.abs(out - want))) < 2e-5


# ------------------------------------------------------------ engine parity


@pytest.mark.slow  # test_backend_differential covers this with smaller gens
def test_paged_matches_contiguous_uninterrupted():
    _, out_paged, _ = _run("paged")
    _, out_contig, _ = _run("contiguous")
    assert out_paged == out_contig


@pytest.mark.slow  # test_backend_differential covers preempt+restore fast
def test_paged_token_identity_under_forced_preemption():
    """The acceptance property: forced preemption + incremental-checkpoint
    restore on the shared pool emits byte-identical greedy tokens."""
    eng0, ref, _ = _run("paged")
    eng, out, reqs = _run(
        "paged",
        eng_kw=dict(num_device_blocks=14, max_model_len=256),
        disturb=True,
    )
    assert sum(r.num_preemptions for r in reqs) > 0, "scenario must preempt"
    assert out == ref
    assert eng.ckpt.stats.blocks_checkpointed > 0
    # preempted pool state restored via O(block) physical copies, never a
    # per-request cache dict
    assert not hasattr(eng, "caches")


@pytest.mark.slow
def test_paged_token_identity_under_swap_preemption():
    """Blocking swap-out preemption (PREEMPTSCHEDULING ablation) moves whole
    physical blocks — including the partial tail — through the host store."""
    _, ref, _ = _run("paged")
    sched = SchedulerConfig(
        chunk_size=32, slo_aware=False, offline_batch_tokens=4096,
        swap_on_preempt=True,
    )
    eng, out, reqs = _run(
        "paged",
        eng_kw=dict(num_device_blocks=14, max_model_len=256,
                    enable_checkpointing=False),
        sched=sched,
        disturb=True,
    )
    assert sum(r.num_preemptions for r in reqs) > 0, "scenario must preempt"
    assert out == ref


# -------------------------------------------------------- bounded recompiles


def test_decode_recompiles_bounded_by_buckets():
    """Batch sizes 5,4,3,2,1 appear as requests drain; bucketed padding must
    trace at most the 4 distinct buckets {8,4,2,1}, not all 5 sizes.
    (Split path: the fused path never dispatches the decode program.)"""
    gens = (4, 6, 8, 10, 12)
    eng, outs, _ = _run(
        "paged", gens=gens,
        eng_kw=dict(enable_safepoints=False, fused_batch=False),
    )
    assert [len(o) for o in outs] == list(gens)
    buckets = {RealEngine._decode_bucket(n) for n in range(1, len(gens) + 1)}
    assert 0 < eng.decode_trace_count <= len(buckets) < len(gens)


def test_retrace_regression_guard_mixed_onoff_drain():
    """Regression guard for the §9 bounded-recompile invariant: a fixed
    draining mixed ON/OFF workload (5 offline requests with staggered gens
    and mixed prompt-length buckets, plus a 3-request online burst) must
    keep jit retraces at the documented bucket-bound values —
    3 decode traces and 3 prefill traces on this trace today, and never
    more than the bucket-count ceilings (decode: |{1,2,4,8}| = 4; prefill:
    batch buckets {1,2,4,8} × length buckets {8,16,32} = 12).  Scheduling
    is wall-clock-independent with ``slo_aware=False``, so the counts are
    deterministic; a future dispatch change that reintroduces per-shape
    recompiles fails this loudly instead of silently regressing serving.
    """
    eng = RealEngine(
        CFG, PARAMS,
        eng_cfg=RealEngineConfig(
            backend="paged", enable_safepoints=False, fused_batch=False
        ),
    )
    gens = (4, 6, 8, 10, 12)
    plens = (40, 24, 40, 10, 40)
    for s, (p, g) in enumerate(zip(plens, gens)):
        eng.submit(mkreq(Priority.OFFLINE, p, g, s))
    for _ in range(4):
        eng.step()
    for s in range(3):
        eng.on_online_arrival(mkreq(Priority.ONLINE, 60, 8, 100 + s))
    eng.run()
    assert eng.decode_trace_count == 3, (
        f"decode retraces changed: {eng.decode_trace_count} (was 3); "
        "did a dispatch change break batch bucketing?"
    )
    assert eng.prefill_trace_count == 3, (
        f"prefill retraces changed: {eng.prefill_trace_count} (was 3); "
        "did a dispatch change break (batch x length) bucketing?"
    )


def test_run_tokens_paged_matches_segmented_composition():
    """The whole-stack fused entry (`run_tokens_paged`) must equal the
    engine's segmented composition (embed -> run_tokens_paged_at per
    segment -> ragged_lm_head) bitwise, logits and pools — the invariant
    that makes host-side safepoint cuts free of numerical consequence."""
    eng = RealEngine(CFG, PARAMS, eng_cfg=RealEngineConfig(backend="paged"))
    eng.blocks.register_seq(1)
    eng.blocks.grow(1, 8)
    eng.blocks.register_seq(2)
    eng.blocks.grow(2, 6)
    items = [
        (8, 0, np.arange(8, dtype=np.int32), eng._block_table(1)),
        (1, 5, np.array([3], np.int32), eng._block_table(2)),
    ]
    toks, tables, positions, meta, li = eng._fused_inputs(
        eng._build_ragged(items)
    )
    logits_full, pools_full = tf.run_tokens_paged(
        CFG, PARAMS, toks, eng.pools, tables, positions[0], meta, li
    )
    x = tf.embed(CFG, PARAMS, toks[None])
    pools_seg = eng.pools
    for lo, pps in tf.segment_spans(CFG):
        x, pools_seg = tf.run_tokens_paged_at(
            CFG, PARAMS, pps, jnp.int32(lo), x, pools_seg, tables,
            positions, meta,
        )
    logits_seg = tf.ragged_lm_head(CFG, PARAMS, x, li)
    assert jnp.array_equal(logits_full, logits_seg)
    assert all(
        jnp.array_equal(a, b)
        for a, b in zip(jax.tree.leaves(pools_full), jax.tree.leaves(pools_seg))
    )


def test_fused_mixed_iteration_is_one_dispatch_per_segment():
    """The §12 acceptance property, stated directly: an iteration
    co-serving >=1 ONLINE decode with >=1 OFFLINE prefill chunk executes
    as exactly one device dispatch per K-layer segment (plus the one
    logits program) — no separate prefill/decode dispatch families."""
    eng = RealEngine(
        CFG, PARAMS,
        eng_cfg=RealEngineConfig(backend="paged", enable_safepoints=False),
    )
    # get an online request into the decode phase first
    online = mkreq(Priority.ONLINE, 40, 8, 0)
    eng.submit(online)
    for _ in range(3):
        eng.step()
    assert online.num_generated >= 1, "online request must be decoding"
    # now co-serve: an offline prompt joins as prefill chunks
    offline = mkreq(Priority.OFFLINE, 40, 4, 1)
    eng.submit(offline)
    before = dict(eng.dispatches)
    gen0 = online.num_generated
    eng.step()
    from repro.models import transformer as tf

    assert online.num_generated == gen0 + 1, "online decode did not advance"
    assert offline.num_prefilled > 0, "offline chunk was not co-served"
    delta = {k: eng.dispatches[k] - before[k] for k in eng.dispatches}
    assert delta == {
        "prefill": 0, "decode": 0, "segment": 0,
        "fused_segment": tf.num_segments(CFG), "fused_logits": 1,
    }, delta


def test_fused_retrace_regression_guard_mixed_onoff_drain():
    """The fused-path twin of the guard above (DESIGN.md §12): the same
    fixed draining mixed ON/OFF workload must keep fused-segment jit
    retraces at the documented value — the trace key is the ragged bucket
    triple (token bucket T, sequence bucket S, query-length bucket Qmax)
    times the distinct segment lengths, NOT one program per iteration
    shape.  On this trace the engine compiles 5 programs today; the hard
    ceiling is |T buckets reachable| x |S buckets| x |Qmax buckets| x
    |segment lengths| — far below the ~20 distinct iteration shapes the
    drain produces.  Also asserts the fusion contract itself: every
    iteration executed exactly one dispatch per K-layer segment and the
    split-path programs never ran.
    """
    eng = RealEngine(
        CFG, PARAMS,
        eng_cfg=RealEngineConfig(backend="paged", enable_safepoints=False),
    )
    gens = (4, 6, 8, 10, 12)
    plens = (40, 24, 40, 10, 40)
    for s, (p, g) in enumerate(zip(plens, gens)):
        eng.submit(mkreq(Priority.OFFLINE, p, g, s))
    for _ in range(4):
        eng.step()
    for s in range(3):
        eng.on_online_arrival(mkreq(Priority.ONLINE, 60, 8, 100 + s))
    eng.run()
    from repro.models import transformer as tf

    assert eng.dispatches["fused_segment"] == eng.steps * tf.num_segments(
        CFG
    ), "an iteration did not execute as one dispatch per K-layer segment"
    assert eng.dispatches["fused_logits"] == eng.steps
    assert eng.dispatches["prefill"] == eng.dispatches["decode"] == 0, (
        "fused engine dispatched a split-path program"
    )
    assert eng.fused_trace_count == 5, (
        f"fused retraces changed: {eng.fused_trace_count} (was 5); "
        "did a dispatch change break (token x seq x qlen) bucketing?"
    )


def test_pipelined_retrace_regression_guard_mixed_onoff_drain():
    """Pipelined twin of the fused drain guard (DESIGN.md §13): the same
    workload on the async-pipeline engine must keep the per-segment
    program's retraces pinned (same ragged bucket triple as the serial
    fused path — speculation and deferred-token injection must not leak
    new trace keys) and the pipeline's own programs (sample_rows /
    inject_sampled) bounded by their row buckets.  Also asserts the
    fusion contract under pipelining — one donated per-slice dispatch
    per K-layer segment per iteration, split paths never run — and that
    the host-gap counters are monotone and mutually consistent."""
    eng = RealEngine(
        CFG, PARAMS,
        eng_cfg=RealEngineConfig(
            backend="paged", enable_safepoints=False, pipeline=True
        ),
    )
    gens = (4, 6, 8, 10, 12)
    plens = (40, 24, 40, 10, 40)
    for s, (p, g) in enumerate(zip(plens, gens)):
        eng.submit(mkreq(Priority.OFFLINE, p, g, s))
    for _ in range(4):
        eng.step()
    gap_count_mid = eng.host_gap.count
    gap_seconds_mid = eng.host_gap.sum
    for s in range(3):
        eng.on_online_arrival(mkreq(Priority.ONLINE, 60, 8, 100 + s))
    eng.run()
    from repro.models import transformer as tf

    assert eng.dispatches["fused_segment"] == eng.steps * tf.num_segments(
        CFG
    ), "an iteration did not execute as one dispatch per K-layer segment"
    assert eng.dispatches["fused_logits"] == eng.steps
    assert eng.dispatches["prefill"] == eng.dispatches["decode"] == 0, (
        "pipelined engine dispatched a split-path program"
    )
    assert eng.fused_trace_count == 5, (
        f"pipelined fused retraces changed: {eng.fused_trace_count} "
        "(was 5); did speculation leak new (token x seq x qlen) keys?"
    )
    assert eng.pipeline_trace_count == 8, (
        f"pipeline program retraces changed: {eng.pipeline_trace_count} "
        "(was 8); did sample-row / injection bucketing break?"
    )
    # host-gap instrumentation: count and sum are monotone (never reset)
    # and stay consistent with the histogram's buckets: every sample is
    # >= 0 and lies in its bucket, so the sum is bracketed by the bounds
    gap = eng.host_gap
    assert gap.count >= gap_count_mid
    assert gap.sum >= gap_seconds_mid
    assert gap.count == sum(gap.counts)
    lo = (0.0,) + gap.bounds
    hi = gap.bounds + (float("inf"),)
    assert sum(c * b for c, b in zip(gap.counts, lo)) <= gap.sum + 1e-12
    assert gap.sum <= sum(c * b for c, b in zip(gap.counts, hi) if c)


def test_prefix_retrace_regression_guard_shared_drain():
    """Sharing must not leak trace keys (DESIGN.md §14): a draining mixed
    ON/OFF workload whose offline prompts share a 32-token stem — so the
    drain takes prefix hits, a mid-block divergence, and a copy-on-write —
    keeps the fused-segment retraces inside the same ragged-bucket family
    as the unshared drains above (sharing rewires block-table *indices*,
    never batch shapes).  The COW copies compile their own bucketed
    program, counted by ``cow_trace_count`` and dispatched outside
    ``eng.dispatches`` (the §12 exact-delta contract stays intact).  Also
    checks counter consistency: with safepoints off (no speculative
    rollback), every token the index served is attributed to exactly one
    request — sum(r.prefix_cached) == blocks.prefix_tokens_saved."""
    eng = RealEngine(
        CFG, PARAMS,
        eng_cfg=RealEngineConfig(backend="paged", enable_safepoints=False),
    )
    stem = (
        np.random.default_rng(7)
        .integers(0, CFG.vocab_size, 32)
        .astype(np.int32)
    )
    # (prompt_len, max_new, shared stem tokens): req 2 IS the stem (exact
    # block multiple -> COW on the final prompt token); the rest diverge
    # mid-block or at the boundary
    specs = [(40, 4, 32), (40, 6, 24), (32, 8, 32), (40, 10, 24),
             (24, 12, 16)]
    reqs = []
    for seed, (plen, gen, share) in enumerate(specs):
        prompt = (
            np.random.default_rng(seed)
            .integers(0, CFG.vocab_size, plen)
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
    for _ in range(2):  # commit req 0's stem blocks into the index
        eng.step()
    for r in reqs[1:]:
        eng.submit(r)
    for _ in range(2):
        eng.step()
    for s in range(3):
        eng.on_online_arrival(mkreq(Priority.ONLINE, 60, 8, 100 + s))
    eng.run()
    # the trace actually exercised sharing
    assert eng.blocks.prefix_hits == 4, "shared drain must hit the index 4x"
    assert eng.blocks.cow_copies >= 1, "block-aligned twin never COWed"
    assert eng.cow_dispatches >= 1
    # fused retraces stay bucket-bounded; sharing adds no per-shape keys.
    # This exact trace with prefix_cache=False compiles 6 programs; the
    # on leg compiles 7 because skipping cached tokens legitimately moves
    # one chunk into a different qlen bucket — still far below the 14
    # iteration shapes the drain produces (a per-shape leak would pin
    # fused_trace_count to eng.steps)
    assert eng.fused_trace_count == 7, (
        f"fused retraces changed under sharing: {eng.fused_trace_count} "
        "(was 7); did prefix mapping leak per-shape trace keys?"
    )
    assert eng.fused_trace_count < eng.steps
    assert eng.cow_trace_count == 1, (
        f"COW retraces changed: {eng.cow_trace_count} (was 1); "
        "did the pow2 pair-list bucketing break?"
    )
    # split-path programs never ran; fusion contract intact
    assert eng.dispatches["prefill"] == eng.dispatches["decode"] == 0
    assert eng.dispatches["fused_segment"] == eng.steps * tf.num_segments(
        CFG
    )
    # attribution: every index-served token belongs to exactly one request
    assert (
        sum(r.prefix_cached for r in reqs)
        == eng.blocks.prefix_tokens_saved
    ), "prefix_tokens_saved disagrees with per-request attribution"
    assert all(len(r.output_tokens) == g for r, (_, g, _s) in
               zip(reqs, specs))


def test_fused_segment_takes_weights_as_arguments():
    """The fused segment program receives the weights as arguments and
    bakes none of them in as constants (a closed-over array would become a
    constant of every compiled bucket — gigabytes at published widths)."""
    import re

    cfg = get_config("qwen2-0.5b").reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    eng = RealEngine(cfg, params, eng_cfg=RealEngineConfig(backend="paged"))
    toks, tables, positions, meta, _li = eng._fused_inputs(
        eng._build_ragged([(4, 0, None, None), (1, 7, None, None)])
    )
    lo, pps = tf.segment_spans(cfg)[0]
    x = tf.embed(cfg, params, toks[None])
    lowered = eng._fused_segment_jit.lower(
        pps, eng.params, np.int32(lo), x, eng.pools, tables, positions, meta
    )
    weights = jax.tree.leaves(lowered.args_info[0][0])  # pps is static
    assert len(weights) == len(jax.tree.leaves(params))
    const_sizes = [
        int(np.prod([int(d) for d in dims.split("x")]))
        for dims in re.findall(
            r"stablehlo\.constant dense<[^>]*>\s*:\s*tensor<((?:\d+x)+)\w+>",
            lowered.as_text(),
        )
        for dims in [dims.rstrip("x")]
    ]
    # the smallest weight is a d_model-wide norm vector
    assert max(const_sizes, default=0) < cfg.d_model, const_sizes
