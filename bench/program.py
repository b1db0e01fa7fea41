"""The system under test, as the benchmark drives it.

Everything that touches the program lives here or in a model family's
``program.py`` (``bench/family.py``): building the program's model
configuration and engine from a configuration file, handing it the weights
drawn by ``reference.draw`` in the program's own layout, warming up every
program the traffic can dispatch, and the hooks that record each
iteration's plan.  The rest of the benchmark reads only what this module
returns.
"""
from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp

from bench import family, reference

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def import_program() -> None:
    """Put the program's sources on the path; fail when they are absent."""
    src = os.path.join(CHECKOUT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"the program is not in this checkout ({src})")
    if src not in sys.path:
        sys.path.insert(0, src)


def _family(m: dict):
    return family.module(m["family"], "program")


def weights(cfg: dict, seed: int) -> dict:
    """``reference.draw``'s weights for the seed, in the program's layout,
    drawn on the device in one program."""
    m = reference.dims(cfg)
    fam = _family(m)
    return jax.jit(lambda key: fam.program_params(m, reference.draw(m, key)))(
        reference.seed_key(seed)
    )


def build_engine(cfg: dict, seed: int):
    """The engine under test: the program's own ``SchedulerConfig()`` (SLO
    aware), its engine defaults, and the pool and ``max_model_len`` of the
    configuration file; weights drawn on the device in one program."""
    from repro.core.scheduler import SchedulerConfig
    from repro.serving.real_engine import RealEngine, RealEngineConfig

    sv = cfg["serving"]
    m = reference.dims(cfg)
    return RealEngine(
        _family(m).model_config(cfg, m),
        weights(cfg, seed),
        sched_cfg=SchedulerConfig(slo_aware=True),
        eng_cfg=RealEngineConfig(
            max_model_len=sv["max_model_len"],
            block_size=sv["block_size"],
            num_device_blocks=sv["kv_blocks"],
            num_host_blocks=sv["host_kv_blocks"],
        ),
    )


def pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def token_cap(eng) -> int:
    """The most tokens one plan can hold: the larger of the offline
    (saturation) budget and the online (SLO) budget the scheduler sizes
    from its calibrated latency model."""
    from repro.core.budget import calc_budget

    sched, sc = eng.sched, eng.sched.sc
    online = calc_budget(
        sched.model, sched.slo, has_decode=True, avg_ctx=sc.avg_ctx_estimate,
        max_seqs=sc.max_batch_seqs, headroom=sc.budget_headroom,
        min_tokens=sc.chunk_size,
    ).max_total_tokens
    return max(sched._saturation_tokens(), online)


def fused_shapes(eng, max_seqs: int) -> list:
    """Every (tokens, sequences, longest query) bucket triple a plan can
    lower to, each with one composition of real lengths that lands in it:
    ``[(T, S, Q, [q_len, ...]), ...]``."""
    chunk = eng.sched.sc.chunk_size
    cap = token_cap(eng)
    out = []
    s = 1
    while s <= pow2(max_seqs):
        q = 1
        while q <= pow2(chunk):
            qmin = 1 if q == 1 else q // 2 + 1
            smin = 1 if s == 1 else s // 2 + 1
            t = pow2(qmin + smin - 1)
            while t <= pow2(min(cap, s * q)):
                lens = _compose(t, s, q, qmin, smin, min(q, chunk))
                if lens is not None:
                    out.append((t, s, q, lens))
                t *= 2
            q *= 2
        s *= 2
    return out


def _compose(t, s, q, qmin, smin, qreal):
    """Query lengths whose sum lands in bucket t, whose count lands in
    bucket s and whose longest lands in bucket q; None when no plan can."""
    tmin = 1 if t == 1 else t // 2 + 1
    for n in (smin, s):
        for top in (qreal, qmin):
            want = max(top + n - 1, tmin)
            if want <= min(top * n, t):
                rest, lens = want - top, [top]
                for i in range(n - 1):
                    take = min(top, rest - (n - 2 - i))
                    lens.append(take)
                    rest -= take
                return lens
    return None


def bucket(lens) -> tuple:
    """The fused program's (tokens, sequences, longest query) bucket
    triple for a plan of these query lengths."""
    return pow2(sum(lens)), pow2(len(lens)), pow2(max(lens))


def warm_up(eng, max_seqs: int, seen=(), log=None) -> dict:
    """Compile and run, once, every program the traffic can dispatch: each
    fused segment and logits program at every bucket triple of plans of up
    to ``max_seqs`` sequences and at the triple of each plan in ``seen``
    (query lengths of plans earlier runs of the cell met), the sampler's
    gathers at every row count, and the checkpoint gather and restore
    scatter at every block bucket.  Probes address only the scratch pool
    row, as calibration's do.  Returns counts."""
    from repro.models.sampling import sample

    scratch = eng._scratch_block
    key = jax.random.PRNGKey(0)
    shapes = fused_shapes(eng, max_seqs)
    known = {(t, s, q) for t, s, q, _l in shapes}
    for lens in seen:
        if bucket(lens) not in known:
            known.add(bucket(lens))
            shapes.append((*bucket(lens), list(lens)))
    rows_done = set()
    t_start = time.perf_counter()
    for i, (t, s, q, lens) in enumerate(shapes):
        if log is not None and i % 25 == 0:
            log(f"warm-up fused {i}/{len(shapes)} T={t} S={s} Q={q} "
                f"{time.perf_counter() - t_start:.1f}s")
        items = [(n, 0, None, None) for n in lens]
        logits, _ = eng._dispatch_fused(
            *eng._fused_inputs(eng._build_ragged(items)), preemptible=False
        )
        for n in range(1, s + 1):  # sampled rows: any count up to S
            if (s, n) in rows_done:
                continue
            rows_done.add((s, n))
            rows = jnp.asarray(list(range(n)))
            jax.block_until_ready(sample(logits[rows], eng.sampling, key))
    _key, _sub = jax.random.split(eng._key)  # the sampler's key split
    eng._t_last_enqueue = None  # probes are no iteration: no host gap
    eng._last_out = None
    if log is not None:
        log(f"warm-up fused done {time.perf_counter() - t_start:.1f}s")
    blocks_cap = pow2(token_cap(eng) // eng.ec.block_size + max_seqs)
    restore_cap = pow2(eng._table_width)
    n = 1
    while n <= blocks_cap:
        stored = eng._extract_blocks_paged([scratch] * n)
        if n <= restore_cap:
            eng._restore_blocks_paged([scratch] * n, stored)
        n *= 2
    jax.block_until_ready(eng.pools)
    return {"fused_shapes": len(shapes), "sampler_shapes": len(rows_done)}


class Recorder:
    """Wraps the engine's ``step`` and the scheduler's ``plan_iteration``
    (both run on the engine thread) to record, per iteration: host start
    and end, the plan's sequences, whether it aborted at a safepoint, and
    each request's progress.  Each call sits in a profiler span so the
    trace names what the host did in each device gap."""

    def __init__(self, eng, clock):
        self.eng = eng
        self.clock = clock
        self.steps = []  # (t0, t1, items, aborted) per executed iteration
        self.plan = None
        self.aborted_ids = set()
        self._hw = {}  # request id -> prompt tokens prefilled (high water)
        self._done = {}  # request id -> useful tokens counted so far
        self.progress = []  # (t1, request, tokens) useful work per step
        self.pool_used = []  # (t, device_utilization) after each step
        self.preempted = []  # (t, requests preempted by the plan)
        self.seen = {}  # bucket triple -> query lengths of a plan in it
        self.preempt_points = {}  # request id -> tokens served at each preemption
        self.hook = None  # called after each step on the engine thread
        self._plan = eng.sched.plan_iteration
        self._step = eng.step
        eng.sched.plan_iteration = self._wrap_plan
        eng.step = self._wrap_step

    def _wrap_plan(self, now):
        with jax.profiler.TraceAnnotation("bench.plan"):
            self.plan = self._plan(now)
        return self.plan

    def _wrap_step(self):
        eng = self.eng
        self.plan = None
        aborts0 = eng.safepoints.stats.preemptions
        steps0 = eng.steps
        t0 = self.clock()
        with jax.profiler.TraceAnnotation("bench.step"):
            alive = self._step()
        t1 = self.clock()
        plan = self.plan
        if plan is not None and plan.preempted:
            self.preempted.append((t1, len(plan.preempted)))
            for r in plan.preempted:  # tokens it had served when preempted
                self.preempt_points.setdefault(r.request_id, []).append(
                    r.num_generated)
        if eng.steps != steps0 and plan is not None and not plan.empty:
            aborted = eng.safepoints.stats.preemptions != aborts0
            items = [
                (c.length, c.offset, c.request.is_online,
                 c.offset + c.length == c.request.prompt_len)
                for c in plan.prefill_chunks
            ] + [(1, r.total_len - 1 - (0 if aborted else 1), r.is_online,
                  True) for r in plan.decode_reqs]
            self.steps.append((t0, t1, items, aborted))
            lens = [it[0] for it in items]
            self.seen.setdefault(bucket(lens), lens)
            reqs = [c.request for c in plan.prefill_chunks] + list(
                plan.decode_reqs
            )
            if aborted:
                self.aborted_ids.update(r.request_id for r in reqs)
            for r in reqs:
                hw = max(self._hw.get(r.request_id, 0),
                         min(r.num_prefilled, r.prompt_len))
                self._hw[r.request_id] = hw
                done = hw + r.num_generated
                new = done - self._done.get(r.request_id, 0)
                if new > 0:
                    self._done[r.request_id] = done
                    self.progress.append((t1, r, new))
            self.pool_used.append((t1, eng.blocks.device_utilization))
        if self.hook is not None:
            self.hook()
        return alive
