"""Real-execution co-serving engine: the same ConServe policies
(UnifiedScheduler / Checkpointer / safepoints) driving ACTUAL JAX compute.

This is the engine the integration tests and examples run on CPU with
reduced models; on TPU the identical code path serves the production
configs.  Key correctness property it exists to prove: a run with forced
preemptions + incremental-checkpoint restores emits *byte-identical* tokens
to an uninterrupted run (greedy sampling) — checkpoint/restore and the
recompute path are exact.

Implementation notes:
* Physical KV layout is the *paged* shared pool (DESIGN.md §5): per-layer
  pools ``(num_device_blocks+1, block_size, Hkv·D)`` addressed via block
  tables built from the BlockManager's physical block ids; attention
  dispatches to the Pallas ``ragged_paged_attention`` kernel on TPU and the
  ``cache_ops`` jnp oracle on CPU.  The last pool row is a scratch block that absorbs writes
  from padded batch rows.
* Fused mixed-batch execution (DESIGN.md §12, the default paged hot
  path, ``RealEngineConfig.fused_batch``): the whole ``IterationPlan`` —
  online decodes plus offline prefill chunks — lowers to ONE flattened
  ragged token batch (``_build_ragged``) and executes as a single
  ``run_tokens_paged_at`` dispatch per K-layer segment, each layer doing
  one fused KV-pool scatter and one ragged paged-attention op on the
  donated pool in place; decode is
  the ``q_len = 1`` degenerate case, not a separate dispatch family.
  ``fused_batch=False`` keeps the split per-family paths below as the
  differential oracle.
* Every jitted entry point runs at bucketed shapes so recompilation is
  bounded by the bucket count, not by workload variety (DESIGN.md §9;
  one shared primitive, ``core.budget.pow2_bucket``): the fused path is
  keyed on the (token, sequence, query-length) bucket triple
  (``fused_trace_count``); on the split paths decode batches pad to
  power-of-two buckets (``decode_trace_count``) and prefill chunks are
  grouped by power-of-two padded length and dispatched as batched
  ``prefill_chunk_paged`` calls capped at ``max_prefill_batch``
  (``prefill_trace_count``); checkpoint extract / resume restore pad
  their block-id lists to buckets; segmented programs use a traced start
  (``run_segment_paged_at`` / ``run_tokens_paged_at``) shared by all
  equal-length segments.
* Incremental checkpointing copies completed blocks out of the pool by
  physical id into a ``HostKVStore`` (O(block), no pytree slicing); restore
  scatters them back into whatever physical blocks the resume re-allocated.
  Preemption-by-discard therefore costs zero device I/O — pure table edits.
* Archs without plain causal KV (SSM/hybrid, sliding-window ring, cross-attn
  VLM, encoder-only) fall back to the contiguous per-request layout
  (capacity = max_model_len) with full-recompute resume (DESIGN.md §4).
* Tensor parallelism (DESIGN.md §11): ``RealEngineConfig.mesh`` runs the
  paged backend sharded over the mesh's ``model`` axis — pools and
  attention shard over KV heads (``distributed.sharding.pool_pspec``),
  params / tables / token ids replicate, and the attention output is
  gathered before the output projection so no contraction runs over a
  sharded dim.  Sharded serving therefore emits bitwise-identical greedy
  tokens (asserted by ``tests/test_backend_differential.py``); a 1-device
  mesh is behaviorally identical to ``mesh=None``.
* Safepoints: every dispatch boundary of a pure-offline iteration —
  between the fused path's K-layer segments (prefill and decode tokens
  alike; KV writes are positional and idempotent on the paged layout),
  or on the split paths between decode segments
  (``core.preemption.SegmentedExecution``) and batched-prefill groups
  — checks the preemption flag.  The optional
  ``arrival_poll`` hook runs at every safepoint so the wall-clock runtime
  (``serving.runtime``, DESIGN.md §10) can drain API-thread arrivals and let
  Algorithm 2 abort the batch mid-iteration.
* Admission: requests whose ``prompt_len + max_new_tokens`` exceed
  ``max_model_len`` are rejected with ``core.scheduler.AdmissionError`` at
  submit time, before any KV block is allocated.
* Calibration: ``calibrate()`` times the engine's own jitted prefill/decode
  entry points (the chunk sizes and power-of-two decode buckets it really
  traces) and swaps the scheduler's latency model for the fitted
  ``MeasuredProfiler`` so SLO token budgets reflect measured wall time.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.budget import pow2_bucket
from repro.core.checkpoint import (
    AdaptiveCheckpointPolicy,
    Checkpointer,
    HostKVStore,
)
from repro.core.preemption import PreemptionFlag, SegmentedExecution
from repro.core.profiler import (
    AnalyticalCostModel,
    BatchShape,
    CalibrationGrid,
    MeasuredProfiler,
    block_bytes,
    calibrate,
    hardware_for,
)
from repro.core.request import Request
from repro.core.faults import InjectedFault, RequestFailed
from repro.core.scheduler import SchedulerConfig, UnifiedScheduler
from repro.core.slo import SLO
from repro.kvcache import cache_ops
from repro.kvcache.block_manager import BlockManager
from repro.models import transformer as tf
from repro.models.config import ModelConfig
from repro.models.sampling import SamplingParams, sample, sample_rows
from repro.serving.metrics import (
    HOST_GAP_BOUNDS,
    SPAN_BUILD,
    SPAN_CHECKPOINT,
    SPAN_COMMIT,
    SPAN_COW,
    SPAN_DISPATCH,
    SPAN_EVENTS,
    SPAN_PLAN,
    SPAN_PUT,
    SPAN_RESTORE,
    SPAN_SAMPLE,
    SPAN_STEP,
    SPAN_SWAP_OUT,
    Histogram,
)


@dataclass
class RealEngineConfig:
    max_model_len: int = 256
    block_size: int = 16
    num_device_blocks: int = 256
    num_host_blocks: int = 1024
    enable_checkpointing: bool = True
    enable_safepoints: bool = True
    max_steps: int = 100_000
    # "auto": paged when the arch supports it; "paged"/"contiguous" force.
    backend: str = "auto"
    # largest batched-prefill dispatch (a bigger prefill wave is split into
    # several dispatches, each boundary a safepoint of pure-offline plans)
    # — split path only; the fused path has no per-dispatch batch cap
    max_prefill_batch: int = 8
    # Fused mixed-batch execution (DESIGN.md §12): lower the whole
    # IterationPlan — prefill chunks + decode tokens — to ONE flattened
    # ragged token batch and execute it as a single dispatch per K-layer
    # segment.  False falls back to the split per-family dispatches
    # (_prefill_paged_batched then _decode_paged), kept as the
    # differential oracle.  Paged backend only; ignored on the
    # contiguous fallback.
    fused_batch: bool = True
    # Async host/device pipeline (DESIGN.md §13), fused paged backend only:
    # while iteration N's K-layer segments run on device, the host
    # speculatively plans and builds iteration N+1 (double-buffered ragged
    # inputs, deferred-token injection, async sampled-token readback), so
    # the next dispatch launches with near-zero host gap.  An arrival
    # invalidates the staged batch — it is rolled back and replanned — and
    # a safepoint abort simply discards it with the aborted iteration, so
    # Algorithm 2 semantics and bitwise token identity are preserved.  Off
    # by default: the serial fused path is the differential oracle for it.
    pipeline: bool = False
    # Tensor-parallel serving mesh (jax.sharding.Mesh with a "model" axis;
    # see launch.mesh.make_serving_mesh).  Paged backend only: the shared
    # pools shard over KV heads, everything host-side stays mesh-oblivious
    # (DESIGN.md §11).  None = plain single-device execution.
    mesh: Optional[Any] = None
    # Shared-prefix KV caching with copy-on-write block sharing
    # (DESIGN.md §14), paged backend only: requests whose prompts share a
    # full-block prefix with earlier committed work map existing pool
    # blocks instead of re-prefilling them; the first divergent write
    # duplicates the shared block on device (an O(block) copy).  Greedy
    # tokens are bitwise identical either way — the differential harness
    # runs both settings.  Ignored on the contiguous fallback.
    prefix_cache: bool = True
    # Deterministic fault injection (core.faults.FaultInjector, DESIGN.md
    # §16): armed at named points in the engine/block-manager hot paths.
    # None (the default) keeps the fault-free path untouched — no extra
    # snapshots, no traced programs, no overhead.
    faults: Optional[Any] = None


class _PendingFetch:
    """One iteration's sampled tokens, in flight from device to host
    (DESIGN.md §13).

    ``arr`` is the padded ``(B,)`` device buffer produced by the jitted
    ``sample_rows`` program; ``reqs`` the requests in sampler order.  The
    constructor starts a non-blocking readback, so by the time ``resolve``
    runs (next step's post-work, or a pipeline flush) the bytes are
    usually already on host.  ``resolve`` backfills
    ``Request.output_tokens`` — the structural commit already *counted*
    these tokens via ``record_token(..., None)``, it just didn't know
    their values yet."""

    __slots__ = ("arr", "reqs")

    def __init__(self, arr, reqs):
        self.arr = arr
        self.reqs = list(reqs)
        try:
            arr.copy_to_host_async()
        except Exception:  # backends without async readback: resolve() blocks
            pass

    def resolve(self) -> None:
        vals = np.asarray(self.arr)
        for i, r in enumerate(self.reqs):
            r.output_tokens.append(int(vals[i]))


@dataclass
class _StagedBatch:
    """A speculatively planned+built iteration awaiting dispatch (§13).

    ``snap`` rolls the scheduler back if ``gen`` goes stale (an arrival
    landed after staging) or the plan is otherwise discarded before
    dispatch; the device-placed ``inputs`` are simply dropped — their
    enqueued transfers/injections write nothing any committed program
    reads."""

    plan: Any
    snap: Any
    gen: int
    samplers: List[tuple]
    inputs: tuple


def _plan_stats(plan) -> Dict[str, int]:
    """A plan's counts, as span stats."""
    reqs = [c.request for c in plan.prefill_chunks] + list(plan.decode_reqs)
    online = sum(1 for r in reqs if r.is_online)
    return {
        "sequences": len(reqs),
        "tokens": plan.shape.total_tokens,
        "online": online,
        "offline": len(reqs) - online,
        "preempted": len(plan.preempted),
    }


def _ckpt_stats(chosen) -> Dict[str, Any]:
    """Blocks and request ids of one checkpoint copy, as span stats (ids
    space-separated: the profiler splits stats at commas)."""
    ids = sorted({c[0] for c in chosen})
    return {"blocks": len(chosen), "requests": " ".join(map(str, ids))}


class RealEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        sched_cfg: Optional[SchedulerConfig] = None,
        eng_cfg: RealEngineConfig = RealEngineConfig(),
        slo: SLO = SLO(),
        sampling: SamplingParams = SamplingParams(),
        clock=None,
    ):
        self.cfg = cfg
        self.params = params
        self.ec = eng_cfg
        self.sampling = sampling
        self._clock = clock or time.perf_counter
        if eng_cfg.backend not in ("auto", "paged", "contiguous"):
            raise ValueError(f"unknown backend {eng_cfg.backend!r}")
        if eng_cfg.backend == "paged" and not tf.supports_paged(cfg):
            raise ValueError(f"{cfg.name}: arch cannot run the paged backend")
        self.paged = eng_cfg.backend != "contiguous" and tf.supports_paged(cfg)

        self.blocks = BlockManager(
            eng_cfg.num_device_blocks, eng_cfg.num_host_blocks, eng_cfg.block_size,
            prefix_cache=eng_cfg.prefix_cache and self.paged,
        )
        # Fault injection (DESIGN.md §16): the manager arms the pool points
        # (alloc.grow/alloc.resume/cow.prepare/host.*); the engine arms the
        # dispatch points pre-execution.  _step_snap is the pre-iteration
        # scheduler snapshot the runtime rolls back to on a request-scoped
        # fault — taken only when an injector is installed, so the
        # fault-free path pays nothing.
        self.faults = eng_cfg.faults
        self.blocks.faults = self.faults
        self._step_snap = None
        self._step_snap_staged = False
        sched_cfg = sched_cfg or SchedulerConfig(
            chunk_size=32, slo_aware=False, offline_batch_tokens=4096
        )
        if sched_cfg.max_model_len is None:
            # admission control: reject what the paged backend cannot hold
            # (copy — never mutate a caller-owned, possibly shared config)
            sched_cfg = dataclasses.replace(
                sched_cfg, max_model_len=eng_cfg.max_model_len
            )
        # until calibrate() replaces it; peaks keyed by the chip's kind
        lat = AnalyticalCostModel(cfg, hardware_for(jax.devices()[0]))
        self.sched = UnifiedScheduler(cfg, lat, slo, self.blocks, sched_cfg)

        self.mesh = eng_cfg.mesh
        if self.mesh is not None:
            if "model" not in self.mesh.axis_names:
                raise ValueError("serving mesh needs a 'model' axis")
            tp = self.mesh.shape["model"]
            if cfg.num_kv_heads % tp:
                raise ValueError(
                    f"{cfg.name}: {cfg.num_kv_heads} KV heads do not divide "
                    f"the {tp}-way model axis"
                )
            if not self.paged:
                raise ValueError(
                    "tensor-parallel serving requires the paged backend "
                    f"({cfg.name} resolved to the contiguous fallback)"
                )
            from jax.sharding import NamedSharding, PartitionSpec

            # Params, tables, token ids, lengths replicate; only the KV
            # pools (and the attention compute addressing them) shard.
            self._replicated = NamedSharding(self.mesh, PartitionSpec())
            self.params = jax.device_put(params, self._replicated)

        # KV-block checkpoint/restore is exact for plain causal-attention
        # archs; SSM state, ring-buffer (SWA) caches and static cross-attn KV
        # resume via full recompute instead (DESIGN.md §4).
        ckpt_ok = (
            eng_cfg.enable_checkpointing
            and not cfg.has_ssm_state
            and not cfg.cross_attn_period
            and cfg.causal
            and tf.cache_capacity(cfg, eng_cfg.max_model_len) == eng_cfg.max_model_len
        )
        self.ckpt = Checkpointer(
            self.blocks,
            AdaptiveCheckpointPolicy(start_threshold=0.0),  # always checkpoint
            block_bytes(cfg, eng_cfg.block_size),
            enabled=ckpt_ok,
        )
        self.flag = PreemptionFlag()
        self.safepoints = SegmentedExecution(self.flag)
        self.host = HostKVStore()  # (seq, block_index) -> KV block bytes
        self.steps = 0
        self._key = jax.random.PRNGKey(0)
        self.decode_trace_count = 0  # jit retraces of the decode entry point
        self.prefill_trace_count = 0  # jit retraces of the paged prefill
        self.fused_trace_count = 0  # jit retraces of the fused segment
        self.cow_trace_count = 0  # jit retraces of the COW block-copy program
        self.cow_dispatches = 0  # COW copy programs actually run on device
        # Device dispatches of the jitted model programs, by entry point —
        # the fusion bench/tests count these (embed/sample eager ops and
        # checkpoint copies excluded).
        self.dispatches: Dict[str, int] = {
            "prefill": 0, "decode": 0, "segment": 0,
            "fused_segment": 0, "fused_logits": 0,
        }
        # Runtime hook: called between K-layer segment dispatches of a
        # pure-offline batch (i.e. at every safepoint) so the wall-clock
        # runtime can drain arrivals that landed on the API thread and run
        # Algorithm 2 against the in-flight batch.
        self.arrival_poll: Optional[Callable[[], None]] = None
        self.profile: Optional[MeasuredProfiler] = None  # set by calibrate()

        self.fused = self.paged and eng_cfg.fused_batch
        self.pipeline = bool(eng_cfg.pipeline)
        if self.pipeline and not self.fused:
            raise ValueError(
                "pipeline=True requires the fused paged backend "
                "(backend='paged'/'auto' with fused_batch=True)"
            )
        # ---- async host/device pipeline state (DESIGN.md §13) ----------
        self._staged: Optional[_StagedBatch] = None
        self._plan_gen = 0  # bumped per arrival; invalidates staged plans
        self._fetches: Deque[_PendingFetch] = deque()
        self._ckpt_pending: List[tuple] = []  # (chosen, staged device gather)
        # (witness, displaced pool slice) pairs: buffers donated to an
        # in-flight segment/restore, parked until the witness (an output
        # of the donating program) is ready — dropping them earlier blocks
        # the host on the CPU client's donation hold (see _drop_retired)
        self._retired: Deque[tuple] = deque()
        self.pipeline_discards = 0  # staged batches invalidated pre-dispatch
        self.pipeline_trace_count = 0  # sample_rows / inject_sampled retraces
        # Host-gap instrumentation: per-iteration device-idle time — the
        # serial host span (sample readback, commit, plan, batch build)
        # during which the device has an empty queue, which the pipeline
        # exists to hide.  ``_t_last_enqueue`` marks where the current
        # gap's clock started (a drain point on serial turns, the last
        # enqueue otherwise); ``_last_out`` is the final array enqueued —
        # if it is still not ready when the next batch is handed over, the
        # device never idled and the sample records 0.  A fixed-bucket
        # histogram (count and sum monotone, never reset), so its memory
        # stays bounded for the life of a server; the runtime publishes it.
        self._t_last_enqueue: Optional[float] = None
        self._last_out: Optional[Any] = None
        self.host_gap = Histogram("host_gap_seconds", HOST_GAP_BOUNDS)
        # KV blocks copied out by checkpoints, scattered back by resumes
        # and duplicated by copy-on-write (monotone; published as
        # ``kv_<kind>_blocks_total``)
        self.kv_blocks = {"checkpoint": 0, "restore": 0, "cow": 0}
        # tokens of the plan the last step executed (0: it ran none)
        self.plan_tokens = 0
        # Calibration-drift instrumentation (DESIGN.md §15): cumulative
        # measured step wall time vs the installed latency model's
        # prediction for the same batch shapes.  The serial engine measures
        # the full blocking iteration (plan dispatch through commit); the
        # pipelined engine measures only the enqueue-side span (device
        # compute overlaps the host), so its drift ratio sits below 1 by
        # design.  Monotone accumulators — the runtime's metrics surface
        # publishes the ratio as ``calibration_drift``.
        self.measured_iter_seconds = 0.0
        self.predicted_iter_seconds = 0.0
        if self.paged:
            # Shared physical pools + one scratch row (id num_device_blocks)
            # that absorbs writes from padded batch rows / padded table
            # columns; real sequences never reference it.
            self._scratch_block = eng_cfg.num_device_blocks
            self._table_width = self.blocks.blocks_for_tokens(
                eng_cfg.max_model_len
            )
            self.pools = tf.init_paged_pools(
                cfg, eng_cfg.num_device_blocks + 1, eng_cfg.block_size
            )
            if self.mesh is not None:
                from repro.distributed.sharding import pool_shardings

                self.pools = jax.device_put(
                    self.pools, pool_shardings(self.pools, self.mesh)
                )
            if self.pipeline:
                # Pipelined engines keep the pools permanently split per
                # fused segment so each segment program donates only its
                # own period slice (DESIGN.md §13).  ``self.pools`` is
                # dropped so any stale whole-pool path fails loudly.
                self._pool_spans = tf.segment_spans(cfg)
                self._pool_segs = [
                    jax.tree.map(lambda a: a[lo : lo + pps], self.pools)
                    for lo, pps in self._pool_spans
                ]
                self.pools = None

            # Every model program takes the weights as its first traced
            # argument and never closes over them: a closed-over array
            # would be baked into the program as a constant (gigabytes per
            # compiled bucket at published widths).
            def _decode_paged(params, last, pools, tables, lens):
                self.decode_trace_count += 1  # runs only while tracing
                return tf.decode_step_paged(
                    self.cfg, params, last, pools, tables, lens,
                    mesh=self.mesh,
                )

            self._decode_jit = jax.jit(_decode_paged, donate_argnums=(2,))

            def _prefill_paged(params, toks, pools, tables, off, last):
                self.prefill_trace_count += 1  # runs only while tracing
                return tf.prefill_chunk_paged(
                    self.cfg, params, toks, pools, tables, off,
                    last_index=last, mesh=self.mesh,
                )

            self._prefill_jit = jax.jit(_prefill_paged, donate_argnums=(2,))
            # traced-start segment program: all equal-length segments share
            # one compilation per batch bucket (run_segment_paged_at)
            self._segment_jit = jax.jit(
                lambda pps, params, lo, x, pools, tables, positions: (
                    tf.run_segment_paged_at(
                        self.cfg, params, pps, lo, x, pools, tables,
                        positions, mesh=self.mesh,
                    )
                ),
                static_argnums=(0,),
                donate_argnums=(4,),
            )

            # fused ragged token-batch programs (DESIGN.md §12): one
            # traced-start segment shared by all equal-length segments of
            # every (token, sequence, query-length) bucket triple, plus
            # the S-row logits gather
            def _fused_segment(pps, params, lo, x, pools, tables, positions,
                               meta):
                self.fused_trace_count += 1  # runs only while tracing
                return tf.run_tokens_paged_at(
                    self.cfg, params, pps, lo, x, pools, tables,
                    positions, meta, mesh=self.mesh,
                )

            self._fused_segment_jit = jax.jit(
                _fused_segment, static_argnums=(0,), donate_argnums=(4,)
            )
            self._fused_logits_jit = jax.jit(
                lambda params, x, li: tf.ragged_lm_head(self.cfg, params, x, li)
            )

            # pipelined-engine programs (DESIGN.md §13): the per-segment
            # pool-slice program, sampling as an enqueued device step
            # (result fetched asynchronously) and the deferred-token
            # scatter that patches a speculatively built batch with the
            # previous iteration's still-on-device samples.
            #
            # Why a separate segment program: the whole-pool form donates
            # the pools, but the CPU client's donation hold makes *every*
            # interaction with a donated-and-pending buffer block until
            # the donating computation retires — enqueueing the consumer
            # (definition-event wait) and even dropping the Python
            # reference (deletion wait).  A whole-pool donation chain
            # therefore serializes exactly the overlap the pipeline
            # exists to create.  The pipelined engine keeps the pools
            # permanently SPLIT per segment (``_pool_segs``): each
            # segment donates only its own slice, whose previous hold
            # (the same segment, one iteration ago) retired long before
            # the host enqueues — real overlap.  Both forms update their
            # donated pool in place (transformer.run_layers_in_place).  The
            # displaced slice references are parked in ``_retired`` until
            # their holds provably resolved (see _drop_retired).
            def _fused_segment_seg(pps, params, lo, x, pool_seg, tables,
                                   positions, meta):
                self.fused_trace_count += 1  # runs only while tracing
                return tf.run_tokens_paged_seg(
                    self.cfg, params, pps, lo, x, pool_seg, tables,
                    positions, meta, mesh=self.mesh,
                )

            self._fused_segment_seg_jit = jax.jit(
                _fused_segment_seg, static_argnums=(0,), donate_argnums=(4,)
            )

            def _extract_segs(segs, ids):
                # seg-split twin of _extract: per-slice gathers concatenate
                # back to the period-major host checkpoint layout
                parts = [
                    {
                        pos: {"k": p["k"][:, ids], "v": p["v"][:, ids]}
                        for pos, p in seg.items()
                    }
                    for seg in segs
                ]
                return {
                    pos: {
                        kv: jnp.concatenate(
                            [pt[pos][kv] for pt in parts], axis=0
                        )
                        for kv in ("k", "v")
                    }
                    for pos in parts[0]
                }

            self._extract_segs_jit = jax.jit(_extract_segs)

            def _restore_segs(segs, ids, blocks):
                # seg-split twin of _restore: scatter each slice's period
                # range of the host-staged blocks into its donated slice
                out, off = [], 0
                for seg in segs:
                    pps = jax.tree.leaves(seg)[0].shape[0]
                    new = {
                        pos: {
                            kv: p[kv]
                            .at[:, ids]
                            .set(blocks[pos][kv][off : off + pps])
                            for kv in ("k", "v")
                        }
                        for pos, p in seg.items()
                    }
                    out.append(tf.constrain_paged_pools(new, self.mesh))
                    off += pps
                return tuple(out)

            self._restore_segs_jit = jax.jit(
                _restore_segs, donate_argnums=(0,)
            )

            def _sample_rows(logits, rows, key):
                self.pipeline_trace_count += 1  # runs only while tracing
                return sample_rows(logits, rows, self.sampling, key)

            self._sample_jit = jax.jit(_sample_rows)

            def _inject(toks, idx, sampled, srows):
                self.pipeline_trace_count += 1  # runs only while tracing
                return tf.inject_sampled(toks, idx, sampled, srows)

            # never donated: the displaced tokens buffer is dropped right
            # after the call, and deleting a donated-and-pending buffer
            # blocks until the whole in-flight chain retires (see above)
            self._inject_jit = jax.jit(_inject)

            def _restore(pools, ids, blocks):
                new = {
                    pos: {
                        "k": pool["k"].at[:, ids].set(blocks[pos]["k"]),
                        "v": pool["v"].at[:, ids].set(blocks[pos]["v"]),
                    }
                    for pos, pool in pools.items()
                }
                # restored blocks arrive replicated from the host store;
                # each shard keeps only its own heads of them (exact)
                return tf.constrain_paged_pools(new, self.mesh)

            self._restore_jit = jax.jit(_restore, donate_argnums=(0,))

            def _extract(pools, ids):
                # the gather runs shard-local (head sharding is on an
                # unindexed dim); device_get assembles full-head blocks so
                # the host store stays mesh-oblivious
                return {
                    pos: {"k": pool["k"][:, ids], "v": pool["v"][:, ids]}
                    for pos, pool in pools.items()
                }

            self._extract_jit = jax.jit(_extract)

            # copy-on-write block duplication (DESIGN.md §14): realize the
            # block manager's COW decisions as pool-internal copies before
            # the iteration's KV writes.  cache_ops.copy_blocks vmaps over
            # the leading period axis; shard-local like extract/restore
            # (the copied dim is unsharded).
            def _cow_copy(leaf, src, dst):
                return jax.vmap(
                    cache_ops.copy_blocks, in_axes=(0, None, None)
                )(leaf, src, dst)

            def _cow(pools, src, dst):
                self.cow_trace_count += 1  # runs only while tracing
                new = {
                    pos: {
                        kv: _cow_copy(pool[kv], src, dst) for kv in ("k", "v")
                    }
                    for pos, pool in pools.items()
                }
                return tf.constrain_paged_pools(new, self.mesh)

            self._cow_jit = jax.jit(_cow, donate_argnums=(0,))

            def _cow_segs(segs, src, dst):
                # seg-split twin for the pipelined engine's permanently
                # split pools: each slice donates in place (§13)
                self.cow_trace_count += 1  # runs only while tracing
                out = []
                for seg in segs:
                    new = {
                        pos: {
                            kv: _cow_copy(pool[kv], src, dst)
                            for kv in ("k", "v")
                        }
                        for pos, pool in seg.items()
                    }
                    out.append(tf.constrain_paged_pools(new, self.mesh))
                return tuple(out)

            self._cow_segs_jit = jax.jit(_cow_segs, donate_argnums=(0,))
        else:
            self.caches: Dict[int, Any] = {}  # request_id -> cache pytree (B=1)

            def _decode(params, last, caches, lens):
                self.decode_trace_count += 1  # runs only while tracing
                return tf.decode_step(self.cfg, params, last, caches, lens)

            self._decode_jit = jax.jit(
                _decode,
                donate_argnums=(2,),  # in-place cache update (TPU semantics)
            )
            self._segment_jit = jax.jit(
                lambda seg, params, x, caches, positions: tf.run_segment(
                    self.cfg, params, seg, x, caches,
                    mode="decode", positions=positions,
                ),
                static_argnums=(0,),
                donate_argnums=(3,),
            )
            self._prefill_jit = jax.jit(
                lambda params, toks, caches, off, img: tf.prefill_chunk(
                    self.cfg, params, toks, caches, off, image_embeds=img
                )
            )

    # ------------------------------------------------------------------ api
    def set_clock(self, clock: Callable[[], float]) -> None:
        """Rebind the engine clock (the wall-clock runtime rebases it to
        seconds-since-replay-start so timestamps align with trace offsets)."""
        self._clock = clock

    def submit(self, req: Request) -> None:
        """Queue a request.  Raises ``core.scheduler.AdmissionError`` before
        any block is allocated if the request cannot fit ``max_model_len``."""
        if req.prompt is None:
            raise ValueError("real engine requires prompt token ids")
        self.sched.submit(req)
        self._plan_gen += 1  # new work invalidates a speculatively staged plan

    def on_online_arrival(self, req: Request) -> None:
        """Streaming-API entry: may trip the preemption flag (Algorithm 2).
        Raises ``AdmissionError`` like ``submit`` (before queueing)."""
        if req.prompt is None:
            raise ValueError("real engine requires prompt token ids")
        if self.sched.on_online_arrival(req, self._clock()):
            self.flag.set()
        self._plan_gen += 1  # new work invalidates a speculatively staged plan

    def _on_safepoint(self, seg_idx: int) -> None:
        if self.arrival_poll is not None:
            self.arrival_poll()

    # ------------------------------------------------------------- placement
    def _put(self, x) -> jnp.ndarray:
        """Device-place one host-built jit input.  On a serving mesh, token
        ids / block tables / lengths / host-staged KV are replicated —
        every chip runs the same SPMD program over the same addressing
        metadata, only the pools (and heads) differ per shard."""
        if self.mesh is None:
            return jnp.asarray(x)
        return jax.device_put(np.asarray(x), self._replicated)

    # ---------------------------------------------------------------- tokens
    def _tokens_of(self, req: Request) -> np.ndarray:
        return np.concatenate(
            [np.asarray(req.prompt, np.int32), np.asarray(req.output_tokens, np.int32)]
        )

    # ----------------------------------------------------------- paged layout
    def _block_table(self, rid: int) -> np.ndarray:
        return np.asarray(
            self.blocks.block_table(
                rid, self._table_width, pad=self._scratch_block
            ),
            np.int32,
        )

    # Shape bucketing (one shared primitive, core.budget.pow2_bucket):
    # decode batches / checkpoint id lists pad at floor 1, prefill chunk
    # lengths at floor 8, so jit retraces are bounded by the bucket count,
    # not by every batch size or residual chunk length the scheduler
    # produces.  The fused ragged path buckets its token / sequence /
    # query-length axes with the same helper (floor 1).
    _decode_bucket = staticmethod(pow2_bucket)

    @staticmethod
    def _chunk_bucket(n: int) -> int:
        return pow2_bucket(n, floor=8)

    def _extract_blocks_paged(self, dev_blocks: List[int]) -> List[Any]:
        """Pack the selected physical blocks with one jitted gather and pull
        them to host in a single transfer (the CPU twin of the Pallas
        ``kv_checkpoint`` staging-DMA path); returns one stored dict per
        block, in ``dev_blocks`` order.

        The id list is padded to a power-of-two bucket (extra rows read the
        scratch block and are discarded) so the gather program compiles once
        per bucket instead of once per distinct block count."""
        n = len(dev_blocks)
        pad = self._decode_bucket(n)
        ids = self._put(
            np.asarray(
                list(dev_blocks) + [self._scratch_block] * (pad - n), np.int32
            )
        )
        if self.pipeline:
            staged = jax.device_get(
                self._extract_segs_jit(tuple(self._pool_segs), ids)
            )
        else:
            staged = jax.device_get(self._extract_jit(self.pools, ids))
        return [
            {
                pos: {"k": b["k"][:, i], "v": b["v"][:, i]}
                for pos, b in staged.items()
            }
            for i in range(n)
        ]

    def _restore_blocks_paged(self, dev_blocks: List[int], stored: List[Any]):
        """Scatter host-stored blocks into (re-allocated) physical pool
        slots — the paper's near-zero-cost resume path.  One jitted donated
        scatter per resume, so the update is in-place O(restored bytes)
        rather than a pool copy per block.  Padded to the same power-of-two
        buckets as extraction (extra rows rewrite the scratch block)."""
        n = len(dev_blocks)
        pad = self._decode_bucket(n)
        ids = self._put(
            np.asarray(
                list(dev_blocks) + [self._scratch_block] * (pad - n), np.int32
            )
        )
        stored = list(stored) + [stored[-1]] * (pad - n)
        batched = {
            pos: {
                "k": self._put(np.stack([s[pos]["k"] for s in stored], axis=1)),
                "v": self._put(np.stack([s[pos]["v"] for s in stored], axis=1)),
            }
            for pos in stored[0]
        }
        if self.pipeline:
            # _restore_segs_jit donated the old slices; park the displaced
            # references until the hold resolves (see _drop_retired).  The
            # witness is a scalar gather enqueued after the restore — the
            # restored slices themselves get donated onward, so they can't
            # witness their own retirement.
            displaced = self._pool_segs
            self._pool_segs = list(
                self._restore_segs_jit(tuple(displaced), ids, batched)
            )
            witness = jax.tree.leaves(self._pool_segs[0])[0][0, 0, 0, 0]
            self._retired.append((witness, displaced))
        else:
            self.pools = self._restore_jit(self.pools, ids, batched)

    def _cow_blocks_paged(self, pairs: List[tuple]) -> None:
        """Realize the block manager's copy-on-write decisions on device
        (DESIGN.md §14): duplicate each shared source block into the fresh
        exclusive destination the manager already rewired the sequence's
        table to.  Runs from ``_process_events`` — strictly before this
        iteration's dispatches enqueue, so device ordering puts the copies
        ahead of the divergent writes that triggered them.  Id lists pad
        to a power-of-two bucket with scratch→scratch no-op pairs: one
        compiled program per bucket, sharing changes indices, never
        shapes."""
        n = len(pairs)
        pad = self._decode_bucket(n)
        src = self._put(np.asarray(
            [s for _i, s, _d in pairs] + [self._scratch_block] * (pad - n),
            np.int32,
        ))
        dst = self._put(np.asarray(
            [d for _i, _s, d in pairs] + [self._scratch_block] * (pad - n),
            np.int32,
        ))
        self.cow_dispatches += 1
        if self.pipeline:
            # donated slices: park the displaced references until the hold
            # resolves, exactly like _restore_blocks_paged (§13)
            displaced = self._pool_segs
            self._pool_segs = list(
                self._cow_segs_jit(tuple(displaced), src, dst)
            )
            witness = jax.tree.leaves(self._pool_segs[0])[0][0, 0, 0, 0]
            self._retired.append((witness, displaced))
        else:
            self.pools = self._cow_jit(self.pools, src, dst)

    # ------------------------------------------------------ contiguous layout
    def _fresh_cache(self, req: Request) -> Any:
        return tf.init_caches(self.cfg, 1, self.ec.max_model_len)

    def _extract_block(self, cache: Any, block_idx: int) -> Any:
        bs = self.ec.block_size
        lo, hi = block_idx * bs, (block_idx + 1) * bs

        def ext(leaf):
            # attn caches: (P, 1, C, ...) — slot axis is 2
            if leaf.ndim >= 3 and leaf.shape[2] == self.ec.max_model_len:
                return np.asarray(leaf[:, :, lo:hi])
            return None

        return {
            pos: jax.tree.map(ext, c)
            for pos, c in cache.items()
            if "k" in c  # only attention positions hold sloted KV
        }

    def _restore_block(self, cache: Any, block_idx: int, stored: Any) -> Any:
        bs = self.ec.block_size
        lo = block_idx * bs

        def rest(leaf, s):
            if s is None:
                return leaf
            return jax.lax.dynamic_update_slice(
                leaf, jnp.asarray(s), (0, 0, lo) + (0,) * (leaf.ndim - 3)
            )

        new = dict(cache)
        for pos, sc in stored.items():
            new[pos] = jax.tree.map(rest, cache[pos], sc)
        return new

    # ---------------------------------------------------------------- events
    def _process_events(self) -> None:
        if not self.sched.events:
            return
        with TraceAnnotation(SPAN_EVENTS, events=len(self.sched.events)):
            self._apply_events()
        self.sched.events.clear()

    def _apply_events(self) -> None:
        if self._ckpt_pending and any(
            kind == "resume" for kind, _r, _p in self.sched.events
        ):
            # a resume reads the host store; in-flight async checkpoint
            # copies must land first or restored KV silently goes missing
            # (the scheduler already counted those blocks as recoverable)
            self._resolve_ckpt_pending()
        for kind, req, payload in self.sched.events:
            rid = req.request_id
            if kind in ("preempt_discard", "preempt_swap"):
                if kind == "preempt_swap":
                    # blocking swap-out: copy the un-checkpointed blocks now
                    # (checkpointed ones are already in the host store)
                    if self.paged and payload:
                        with TraceAnnotation(SPAN_SWAP_OUT, request=rid,
                                             blocks=len(payload)):
                            stored = self._extract_blocks_paged(
                                [dev for _idx, dev, _host in payload]
                            )
                            for (idx, _dev, _host), blk in zip(payload, stored):
                                self.host.put(rid, idx, blk)
                    elif not self.paged:
                        cache = self.caches.get(rid)
                        for idx, _dev, _host in payload:
                            if cache is not None:
                                self.host.put(
                                    rid, idx, self._extract_block(cache, idx)
                                )
                # discard costs zero device I/O: pure table edits (§4.4)
                if not self.paged:
                    self.caches.pop(rid, None)
                self.ckpt.unmark(req)
            elif kind == "cow":
                # copy-on-write: duplicate shared blocks before this
                # iteration's writes land in them (DESIGN.md §14).  Any
                # host-store bytes for the re-written indices predate the
                # divergence — drop them (the manager already released the
                # host blocks) so a later resume can never restore stale KV.
                if self.paged and payload:
                    self.kv_blocks["cow"] += len(payload)
                    with TraceAnnotation(SPAN_COW, request=rid,
                                         blocks=len(payload)):
                        self._cow_blocks_paged(payload)
                for idx, _src, _dst in payload:
                    self.host.pop(rid, idx)
            elif kind == "resume":
                nrec = self.blocks.blocks_for_tokens(req.host_recoverable)
                if self.paged:
                    sb = self.blocks.seq(rid)
                    devs, blks = [], []
                    for b in range(nrec):
                        stored = self.host.get(rid, b)
                        if stored is not None:
                            devs.append(sb.device_blocks[b])
                            blks.append(stored)
                    if devs:
                        self.kv_blocks["restore"] += len(devs)
                        with TraceAnnotation(SPAN_RESTORE, request=rid,
                                             blocks=len(devs)):
                            self._restore_blocks_paged(devs, blks)
                else:
                    cache = self._fresh_cache(req)
                    for b in range(nrec):
                        stored = self.host.get(rid, b)
                        if stored is not None:
                            cache = self._restore_block(cache, b, stored)
                    self.caches[rid] = cache

    # --------------------------------------------------- fault injection (§16)
    def _arm_iteration_faults(self, plan) -> None:
        """Arm the per-iteration dispatch fault points — once per *executed*
        iteration, after planning/event processing but BEFORE any of this
        iteration's device work.  The pre-dispatch cut is what makes the
        rollback exact for every arch (SSM state included): when a fault
        fires here, nothing of the iteration has run, so restoring the
        pre-iteration scheduler snapshot recovers the precise pre-fault
        state and surviving requests stay bitwise identical."""
        if self.faults is None:
            return
        spec = self.faults.arm("dispatch.slow")
        if spec is not None and spec.delay_s > 0:
            self.faults.sleep(spec.delay_s)
        spec = self.faults.arm("dispatch")
        if spec is None:
            return
        if spec.scope == "request":
            rid = spec.request_id
            if rid is None:
                # default victim: first offline request in the plan (the
                # harvested class absorbs the blast), else first planned
                reqs = [c.request for c in plan.prefill_chunks] + list(
                    plan.decode_reqs
                )
                offline = [r for r in reqs if not r.is_online]
                pick = (offline or reqs)[0] if (offline or reqs) else None
                rid = None if pick is None else pick.request_id
            if rid is not None:
                raise RequestFailed(
                    rid, f"injected dispatch fault at step {self.steps}"
                )
            return  # empty plan slot: nothing to attribute the fault to
        raise InjectedFault(
            f"injected engine-fatal dispatch fault at step {self.steps}"
        )

    def recover_from_fault(self) -> None:
        """Roll the engine back to the pre-iteration cut after an exception
        escaped ``step()`` (the runtime's request-scoped recovery path,
        DESIGN.md §16).

        Restores the scheduler/block-manager snapshot taken before the
        failed iteration planned (nothing of that iteration dispatched —
        faults fire pre-execution), discards staged speculation, drains the
        pipeline's async artifacts, and reconciles the host KV store: a
        rollback can resurrect manager host-table entries whose bytes a
        processed COW event already popped, which would make a later resume
        count tokens it cannot restore — such entries are dropped."""
        if self._staged is not None:  # defensive: faults fire mid-step,
            self.sched.restore(self._staged.snap)  # after _staged was popped
            self._staged = None
            self.pipeline_discards += 1
        snap, self._step_snap = self._step_snap, None
        was_staged, self._step_snap_staged = self._step_snap_staged, False
        if snap is not None:
            self.sched.restore(snap)
            if was_staged:
                self.pipeline_discards += 1
        self.flag.clear()
        if self.pipeline:
            self.flush_pipeline()
        for sid in self.blocks.seq_ids():
            sb = self.blocks.seq(sid)
            for i, hb in enumerate(sb.host_blocks):
                if hb >= 0 and self.host.get(sid, i) is None:
                    self.blocks.drop_host_block(sid, i)

    def fail_request(self, req: Request) -> None:
        """Remove one request from every engine-side structure (the runtime
        already rolled the iteration back via ``recover_from_fault``): the
        scheduler's queues, its pool blocks, host-store bytes, checkpoint
        candidacy, and the contiguous-fallback cache."""
        sched = self.sched
        for q in (sched.online_q, sched.offline_q, sched.running, sched.preempted):
            if req in q:
                q.remove(req)
        self.ckpt.unmark(req)
        if self.blocks.has_seq(req.request_id):
            self.blocks.free_seq(req.request_id)
        self.host.drop_seq(req.request_id)
        if not self.paged:
            self.caches.pop(req.request_id, None)
        self._plan_gen += 1  # staged speculation may reference the request

    # ------------------------------------------------------------------ step
    def step(self) -> bool:
        """One engine iteration. Returns False when no work remains."""
        self.plan_tokens = 0
        with TraceAnnotation(SPAN_STEP):
            if self.pipeline:
                return self._step_pipelined()
            return self._step_serial()

    def _plan(self, now: float):
        """``plan_iteration`` under its span, with the plan's counts."""
        with TraceAnnotation(SPAN_PLAN) as span:
            plan = self.sched.plan_iteration(now)
            span.set_metadata(**_plan_stats(plan))
        return plan

    def _commit(self, plan, aborted: bool, tokens) -> None:
        with TraceAnnotation(SPAN_COMMIT, aborted=int(aborted),
                             **_plan_stats(plan)):
            self.sched.commit(plan, self._clock(), aborted=aborted,
                              tokens=tokens)

    def _step_serial(self) -> bool:
        now = self._clock()
        sched = self.sched
        if self.faults is not None:
            # pre-iteration cut for request-scoped fault rollback (§16)
            self._step_snap = sched.snapshot()
            self._step_snap_staged = False
        plan = self._plan(now)
        self._process_events()
        if plan.empty:
            self._step_snap = None
            return bool(
                sched.online_q or sched.offline_q or sched.running or sched.preempted
            )
        self.steps += 1
        self.plan_tokens = plan.shape.total_tokens
        t_iter0 = time.perf_counter()
        predicted_s = self.sched.model.iter_time(plan.shape)
        self._arm_iteration_faults(plan)

        aborted = False
        tokens: Dict[int, int] = {}
        preemptible = (
            plan.pure_offline
            and self.ec.enable_safepoints
            and sched.sc.preempt_running
        )
        if not preemptible:
            # a flag left set after an un-aborted batch must not leak into a
            # later pure-offline iteration as a spurious abort
            self.flag.clear()

        if self.fused:
            # ---- fused ragged batch (DESIGN.md §12) -----------------------
            # prefill chunks + decode tokens lower to ONE flattened token
            # batch, one dispatch per K-layer segment, safepoints between
            aborted = self._run_fused(plan, preemptible, tokens)
        else:
            # ---- prefill chunks -------------------------------------------
            if self.paged:
                aborted = self._prefill_paged_batched(plan, preemptible, tokens)
            else:
                self._prefill_contiguous(plan, tokens)

            # ---- decode batch ---------------------------------------------
            if plan.decode_reqs and not aborted:
                reqs = plan.decode_reqs
                if self.paged:
                    logits, aborted = self._decode_paged(reqs, preemptible)
                else:
                    logits, aborted = self._decode_contiguous(reqs, preemptible)
                if not aborted:
                    self._key, sk = jax.random.split(self._key)
                    toks = np.asarray(sample(logits, self.sampling, sk))
                    for i, r in enumerate(reqs):
                        tokens[r.request_id] = int(toks[i])

        self._commit(plan, aborted, tokens)
        # the iteration is committed: token progress is now commit-owned
        # state the snapshot does not capture, so the rollback cut is gone
        self._step_snap = None
        self.measured_iter_seconds += time.perf_counter() - t_iter0
        self.predicted_iter_seconds += predicted_s
        if not self.paged:
            for r in list(self.caches):
                if not self.blocks.has_seq(r):
                    self.caches.pop(r, None)
        for sid in self.host.seq_ids():
            if not self.blocks.has_seq(sid):
                self.host.drop_seq(sid)

        if not aborted:
            self._checkpoint_after(plan)
        return True

    def _checkpoint_after(self, plan) -> None:
        """Post-iteration incremental checkpointing (shared by both step
        paths): mark the offline sequences that just executed, pick blocks,
        and copy them to the host store.  The serial engine copies
        synchronously; the pipelined engine only *enqueues* the jitted
        gather (device order puts it after this iteration's KV scatters)
        and fetches it next step, off the critical path (§13)."""
        executed_offline = [
            r for r in plan.decode_reqs if not r.is_online
        ] + [c.request for c in plan.prefill_chunks if not c.request.is_online]
        self.ckpt.mark(executed_offline)
        chosen = self.ckpt.plan(io_budget_blocks=1 << 30)
        if not chosen:
            return
        self.kv_blocks["checkpoint"] += len(chosen)
        with TraceAnnotation(SPAN_CHECKPOINT, **_ckpt_stats(chosen)):
            self._copy_checkpoint(chosen)

    def _copy_checkpoint(self, chosen) -> None:
        if self.paged:
            if self.pipeline:
                n = len(chosen)
                pad = self._decode_bucket(n)
                ids = self._put(
                    np.asarray(
                        [c[2] for c in chosen]
                        + [self._scratch_block] * (pad - n),
                        np.int32,
                    )
                )
                staged = self._extract_segs_jit(tuple(self._pool_segs), ids)
                for leaf in jax.tree.leaves(staged):
                    try:
                        leaf.copy_to_host_async()
                    except Exception:
                        pass
                self._ckpt_pending.append((chosen, staged))
            else:
                stored = self._extract_blocks_paged([c[2] for c in chosen])
                for (seq_id, idx, _dev, _host), blk in zip(chosen, stored):
                    self.host.put(seq_id, idx, blk)
        else:
            for seq_id, idx, _dev, _host in chosen:
                cache = self.caches.get(seq_id)
                if cache is not None:
                    self.host.put(seq_id, idx, self._extract_block(cache, idx))

    def _resolve_ckpt_pending(self) -> None:
        """Land in-flight async checkpoint copies in the host store.  A
        sequence freed since the gather was enqueued (it finished in the
        meantime) is skipped — its host entries were already dropped."""
        for chosen, staged in self._ckpt_pending:
            with TraceAnnotation(SPAN_CHECKPOINT, **_ckpt_stats(chosen)):
                staged = jax.device_get(staged)
                for i, (seq_id, idx, _dev, _host) in enumerate(chosen):
                    if not self.blocks.has_seq(seq_id):
                        continue
                    self.host.put(
                        seq_id,
                        idx,
                        {
                            pos: {"k": b["k"][:, i], "v": b["v"][:, i]}
                            for pos, b in staged.items()
                        },
                    )
        self._ckpt_pending.clear()

    # ------------------------------------------------- fused ragged execution
    def _build_ragged(self, items: List[tuple]) -> Dict[str, np.ndarray]:
        """Lower one iteration's sequences to flat ragged-batch arrays.

        ``items`` holds one ``(q_len, ctx_start, tokens|None, table|None)``
        per sequence — prefill chunks contribute ``q_len = chunk length``
        at ``ctx_start = offset``, decodes are the ``q_len = 1`` case at
        ``ctx_start = total_len - 1``.  ``None`` tokens/tables build a
        calibration probe that addresses only the scratch row.

        Every variable axis pads to a power-of-two bucket (DESIGN.md §12):
        T (total tokens), S (sequences) and Qmax (longest per-sequence
        query run), so fused jit retraces are keyed on the bucket triple.
        All indirection — KV scatter targets, the (S, Qmax) query padding,
        the flat unpad gather, per-sequence logit rows — is resolved here
        on the host; padded tokens scatter to the scratch row and padded
        query/sequence slots compute garbage nothing reads back.
        """
        bs = self.ec.block_size
        t_pad = pow2_bucket(sum(it[0] for it in items))
        s_pad = pow2_bucket(len(items))
        qmax = pow2_bucket(max(it[0] for it in items))
        a = {
            "tokens": np.zeros((t_pad,), np.int32),
            "positions": np.zeros((t_pad,), np.int32),
            "dst_row": np.full((t_pad,), self._scratch_block, np.int32),
            "dst_off": np.zeros((t_pad,), np.int32),
            "tables": np.full(
                (s_pad, self._table_width), self._scratch_block, np.int32
            ),
            "qpad": np.full((s_pad, qmax), t_pad - 1, np.int32),
            "q_pos": np.zeros((s_pad, qmax), np.int32),
            "kv_lens": np.zeros((s_pad,), np.int32),
            "unpad_seq": np.full((t_pad,), s_pad - 1, np.int32),
            "unpad_j": np.zeros((t_pad,), np.int32),
            "logit_idx": np.full((s_pad,), t_pad - 1, np.int32),
        }
        start = 0
        for i, (qlen, ctx, toks, table) in enumerate(items):
            sl = slice(start, start + qlen)
            pos = ctx + np.arange(qlen, dtype=np.int32)
            if toks is not None:
                a["tokens"][sl] = toks
            a["positions"][sl] = pos
            if table is not None:
                a["tables"][i] = table
                a["dst_row"][sl] = table[pos // bs]
                a["dst_off"][sl] = pos % bs
            a["qpad"][i, :qlen] = start + np.arange(qlen, dtype=np.int32)
            a["q_pos"][i, :qlen] = pos
            a["kv_lens"][i] = ctx + qlen
            a["unpad_seq"][sl] = i
            a["unpad_j"][sl] = np.arange(qlen, dtype=np.int32)
            a["logit_idx"][i] = start + qlen - 1
            start += qlen
        return a

    def _fused_inputs(self, a: Dict[str, np.ndarray]):
        """Device-place one ragged batch (replicated on a serving mesh)."""
        meta = tf.RaggedMeta(
            dst_row=self._put(a["dst_row"]),
            dst_off=self._put(a["dst_off"]),
            qpad=self._put(a["qpad"]),
            q_pos=self._put(a["q_pos"]),
            kv_lens=self._put(a["kv_lens"]),
            unpad_seq=self._put(a["unpad_seq"]),
            unpad_j=self._put(a["unpad_j"]),
        )
        return (
            self._put(a["tokens"]),
            self._put(a["tables"]),
            self._put(a["positions"][None]),
            meta,
            self._put(a["logit_idx"]),
        )

    def _run_segments(self, x, seg_fn, counter: str, preemptible: bool):
        """Shared segment-closure scaffolding for every segmented program
        (the fused ragged stack and the split paged decode): one jitted
        dispatch per K-layer segment with host-side safepoint cuts between
        them (DESIGN.md §9/§12).  ``seg_fn(lo, pps, x) -> x``; the closure
        owns its pool bookkeeping (whole-pool rebind for serial engines,
        per-segment slice swap for pipelined ones).  Returns
        ``(x | None, aborted)``; on abort the flag is consumed."""
        state = {"x": x}

        def make_seg(lo, pps):
            def run():
                self.dispatches[counter] += 1
                state["x"] = seg_fn(lo, pps, state["x"])

            return run

        completed, _done = self.safepoints.run(
            [make_seg(lo, pps) for lo, pps in tf.segment_spans(self.cfg)],
            preemptible=preemptible,
            on_safepoint=self._on_safepoint,
        )
        if not completed:
            self.flag.clear()
            return None, True
        return state["x"], False

    def _dispatch_fused(self, toks, tables, positions, meta, logit_idx,
                        preemptible: bool):
        """Run the fused stack: embed, then ONE dispatch per K-layer
        segment (host-side safepoint cuts between them when the plan is
        abortable), then the S-row logits program.  Returns
        (logits | None, aborted)."""
        if self._t_last_enqueue is not None:
            gap = time.perf_counter() - self._t_last_enqueue
            out, self._last_out = self._last_out, None
            if out is not None and not out.is_ready():
                # the device still had queued work when this batch was
                # handed over: zero observable idle (§13)
                gap = 0.0
            self._t_last_enqueue = None
            self.host_gap.observe(gap)
        segs = self.dispatches["fused_segment"]
        with TraceAnnotation(SPAN_DISPATCH) as span:
            logits, aborted = self._enqueue_fused(
                toks, tables, positions, meta, logit_idx, preemptible
            )
            span.set_metadata(
                segments=self.dispatches["fused_segment"] - segs,
                aborted=int(aborted),
            )
        return logits, aborted

    def _enqueue_fused(self, toks, tables, positions, meta, logit_idx,
                       preemptible: bool):
        x = tf.embed(self.cfg, self.params, toks[None])
        if self.pipeline:
            # per-segment split pools (§13): each segment program donates
            # its OWN period slice, whose previous donation hold (the same
            # segment, one iteration ago) retired long before this enqueue
            # — so the enqueue never waits, the update is in-place, and no
            # merge or extra pool traffic exists.  The displaced slice
            # reference is parked with the segment's activation output as
            # witness (never donated, defined by the donating program).
            # An abort leaves partial slice updates in place, which is
            # sound for the same reason the serial donated path is: writes
            # at uncommitted positions are rewritten verbatim on
            # re-execution (§12).
            idx = {"i": 0}

            def seg(lo, pps, h):
                i = idx["i"]
                idx["i"] += 1
                old = self._pool_segs[i]
                h, self._pool_segs[i] = self._fused_segment_seg_jit(
                    pps, self.params, np.int32(lo), h, old, tables,
                    positions, meta,
                )
                self._retired.append((h, old))
                return h

            x, aborted = self._run_segments(x, seg, "fused_segment",
                                            preemptible)
            if aborted:
                return None, True
            self.dispatches["fused_logits"] += 1
            logits = self._fused_logits_jit(self.params, x, logit_idx)
            self._drop_retired()
            return logits, False
        else:

            def seg(lo, pps, h):
                h, self.pools = self._fused_segment_jit(
                    pps, self.params, np.int32(lo), h, self.pools, tables,
                    positions, meta,
                )
                return h

            x, aborted = self._run_segments(x, seg, "fused_segment",
                                            preemptible)
            if aborted:
                return None, True
        self.dispatches["fused_logits"] += 1
        return self._fused_logits_jit(self.params, x, logit_idx), False

    def _build_fused(self, plan) -> Tuple[List[tuple], tuple]:
        """Lower an ``IterationPlan`` to device-ready fused inputs.

        Returns ``(samplers, (toks, tables, positions, meta, logit_idx))``
        where ``samplers`` is the ``(sequence row, request)`` list whose
        logit rows must be sampled after the dispatch.

        Pipelined engine only (§13): a decode row whose latest token is
        still in flight (sampled last iteration, not yet fetched) gets a
        placeholder slot in the flat token array, patched by ONE jitted
        ``inject_sampled`` scatter reading straight from the pending
        device sample buffer — speculation never blocks on token values.
        The injection index/row lists pad to a power-of-two bucket by
        *repeating* a real pair, which is idempotent under ``.at[].set``
        (the padded slot at ``t_pad - 1`` may be a real token when the
        batch exactly fills its bucket, so padding with it is unsafe)."""
        with TraceAnnotation(SPAN_BUILD) as span:
            items, samplers, inj = self._fused_items(plan)
            arrays = self._build_ragged(items)
            span.set_metadata(
                sequences=len(items),
                tokens=sum(it[0] for it in items),
                bucket="%d,%d,%d" % (len(arrays["tokens"]),
                                     len(arrays["kv_lens"]),
                                     arrays["qpad"].shape[1]),
            )
        with TraceAnnotation(SPAN_PUT) as span:
            inputs = self._fused_inputs(arrays)
            if inj:
                toks_d, tables, positions, meta, li = inputs
                pad = pow2_bucket(len(inj))
                inj = inj + [inj[-1]] * (pad - len(inj))
                toks_d = self._inject_jit(
                    toks_d,
                    self._put(np.asarray([i for i, _ in inj], np.int32)),
                    self._fetches[-1].arr,
                    self._put(np.asarray([r for _, r in inj], np.int32)),
                )
                inputs = (toks_d, tables, positions, meta, li)
            span.set_metadata(arrays=len(arrays) + (2 if inj else 0))
        return samplers, inputs

    def _fused_items(self, plan) -> Tuple[List[tuple], List[tuple], List[tuple]]:
        """The plan's ``_build_ragged`` items, the ``(sequence row,
        request)`` pairs to sample, and the ``(flat token slot, row in
        pending samples)`` pairs to inject."""
        pend: Dict[int, int] = {}
        if self._fetches:
            latest = self._fetches[-1]
            pend = {r.request_id: i for i, r in enumerate(latest.reqs)}
        items: List[tuple] = []
        samplers: List[tuple] = []  # (sequence row, request) to sample
        inj: List[tuple] = []  # (flat token slot, row in pending samples)
        start = 0
        for c in plan.prefill_chunks:
            toks = self._tokens_of(c.request)[c.offset : c.offset + c.length]
            items.append(
                (c.length, c.offset, toks,
                 self._block_table(c.request.request_id))
            )
            if (
                c.offset + c.length == c.request.kv_target
                and c.request.num_generated == 0
            ):
                samplers.append((len(items) - 1, c.request))
            start += c.length
        for r in plan.decode_reqs:
            row = pend.get(r.request_id)
            if row is None:
                tok = self._tokens_of(r)[-1:]
            else:
                tok = np.zeros((1,), np.int32)  # injected on device
                inj.append((start, row))
            items.append(
                (1, r.total_len - 1, tok, self._block_table(r.request_id))
            )
            samplers.append((len(items) - 1, r))
            start += 1
        return items, samplers, inj

    def _run_fused(
        self, plan, preemptible: bool, tokens: Dict[int, int]
    ) -> bool:
        """Execute the whole ``IterationPlan`` as one fused ragged batch.

        Abort rule (Algorithm 2, DESIGN.md §12): ``preemptible`` is set
        only for pure-offline plans, so an abort at a segment cut only
        ever discards offline tokens — an iteration containing any online
        token runs to completion (it is budget-bounded by construction).
        Returns True if the iteration aborted at a safepoint.
        """
        samplers, inputs = self._build_fused(plan)
        logits, aborted = self._dispatch_fused(*inputs, preemptible=preemptible)
        if aborted:
            return True
        if samplers:
            with TraceAnnotation(SPAN_SAMPLE, rows=len(samplers)):
                rows = jnp.asarray([i for i, _ in samplers])
                self._key, sk = jax.random.split(self._key)
                toks = np.asarray(sample(logits[rows], self.sampling, sk))
                for (_, r), t in zip(samplers, toks):
                    tokens[r.request_id] = int(t)
            self._last_out = None  # the readback above drained the device
        else:
            self._last_out = logits  # queue may still be busy
        self._t_last_enqueue = time.perf_counter()
        return False

    # ------------------------------------- async host/device pipeline (§13)
    def _step_pipelined(self) -> bool:
        """One iteration of the pipelined engine (DESIGN.md §13).

        Dispatches the batch staged by the previous step's speculation
        (falling back to serial plan+build when there is none or it went
        stale), enqueues sampling as a device step with an asynchronous
        readback, commits the iteration *structurally* (token counts now,
        token values backfilled by the pending fetch), then speculatively
        plans and builds the NEXT iteration while this one still runs on
        device.

        Soundness: safepoint checks are host-side cuts between segment
        enqueues, so once every segment is enqueued the iteration can no
        longer abort — committing at enqueue time observes exactly the
        outcomes the serial engine commits after blocking.  An abort
        discards only the current (pure-offline) iteration, same as
        serial; the staged next batch was already consumed above, and no
        new one is staged on the abort path, so replanning sees the
        post-abort scheduler state."""
        now = self._clock()
        sched = self.sched
        staged, self._staged = self._staged, None
        if staged is not None and staged.gen != self._plan_gen:
            # an arrival landed after staging: Algorithm 2 must see it, so
            # roll the scheduler back and replan serially below
            sched.restore(staged.snap)
            self.pipeline_discards += 1
            staged = None
        if staged is None:
            # serial (non-overlapped) turn: first iteration, after an
            # abort/idle stretch, or a discarded staged batch.  Token
            # values are needed on host to build decode inputs.
            self._resolve_fetches()
            if self._t_last_enqueue is not None:
                # the readbacks above drained the device queue: restart the
                # gap clock here so this turn's sample measures plan+build
                # time (exactly the serial engine's gap), not device compute
                self._t_last_enqueue = time.perf_counter()
                self._last_out = None
            if self.faults is not None:
                # pre-iteration cut for request-scoped fault rollback (§16)
                self._step_snap = sched.snapshot()
                self._step_snap_staged = False
            plan = self._plan(now)
            self._process_events()
            if plan.empty:
                self._step_snap = None
                self.flush_pipeline()
                self._t_last_enqueue = None
                self._last_out = None
                return bool(
                    sched.online_q or sched.offline_q
                    or sched.running or sched.preempted
                )
            samplers, inputs = self._build_fused(plan)
        else:
            plan, samplers, inputs = staged.plan, staged.samplers, staged.inputs
            if self.faults is not None:
                # the speculation's own snapshot predates every mutation
                # the staged plan made — it IS the rollback cut
                self._step_snap = staged.snap
                self._step_snap_staged = True
            # Algorithm 2's in-flight estimate measures from dispatch time,
            # not staging time
            sched.t_sched = now
            self._process_events()
        self.steps += 1
        self.plan_tokens = plan.shape.total_tokens
        t_iter0 = time.perf_counter()
        predicted_s = self.sched.model.iter_time(plan.shape)
        self._arm_iteration_faults(plan)

        preemptible = (
            plan.pure_offline
            and self.ec.enable_safepoints
            and sched.sc.preempt_running
        )
        if not preemptible:
            self.flag.clear()
        logits, aborted = self._dispatch_fused(*inputs, preemptible=preemptible)
        if aborted:
            self._commit(plan, True, {})
            self._step_snap = None
            self.measured_iter_seconds += time.perf_counter() - t_iter0
            self.predicted_iter_seconds += predicted_s
            return True

        if samplers:
            with TraceAnnotation(SPAN_SAMPLE, rows=len(samplers)):
                rows = [i for i, _ in samplers]
                pad = pow2_bucket(len(rows))
                rows_arr = self._put(
                    np.asarray(rows + [rows[-1]] * (pad - len(rows)), np.int32)
                )
                self._key, sk = jax.random.split(self._key)
                sampled = self._sample_jit(logits, rows_arr, sk)
                self._fetches.append(
                    _PendingFetch(sampled, [r for _, r in samplers])
                )
            self._last_out = sampled
        else:
            self._last_out = logits
        self._t_last_enqueue = time.perf_counter()
        # structural commit at enqueue time: every safepoint has passed, so
        # this iteration can no longer abort; tokens=None counts generated
        # tokens without values (record_token(None)), the pending fetch
        # backfills output_tokens before anything on host reads them
        self._commit(plan, False, None)
        self._step_snap = None
        self.measured_iter_seconds += time.perf_counter() - t_iter0
        self.predicted_iter_seconds += predicted_s

        # All remaining post-work runs BEFORE the speculation snapshot so a
        # rollback only ever reverts the speculative plan's own mutations.
        self._resolve_ckpt_pending()
        self._checkpoint_after(plan)
        self._resolve_fetches(keep_latest=True)
        for sid in self.host.seq_ids():
            if not self.blocks.has_seq(sid):
                self.host.drop_seq(sid)
        self._speculate()
        return True

    def _speculate(self) -> None:
        """Plan + host-build iteration N+1 while N runs on device (§13).

        The scheduler snapshot makes the plan *previewable*: every host
        mutation planning performs (admissions, block growth, preemption,
        resume, event emission) rolls back via ``restore`` if the staged
        batch is invalidated before dispatch.  Device work enqueued for
        the staged batch (input transfers, the token injection) simply
        goes unread on discard."""
        snap = self.sched.snapshot()
        plan = self._plan(self._clock())
        if plan.empty:
            self.sched.restore(snap)
            return
        samplers, inputs = self._build_fused(plan)
        self._staged = _StagedBatch(plan, snap, self._plan_gen, samplers, inputs)

    def _resolve_fetches(self, keep_latest: bool = False) -> None:
        """Backfill ``Request.output_tokens`` from pending sample fetches,
        oldest first.  ``keep_latest`` leaves the newest fetch in flight —
        the steady-state step keeps exactly one (the iteration still on
        device), which speculation reads via device-side injection."""
        keep = 1 if keep_latest else 0
        if len(self._fetches) > keep:
            rows = sum(len(self._fetches[i].reqs)
                       for i in range(len(self._fetches) - keep))
            with TraceAnnotation(SPAN_SAMPLE, rows=rows):
                while len(self._fetches) > keep:
                    self._fetches.popleft().resolve()
        self._drop_retired()

    def _drop_retired(self) -> None:
        """Release displaced pool buffers whose donation hold has resolved.

        A buffer donated to a still-pending program must keep a live
        Python reference: on the CPU client, deleting it blocks the host
        until the donating computation retires — the same stall the
        pipeline exists to remove.  Each retired entry carries a witness
        (the donating program's output); once the witness is ready the
        hold has resolved and the drop is instant.  Bounded by pipeline
        depth: one entry per in-flight iteration."""
        while self._retired and self._retired[0][0].is_ready():
            self._retired.popleft()

    def flush_pipeline(self) -> None:
        """Drain every asynchronous artifact of the pipelined engine:
        pending sampled-token fetches (backfilling output_tokens),
        in-flight checkpoint copies, and retired donated pool buffers.
        Idempotent; a no-op on serial engines.  Runs automatically when a
        step finds no work; the wall-clock runtime also calls it at
        replay end / stop so metrics and emitted tokens are complete
        (DESIGN.md §13)."""
        self._resolve_fetches()
        self._resolve_ckpt_pending()
        if self._retired:
            jax.block_until_ready(self._retired[-1][0])
            self._retired.clear()

    # --------------------------------------------------------------- prefill
    def _prefill_paged_batched(
        self, plan, preemptible: bool, tokens: Dict[int, int]
    ) -> bool:
        """Execute the plan's prefill chunks as bucket-batched dispatches.

        Chunks are grouped by padded length bucket (``_chunk_bucket``) and
        each group runs as ONE ``prefill_chunk_paged`` dispatch with the
        batch padded to a power of two — so a 12-sequence offline wave costs
        ~1 dispatch instead of 12, jit retraces are bounded by
        (batch buckets × length buckets), and the measured profile's single
        per-iteration overhead term matches what actually executes.

        Padding is harmless by construction: padded token positions write
        junk KV only into slots that are overwritten when the real tokens
        arrive, or are dropped beyond the table
        (``cache_ops.write_paged_chunk``); padded batch rows address only
        the scratch pool row.

        Group boundaries of a pure-offline iteration are safepoints
        (``preemptible``): KV writes are positional and idempotent, so an
        aborted iteration re-executes its chunks and rewrites the same
        bytes.  Returns True if the iteration aborted at such a safepoint.
        The contiguous fallback keeps decode-only safepoints — SSM state
        advances are not idempotent.
        """
        groups: Dict[int, List] = {}
        for chunk in plan.prefill_chunks:
            groups.setdefault(self._chunk_bucket(chunk.length), []).append(
                chunk
            )
        # split oversize groups: dispatch batch is capped so jit shapes stay
        # within the calibrated (batch bucket × length bucket) grid and a
        # long wave exposes several safepoint boundaries
        cap = max(1, self.ec.max_prefill_batch)
        dispatches = []
        for lpad in sorted(groups):
            g = groups[lpad]
            dispatches += [(lpad, g[i : i + cap]) for i in range(0, len(g), cap)]
        for gi, (lpad, chunks) in enumerate(dispatches):
            if preemptible and gi > 0:
                t0 = time.perf_counter()
                self._on_safepoint(gi)
                hit = self.flag.is_set()
                st = self.safepoints.stats
                st.checks += 1
                st.check_seconds += time.perf_counter() - t0
                if hit:
                    st.preemptions += 1
                    self.flag.clear()
                    return True
            bp = self._decode_bucket(len(chunks))
            toks = np.zeros((bp, lpad), np.int32)
            tables = np.full(
                (bp, self._table_width), self._scratch_block, np.int32
            )
            offs = np.zeros((bp,), np.int32)
            last = np.zeros((bp,), np.int32)
            for i, c in enumerate(chunks):
                toks[i, : c.length] = self._tokens_of(c.request)[
                    c.offset : c.offset + c.length
                ]
                tables[i] = self._block_table(c.request.request_id)
                offs[i] = c.offset
                last[i] = c.length - 1
            self.dispatches["prefill"] += 1
            logits, self.pools = self._prefill_jit(
                self.params,
                self._put(toks),
                self.pools,
                self._put(tables),
                self._put(offs),
                self._put(last),
            )
            done = [
                i
                for i, c in enumerate(chunks)
                if c.offset + c.length == c.request.kv_target
                and c.request.num_generated == 0
            ]
            if done:
                # one batched sample per dispatch (per-row eager sampling
                # costs a host round-trip per request)
                self._key, sk = jax.random.split(self._key)
                toks = np.asarray(
                    sample(logits[jnp.asarray(done)], self.sampling, sk)
                )
                for j, i in enumerate(done):
                    tokens[chunks[i].request.request_id] = int(toks[j])
        return False

    def _prefill_contiguous(self, plan, tokens: Dict[int, int]) -> None:
        """Per-sequence prefill chunks on the contiguous fallback layout."""
        for chunk in plan.prefill_chunks:
            r = chunk.request
            rid = r.request_id
            if not self.cfg.causal:
                # Encoder-only (audio): bidirectional — one full forward, no
                # cache, no chunking (scheduler must be configured with
                # chunk_size >= prompt_len for these jobs).
                assert chunk.offset == 0 and chunk.length == r.prompt_len, (
                    "encoder jobs cannot be chunked"
                )
                logits, _, _ = tf.forward_full(
                    self.cfg, self.params, jnp.asarray(r.prompt)[None]
                )
                self._key, sk = jax.random.split(self._key)
                tokens[rid] = int(sample(logits[:, -1, :], self.sampling, sk)[0])
                continue
            toks = self._tokens_of(r)[chunk.offset : chunk.offset + chunk.length]
            if rid not in self.caches:
                self.caches[rid] = self._fresh_cache(r)
            img = getattr(r, "image_embeds", None)
            img = img if (img is not None and chunk.offset == 0) else None
            logits, cache = self._prefill_jit(
                self.params,
                jnp.asarray(toks)[None, :],
                self.caches[rid],
                jnp.array([chunk.offset], jnp.int32),
                None if img is None else jnp.asarray(img)[None],
            )
            self.caches[rid] = cache
            if chunk.offset + chunk.length == r.kv_target and r.num_generated == 0:
                self._key, sk = jax.random.split(self._key)
                tokens[rid] = int(sample(logits, self.sampling, sk)[0])

    # ---------------------------------------------------------------- decode
    def _decode_paged(self, reqs: List[Request], use_safepoints: bool):
        """Batched decode on the shared pool at a bucketed shape."""
        bsz = len(reqs)
        bp = self._decode_bucket(bsz)
        tables = np.full(
            (bp, self._table_width), self._scratch_block, np.int32
        )
        last = np.zeros((bp,), np.int32)
        lens = np.zeros((bp,), np.int32)
        for i, r in enumerate(reqs):
            tables[i] = self._block_table(r.request_id)
            last[i] = self._tokens_of(r)[-1]
            lens[i] = r.total_len - 1
        last_j, tables_j, lens_j = (
            self._put(last), self._put(tables), self._put(lens)
        )
        if use_safepoints:
            logits, aborted = self._segmented_decode_paged(
                last_j, tables_j, lens_j
            )
            if aborted:
                return None, True
        else:
            self.dispatches["decode"] += 1
            logits, self.pools = self._decode_jit(
                self.params, last_j, self.pools, tables_j, lens_j
            )
        return logits[:bsz], False

    def _segmented_decode_paged(self, last, tables, positions_1d):
        """Safepoint-instrumented paged decode: one jitted dispatch per
        K-layer segment, flag check between dispatches (§4.3).  Pool writes
        of an aborted attempt sit at the uncommitted position and are
        overwritten verbatim on re-execution."""
        x = tf.embed(self.cfg, self.params, last[:, None])
        positions = positions_1d[:, None]

        def seg(lo, pps, h):
            h, self.pools = self._segment_jit(
                pps, self.params, np.int32(lo), h, self.pools, tables,
                positions,
            )
            return h

        x, aborted = self._run_segments(x, seg, "segment", True)
        if aborted:
            return None, True
        logits = tf.lm_head(self.cfg, self.params, x)[:, 0, :]
        return logits, False

    def _decode_contiguous(self, reqs: List[Request], use_safepoints: bool):
        stacked = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=1),
            *[self.caches[r.request_id] for r in reqs],
        )
        last = jnp.asarray([self._tokens_of(r)[-1] for r in reqs], jnp.int32)
        lens = jnp.asarray([r.total_len - 1 for r in reqs], jnp.int32)
        if use_safepoints:
            logits, stacked, aborted = self._segmented_decode(stacked, last, lens)
            if aborted:
                return None, True
        else:
            logits, stacked = self._decode_jit(
                self.params, last, stacked, lens
            )
        for i, r in enumerate(reqs):
            self.caches[r.request_id] = jax.tree.map(
                lambda x, i=i: x[:, i : i + 1], stacked
            )
        return logits, False

    def _segmented_decode(self, stacked, last, lens):
        """Safepoint-instrumented decode: one jitted dispatch per K-layer
        segment, flag check between dispatches (§4.3)."""
        x = tf.embed(self.cfg, self.params, last[:, None])
        positions = lens[:, None]
        state = {"x": x, "caches": stacked}
        nseg = tf.num_segments(self.cfg)

        def make_seg(i):
            def run():
                state["x"], state["caches"] = self._segment_jit(
                    i, self.params, state["x"], state["caches"], positions
                )

            return run

        completed, _done = self.safepoints.run(
            [make_seg(i) for i in range(nseg)],
            preemptible=True,
            on_safepoint=self._on_safepoint,
        )
        if not completed:
            self.flag.clear()
            return None, stacked, True
        logits = tf.lm_head(self.cfg, self.params, state["x"])[:, 0, :]
        return logits, state["caches"], False

    # ----------------------------------------------------------- calibration
    def calibrate(
        self, grid: Optional[CalibrationGrid] = None
    ) -> MeasuredProfiler:
        """On-device calibration pass (DESIGN.md §10).

        Times the engine's *own* jitted entry points — on the fused paged
        path (DESIGN.md §12) every probe is a fused ragged dispatch:
        pure-prefill and pure-decode compositions over the classic grid
        axes, plus mixed chunk+decode probes at
        ``CalibrationGrid.token_buckets`` so the profiler prices mixed
        batches directly; on the split paths, prefill chunks at the
        scheduler's chunk size and decode batches at the power-of-two
        bucket sizes the jit cache is keyed on — fits a
        ``MeasuredProfiler``, and
        installs it as the scheduler's latency model so ``calc_budget``
        token budgets reflect measured wall time on this machine instead of
        the analytical roofline.  Also doubles as a jit warm-up: every shape
        it times is a shape serving will dispatch, so compilation happens
        here rather than on the first online request.

        Probe batches address only the scratch pool row (paged) or throwaway
        caches (contiguous), so calibration never perturbs live KV.  The
        contiguous path's decode timings include per-call cache allocation
        (donated buffers can't be reused), slightly overestimating — the
        conservative direction for SLO budgets.
        """
        if not self.cfg.causal:
            raise ValueError("calibration requires a causal decoder arch")
        if grid is None:
            # every chunk bucket the scheduler can produce (lengths are
            # min(remaining, chunk_size, budget-room) -> buckets 8..chunk)
            top = self._chunk_bucket(
                min(self.sched.sc.chunk_size, self.ec.max_model_len)
            )
            chunks, c = [], 8
            while c <= top:
                chunks.append(c)
                c *= 2
            chunks = tuple(chunks)
            # warm/measure every batch bucket serving can dispatch — decode
            # pads to _decode_bucket(<= max_batch_seqs), prefill groups to
            # _decode_bucket(<= max_prefill_batch) — so the request path
            # never compiles (DESIGN.md §10)
            buckets, b = [], 1
            while b <= self._decode_bucket(self.sched.sc.max_batch_seqs):
                buckets.append(b)
                b *= 2
            pbatches, b = [], 1
            while b <= self._decode_bucket(max(1, self.ec.max_prefill_batch)):
                pbatches.append(b)
                b *= 2
            # fused engines additionally sample mixed ragged dispatches at
            # the token buckets past one chunk (a chunk plus decode rows),
            # the shapes only the fused path can execute (DESIGN.md §12)
            tok0 = pow2_bucket(top + 1)
            grid = CalibrationGrid(
                chunk_sizes=chunks,
                prefill_batches=tuple(pbatches) if self.paged else (1,),
                decode_buckets=tuple(buckets),
                token_buckets=(tok0, 2 * tok0) if self.fused else (),
                # pipelined engines serve back-to-back enqueues, so the
                # profile must price that steady state, not the serial
                # enqueue->block->enqueue path they never run (§13)
                pipeline_depth=4 if self.pipeline else 1,
            )

        def timed(fn) -> float:
            for _ in range(grid.warmup):
                fn()
            best = float("inf")
            for _ in range(grid.repeats):
                t0 = time.perf_counter()
                fn()
                best = min(best, time.perf_counter() - t0)
            return best

        max_ctx = self.ec.max_model_len
        fused_timer = None
        if self.paged and self.fused:
            # Fused engine (DESIGN.md §12): every serve-time program is a
            # fused ragged dispatch, so the timers probe exactly those —
            # pure-prefill and pure-decode compositions reuse the classic
            # grid axes, and `fused_timer` adds the mixed points the split
            # paths cannot express.  Probes address only the scratch row.
            scratch = self._scratch_block

            def _probe(items) -> Callable[..., Any]:
                toks, tables, positions, meta, li = self._fused_inputs(
                    self._build_ragged(items)
                )
                spans = tf.segment_spans(self.cfg)

                def once(block: bool = True):
                    x = tf.embed(self.cfg, self.params, toks[None])
                    if self.pipeline:
                        for i, (lo, pps) in enumerate(spans):
                            old = self._pool_segs[i]
                            x, self._pool_segs[i] = (
                                self._fused_segment_seg_jit(
                                    pps, self.params, np.int32(lo), x, old,
                                    tables, positions, meta,
                                )
                            )
                            self._retired.append((x, old))
                    else:
                        for lo, pps in spans:
                            x, self.pools = self._fused_segment_jit(
                                pps, self.params, np.int32(lo), x,
                                self.pools, tables, positions, meta,
                            )
                    out = self._fused_logits_jit(self.params, x, li)
                    if block:
                        jax.block_until_ready(out)
                        self._drop_retired()
                    return out

                return once

            def timed_fused(once) -> float:
                """Time one fused probe at ``grid.pipeline_depth``.  Depth 1
                is the serial engine's enqueue->block cadence; depth > 1
                enqueues that many iterations back-to-back and blocks once
                at the end, so the per-iteration figure prices the
                *pipelined steady state* — host gaps overlapped with device
                compute — which is what the pipelined engine's scheduler
                budgets must reflect (DESIGN.md §13)."""
                depth = max(1, grid.pipeline_depth)
                if depth == 1:
                    return timed(once)
                for _ in range(grid.warmup):
                    once()
                best = float("inf")
                for _ in range(grid.repeats):
                    t0 = time.perf_counter()
                    out = None
                    for _ in range(depth):
                        out = once(block=False)
                    jax.block_until_ready(out)
                    best = min(best, (time.perf_counter() - t0) / depth)
                return best

            def prefill_timer(b: int, c: int) -> float:
                b = self._decode_bucket(b)
                c = self._chunk_bucket(min(c, max_ctx))
                return timed_fused(_probe([(c, 0, None, None)] * b))

            def decode_timer(b: int, ctx: int) -> float:
                ctx = max(1, min(ctx, max_ctx - 1))
                return timed_fused(_probe([(1, ctx, None, None)] * b))

            def fused_timer(tok: int, kv: int):
                c = min(self.sched.sc.chunk_size, max_ctx, tok)
                # decode rows fill the token bucket, but never beyond the
                # sequence count a real plan can contain — probing S-shapes
                # past max_batch_seqs would compile (and on the CPU oracle,
                # materialize) batches serving can never dispatch
                ndec = max(0, min(tok - c, self.sched.sc.max_batch_seqs - 1))
                items = [(c, 0, None, None)] + [(1, kv, None, None)] * ndec
                shape = BatchShape(
                    prefill_tokens=c,
                    prefill_attn_tokens=c * c / 2.0,
                    prefill_ctx_end=c,
                    decode_tokens=ndec,
                    decode_ctx=ndec * kv,
                    num_seqs=1 + ndec,
                )
                return shape, timed_fused(_probe(items))

            def swap_timer(n: int):
                nbytes = n * block_bytes(self.cfg, self.ec.block_size)
                return nbytes, timed(
                    lambda: self._extract_blocks_paged([scratch] * n)
                )

        elif self.paged:
            width, scratch = self._table_width, self._scratch_block

            def prefill_timer(b: int, c: int) -> float:
                # serve-time dispatches are bucketed in both axes
                b = self._decode_bucket(b)
                c = self._chunk_bucket(c)
                toks = self._put(np.zeros((b, c), np.int32))
                table = self._put(np.full((b, width), scratch, np.int32))
                off = self._put(np.zeros((b,), np.int32))
                last = self._put(np.full((b,), c - 1, np.int32))

                def once():
                    logits, self.pools = self._prefill_jit(
                        self.params, toks, self.pools, table, off, last
                    )
                    jax.block_until_ready(logits)

                return timed(once)

            def decode_timer(b: int, ctx: int) -> float:
                last = self._put(np.zeros((b,), np.int32))
                tables = self._put(np.full((b, width), scratch, np.int32))
                lens = self._put(
                    np.full((b,), min(ctx, max_ctx - 1), np.int32)
                )

                # warm the safepoint-instrumented twin of this bucket (the
                # pure-offline path dispatches per-segment programs)
                x = tf.embed(self.cfg, self.params, last[:, None])
                for lo, pps in tf.segment_spans(self.cfg):
                    x, self.pools = self._segment_jit(
                        pps, self.params, np.int32(lo), x, self.pools,
                        tables, lens[:, None],
                    )
                jax.block_until_ready(x)

                def once():
                    logits, self.pools = self._decode_jit(
                        self.params, last, self.pools, tables, lens
                    )
                    jax.block_until_ready(logits)

                return timed(once)

            def swap_timer(n: int):
                nbytes = n * block_bytes(self.cfg, self.ec.block_size)
                return nbytes, timed(
                    lambda: self._extract_blocks_paged([scratch] * n)
                )

        else:

            def prefill_timer(b: int, c: int) -> float:
                del b  # contiguous prefill is one sequence per dispatch
                toks = jnp.zeros((1, c), jnp.int32)
                off = jnp.zeros((1,), jnp.int32)

                def once():
                    logits, _ = self._prefill_jit(
                        self.params, toks, tf.init_caches(self.cfg, 1, max_ctx),
                        off, None,
                    )
                    jax.block_until_ready(logits)

                return timed(once)

            def decode_timer(b: int, ctx: int) -> float:
                last = jnp.zeros((b,), jnp.int32)
                lens = jnp.full((b,), min(ctx, max_ctx - 1), jnp.int32)

                def once():
                    logits, _ = self._decode_jit(
                        self.params, last, tf.init_caches(self.cfg, b, max_ctx),
                        lens,
                    )
                    jax.block_until_ready(logits)

                return timed(once)

            swap_timer = None

        prof = calibrate(
            prefill_timer, decode_timer, max_ctx, grid, swap_timer,
            fused_timer=fused_timer,
        )
        self.profile = prof
        self.sched.model = prof
        self.sched._sat_cache = None  # saturation knee derives from the model
        return prof

    # ------------------------------------------------------------------ run
    def run(self, max_steps: Optional[int] = None) -> None:
        limit = max_steps or self.ec.max_steps
        for _ in range(limit):
            if not self.step():
                break
        if self.pipeline:
            # a step limit can stop the loop mid-flight; emitted tokens and
            # host-store contents must still be complete (§13)
            self.flush_pipeline()
