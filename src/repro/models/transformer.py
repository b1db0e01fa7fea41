"""Composable decoder/encoder stack for every architecture family.

The layer stack is a ``lax.scan`` over *periods* of the repeating layer
pattern (see ``ModelConfig.layer_pattern``), keeping HLO compact enough to
compile all 40 (arch × shape) dry-run combinations quickly.

Three execution modes share the same per-layer code:

* ``forward_full``   — whole-sequence forward (training / encoding /
                       monolithic prefill); no cache needed, but *can emit*
                       caches+states so it doubles as prefill.
* ``prefill_chunk``  — chunked prefill against existing caches (ConServe
                       uses chunked prefill to bound per-iteration latency).
* ``decode_step``    — one-token decode against caches.

Segmented execution for ConServe's layer-granularity preemption safepoints:
``num_segments``/``run_segment`` splits the period scan into contiguous
groups of ``safepoint_interval`` layers; the serving worker dispatches one
segment at a time and checks the preemption flag between dispatches
(DESIGN.md §3).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import mamba2, moe as moe_mod
from .config import (
    FFN_DENSE,
    FFN_MOE,
    MIXER_ATTN,
    MIXER_CROSS_ATTN,
    MIXER_MAMBA,
    ModelConfig,
)
from .layers import (
    KVCache,
    RaggedMeta,
    cached_attention,
    cross_attention,
    dense_attention,
    init_attention,
    init_mlp,
    mlp,
    paged_decode_attention,
    paged_prefill_attention,
    paged_ragged_attention,
    project_cross_kv,
    rmsnorm,
)

PyTree = Any

# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_period(cfg: ModelConfig, key: jax.Array, dtype) -> Dict[str, PyTree]:
    """Params for one period (all pattern positions)."""
    pp: Dict[str, PyTree] = {}
    pattern = cfg.layer_pattern()
    keys = jax.random.split(key, len(pattern) * 2)
    for i, spec in enumerate(pattern):
        km, kf = keys[2 * i], keys[2 * i + 1]
        layer: Dict[str, PyTree] = {
            "norm1": jnp.ones((cfg.d_model,), dtype),
            "norm2": jnp.ones((cfg.d_model,), dtype),
        }
        if spec.mixer in (MIXER_ATTN, MIXER_CROSS_ATTN):
            layer["mixer"] = init_attention(cfg, km, dtype)
        else:
            layer["mixer"] = mamba2.init_mamba(cfg, km, dtype)
        if spec.ffn == FFN_MOE:
            layer["ffn"] = moe_mod.init_moe(cfg, kf, dtype)
        elif cfg.d_ff:
            layer["ffn"] = init_mlp(cfg, kf, dtype)
        else:  # pure-SSM archs (Mamba-2) have no FFN sublayer
            del layer["norm2"]
        pp[str(i)] = layer
    return pp


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32) -> PyTree:
    ke, kl, kh, kv = jax.random.split(key, 4)
    params: Dict[str, PyTree] = {}
    if cfg.embed_inputs:
        params["embed"] = (
            jax.random.normal(ke, (cfg.vocab_size, cfg.d_model), dtype) * 0.02
        )
    params["final_norm"] = jnp.ones((cfg.d_model,), dtype)
    if not cfg.tie_embeddings or not cfg.embed_inputs:
        params["lm_head"] = (
            jax.random.normal(kh, (cfg.d_model, cfg.vocab_size), dtype)
            * cfg.d_model**-0.5
        )
    if cfg.vision_dim:
        params["vision_proj"] = (
            jax.random.normal(kv, (cfg.vision_dim, cfg.d_model), dtype)
            * cfg.vision_dim**-0.5
        )
    period_keys = jax.random.split(kl, cfg.num_periods)
    params["layers"] = jax.vmap(lambda k: _init_period(cfg, k, dtype))(period_keys)
    return params


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed(cfg: ModelConfig, params: PyTree, inputs: jnp.ndarray) -> jnp.ndarray:
    """tokens (B,T) int -> (B,T,d); or passthrough for embedded inputs."""
    if cfg.embed_inputs:
        return jnp.take(params["embed"], inputs, axis=0)
    return inputs


def lm_head(cfg: ModelConfig, params: PyTree, x: jnp.ndarray) -> jnp.ndarray:
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if "lm_head" in params:
        logits = x @ params["lm_head"]
    else:
        logits = x @ params["embed"].T
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits.astype(jnp.float32)


def project_image_embeds(
    cfg: ModelConfig, params: PyTree, image_embeds: jnp.ndarray
) -> jnp.ndarray:
    return image_embeds @ params["vision_proj"]


# ---------------------------------------------------------------------------
# Cache / state construction
# ---------------------------------------------------------------------------


def cache_capacity(cfg: ModelConfig, max_seq: int) -> int:
    return min(max_seq, cfg.sliding_window) if cfg.sliding_window else max_seq


def supports_paged(cfg: ModelConfig) -> bool:
    """True iff every layer holds plain causal full-attention KV.

    SSM/hybrid recurrent state, sliding-window ring buffers, static
    cross-attn KV and encoder-only archs keep the contiguous per-request
    fallback (capability matrix in DESIGN.md §5)."""
    return (
        cfg.causal
        and not cfg.has_ssm_state
        and not cfg.cross_attn_period
        and not cfg.sliding_window
        and all(s.mixer == MIXER_ATTN for s in cfg.layer_pattern())
    )


def init_paged_pools(
    cfg: ModelConfig,
    num_blocks: int,
    block_size: int,
    dtype=jnp.float32,
) -> Dict[str, PyTree]:
    """Shared physical KV pools, one {"k","v"} pair per pattern position.

    Leaves are (num_periods, num_blocks, block_size, Hkv·D) — the same
    period-major stacking as params/caches, so the period scan and the
    segment slicing helpers apply unchanged.  A page is a lane-dense
    ``(block_size, Hkv·D)`` tile, head-major along the lanes: the chip's
    default layout for this shape is its plain row-major tiling, which is
    what the attention kernel DMAs and the KV scatter writes, so no program
    that takes the pools relays them out (``kernels.paged_attention``).
    Every resident sequence lives in these pools, addressed via block
    tables of physical block ids."""
    if not supports_paged(cfg):
        raise ValueError(f"{cfg.name}: paged pools require plain causal KV")
    width = cfg.num_kv_heads * cfg.resolved_head_dim
    shape = (cfg.num_periods, num_blocks, block_size, width)
    return {
        str(i): {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        for i, _ in enumerate(cfg.layer_pattern())
    }


def constrain_paged_pools(pools: Dict[str, PyTree], mesh) -> Dict[str, PyTree]:
    """Pin the pools' KV-head sharding (DESIGN.md §11).

    Applied at the entry and exit of every paged entry point so GSPMD keeps
    the tensor-parallel layout stable across the period scan and the
    engine's donated-buffer reuse (a drifting output sharding would force a
    reshard copy on every dispatch).  No-op without a mesh."""
    if mesh is None:
        return pools
    from repro.distributed.sharding import pool_shardings

    return jax.tree.map(
        jax.lax.with_sharding_constraint, pools, pool_shardings(pools, mesh)
    )


def init_caches(
    cfg: ModelConfig,
    batch: int,
    max_seq: int,
    dtype=jnp.float32,
) -> Dict[str, PyTree]:
    """Per-pattern-position cache/state pytrees, stacked over periods."""
    caches: Dict[str, PyTree] = {}
    hd = cfg.resolved_head_dim
    np_ = cfg.num_periods
    cap = cache_capacity(cfg, max_seq)

    def stack(tree):
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (np_,) + a.shape), tree)

    for i, spec in enumerate(cfg.layer_pattern()):
        if spec.mixer == MIXER_ATTN:
            caches[str(i)] = stack(
                KVCache.init(batch, cap, cfg.num_kv_heads, hd, dtype)
            )
        elif spec.mixer == MIXER_CROSS_ATTN:
            caches[str(i)] = stack(
                {
                    "ck": jnp.zeros((batch, cfg.num_image_tokens, cfg.num_kv_heads, hd), dtype),
                    "cv": jnp.zeros((batch, cfg.num_image_tokens, cfg.num_kv_heads, hd), dtype),
                }
            )
        else:  # mamba
            st = mamba2.zero_state(cfg, batch, dtype)
            caches[str(i)] = stack({"ssm": st.ssm, "conv": st.conv})
    return caches


# ---------------------------------------------------------------------------
# One layer
# ---------------------------------------------------------------------------


def _apply_layer(
    cfg: ModelConfig,
    spec,
    lp: PyTree,
    x: jnp.ndarray,
    cache: Optional[PyTree],
    *,
    mode: str,  # "full" | "prefill" | "decode"
    positions: jnp.ndarray,
    valid: Optional[jnp.ndarray],
    img_x: Optional[jnp.ndarray],
    capacity_factor: float,
    block_tables: Optional[jnp.ndarray] = None,  # paged physical layout
    ragged: Optional[RaggedMeta] = None,  # fused ragged token batch (§12)
    layer: Optional[jnp.ndarray] = None,  # ragged: index in the pool stack
    mesh=None,  # tensor-parallel serving mesh (paged path only, §11)
) -> Tuple[jnp.ndarray, Optional[PyTree], jnp.ndarray]:
    """Returns (x_out, new_cache, aux_loss).

    Modes:
      full    — whole sequence, no prior context (train / encode / monolithic
                prefill for the dry-run).  Caches, if given, are *emitted*.
      prefill — chunk with prior context in caches (ConServe chunked prefill).
      decode  — one token against caches.
      ragged  — fused mixed token batch on the paged layout (``ragged`` set):
                prefill chunks and decode tokens share one flattened axis;
                ``cache`` is the whole stacked pool, updated in place at
                ``layer``.
    """
    from repro.distributed.act_sharding import (
        constrain_block_input,
        constrain_residual,
    )

    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    if spec.mixer in (MIXER_ATTN, MIXER_CROSS_ATTN):
        # Megatron seq-parallel: gather the sequence at the block entry so
        # GSPMD gathers ~0.1GB activations instead of replicating multi-GB
        # weights (confirmed 3-8x collective cut on dense archs).  Mamba
        # mixers keep the sequence sharded — the SSD chunk scan is local in
        # time and gathering regressed it (refuted, see EXPERIMENTS.md §Perf).
        hd_ = cfg.resolved_head_dim
        attn_w = 2 * cfg.d_model * (cfg.num_heads + 2 * cfg.num_kv_heads) * hd_ * 2
        # heads that don't divide the model axis can't shard: must gather
        from repro.distributed.act_sharding import model_axis_size

        msz = model_axis_size()
        force = bool(msz) and (
            cfg.num_heads % msz != 0 or cfg.num_kv_heads % msz != 0
        )
        h = constrain_block_input(h, weight_bytes=attn_w, force=force)

    if spec.mixer == MIXER_ATTN:
        if block_tables is not None:  # shared paged pool (serving hot path)
            if ragged is not None:  # fused mixed batch (one dispatch, §12)
                mix, new_cache = paged_ragged_attention(
                    cfg, lp["mixer"], h, cache, layer, block_tables,
                    positions, ragged, mesh=mesh,
                )
            else:
                attn_fn = (
                    paged_decode_attention
                    if mode == "decode"
                    else paged_prefill_attention
                )
                mix, new_cache = attn_fn(
                    cfg, lp["mixer"], h, cache, block_tables, positions,
                    mesh=mesh,
                )
        elif mode == "full":
            mix = dense_attention(cfg, lp["mixer"], h, positions)
            new_cache = cache
            if cache is not None:
                # emit prefill caches: write the whole (roped) sequence
                from .layers import apply_rope, project_qkv, write_kv

                _, k, v = project_qkv(cfg, lp["mixer"], h)
                k = apply_rope(k, positions, cfg.rope_theta)
                new_cache = write_kv(cache, k, v, positions, valid)
        else:  # prefill chunk or decode: attend through the cache
            mix, new_cache = cached_attention(
                cfg, lp["mixer"], h, cache, positions, valid
            )
    elif spec.mixer == MIXER_CROSS_ATTN:
        if img_x is not None:  # first chunk / full pass: build static cross KV
            ck, cv = project_cross_kv(cfg, lp["mixer"], img_x)
            new_cache = {"ck": ck, "cv": cv} if cache is not None else cache
        else:
            ck, cv = cache["ck"], cache["cv"]
            new_cache = cache
        mix = cross_attention(cfg, lp["mixer"], h, ck, cv)
    else:  # mamba
        state = (
            mamba2.MambaState(ssm=cache["ssm"], conv=cache["conv"])
            if cache is not None
            else None
        )
        if mode == "decode":
            mix, new_state = mamba2.mamba_decode_step(cfg, lp["mixer"], h, state)
        else:  # full or prefill: chunked SSD with carried state
            mix, new_state = mamba2.mamba_full(cfg, lp["mixer"], h, state)
        new_cache = (
            {"ssm": new_state.ssm, "conv": new_state.conv}
            if cache is not None
            else None
        )
    x = x + mix

    if "ffn" in lp:
        h2 = rmsnorm(x, lp["norm2"], cfg.norm_eps)
        if spec.ffn != FFN_MOE:
            # dense MLPs benefit like attention does
            mlp_w = 3 * cfg.d_model * cfg.d_ff * 2
            h2 = constrain_block_input(h2, weight_bytes=mlp_w)
        else:
            # MoE dispatch must act on SHARDED tokens — the attention block
            # above may have left the residual sequence-gathered, so re-shard
            # before routing (gathered dispatch made every chip route the
            # full batch: +13x FLOPs on Mixtral — refuted).
            h2 = constrain_residual(h2)
        if spec.ffn == FFN_MOE:
            ffn_out, aux = moe_mod.moe_ffn(cfg, lp["ffn"], h2, capacity_factor)
        else:
            ffn_out = mlp(cfg, lp["ffn"], h2)
        x = x + ffn_out
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Period scan
# ---------------------------------------------------------------------------


def run_periods(
    cfg: ModelConfig,
    layer_params: PyTree,  # leaves stacked over (a slice of) periods
    x: jnp.ndarray,
    *,
    mode: str,
    positions: jnp.ndarray,
    caches: Optional[Dict[str, PyTree]] = None,  # leaves stacked same as params
    valid: Optional[jnp.ndarray] = None,
    img_x: Optional[jnp.ndarray] = None,
    capacity_factor: float = 1.25,
    remat: bool = False,
    block_tables: Optional[jnp.ndarray] = None,  # paged: caches are pools
    mesh=None,  # tensor-parallel serving mesh (paged path only, §11)
) -> Tuple[jnp.ndarray, Optional[Dict[str, PyTree]], jnp.ndarray]:
    """Scan the pattern periods. Returns (x, new_caches, total_aux).

    Caches ride the scan as per-period slices in and out, which is a copy
    of them; the fused serving path carries its pools instead
    (``run_layers_in_place``)."""
    pattern = cfg.layer_pattern()

    from repro.distributed.act_sharding import constrain_residual

    def body(carry, per):
        x, aux_tot = carry
        x = constrain_residual(x)  # seq-parallel residual (no-op if inactive)
        lp, cache_in = per
        new_caches = {}
        for i, spec in enumerate(pattern):
            c_in = cache_in[str(i)] if cache_in is not None else None
            x, c_out, aux = _apply_layer(
                cfg,
                spec,
                lp[str(i)],
                x,
                c_in,
                mode=mode,
                positions=positions,
                valid=valid,
                img_x=img_x,
                capacity_factor=capacity_factor,
                block_tables=block_tables,
                mesh=mesh,
            )
            if cache_in is not None:
                new_caches[str(i)] = c_out
        return (x, aux_tot + aux), (new_caches if cache_in is not None else 0)

    fn = jax.checkpoint(body) if remat else body
    (x, aux), new_caches = jax.lax.scan(
        fn, (x, jnp.zeros((), jnp.float32)), (layer_params, caches)
    )
    return x, (new_caches if caches is not None else None), aux


# ---------------------------------------------------------------------------
# Top-level entry points
# ---------------------------------------------------------------------------


def forward_full(
    cfg: ModelConfig,
    params: PyTree,
    inputs: jnp.ndarray,
    *,
    image_embeds: Optional[jnp.ndarray] = None,
    emit_caches: bool = False,
    max_seq: Optional[int] = None,
    capacity_factor: float = 1.25,
    remat: bool = False,
    cache_dtype=None,
) -> Tuple[jnp.ndarray, Optional[Dict[str, PyTree]], jnp.ndarray]:
    """Whole-sequence forward. Returns (logits, caches|None, aux_loss)."""
    x = embed(cfg, params, inputs)
    b, t = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    img_x = (
        project_image_embeds(cfg, params, image_embeds)
        if image_embeds is not None
        else None
    )
    caches = (
        init_caches(cfg, b, max_seq or t, cache_dtype or x.dtype)
        if emit_caches
        else None
    )
    x, caches, aux = run_periods(
        cfg,
        params["layers"],
        x,
        mode="full",
        positions=positions,
        caches=caches,
        img_x=img_x,
        capacity_factor=capacity_factor,
        remat=remat,
    )
    return lm_head(cfg, params, x), caches, aux


def prefill_chunk(
    cfg: ModelConfig,
    params: PyTree,
    tokens: jnp.ndarray,  # (B, L) chunk tokens
    caches: Dict[str, PyTree],
    offsets: jnp.ndarray,  # (B,) tokens already prefilled per sequence
    *,
    lengths: Optional[jnp.ndarray] = None,  # (B,) valid tokens in this chunk
    image_embeds: Optional[jnp.ndarray] = None,
    capacity_factor: float = -1.0,  # dropless by default (path-exact serving)
) -> Tuple[jnp.ndarray, Dict[str, PyTree]]:
    """Chunked prefill. Returns (last-token logits (B,V), new caches).

    Mamba layers run the chunked SSD with carried state; attention layers
    attend through the KV cache (exact for chunk_size <= sliding_window).

    NOTE: for SSM/hybrid archs, ragged chunks (``lengths`` set with padding)
    would contaminate the recurrent state — the serving engine therefore
    prefills SSM sequences unpadded (per-sequence chunks).
    """
    if lengths is not None and cfg.has_ssm_state:
        raise ValueError("ragged chunked prefill unsupported for SSM layers")
    x = embed(cfg, params, tokens)
    b, l = tokens.shape[:2]
    positions = offsets[:, None] + jnp.arange(l, dtype=jnp.int32)[None, :]
    valid = (
        jnp.arange(l)[None, :] < lengths[:, None]
        if lengths is not None
        else None
    )
    img_x = (
        project_image_embeds(cfg, params, image_embeds)
        if image_embeds is not None
        else None
    )
    x, caches, _ = run_periods(
        cfg,
        params["layers"],
        x,
        mode="prefill",
        positions=positions,
        caches=caches,
        valid=valid,
        img_x=img_x,
        capacity_factor=capacity_factor,
    )
    logits = lm_head(cfg, params, x)  # (B, L, V)
    if lengths is not None:
        last_idx = jnp.maximum(lengths - 1, 0)
    else:
        last_idx = jnp.full((b,), l - 1, jnp.int32)
    last_logits = jnp.take_along_axis(
        logits, last_idx[:, None, None], axis=1
    )[:, 0, :]
    return last_logits, caches


def decode_step(
    cfg: ModelConfig,
    params: PyTree,
    last_tokens: jnp.ndarray,  # (B,) int32
    caches: Dict[str, PyTree],
    seq_lens: jnp.ndarray,  # (B,) current lengths (new token position)
    *,
    capacity_factor: float = -1.0,  # dropless by default (path-exact serving)
) -> Tuple[jnp.ndarray, Dict[str, PyTree]]:
    """One decode iteration. Returns (logits (B,V), new caches)."""
    x = embed(cfg, params, last_tokens[:, None])
    positions = seq_lens[:, None]
    x, caches, _ = run_periods(
        cfg,
        params["layers"],
        x,
        mode="decode",
        positions=positions,
        caches=caches,
        capacity_factor=capacity_factor,
    )
    return lm_head(cfg, params, x)[:, 0, :], caches


# ---------------------------------------------------------------------------
# Paged entry points (shared block pool; see init_paged_pools)
# ---------------------------------------------------------------------------


def prefill_chunk_paged(
    cfg: ModelConfig,
    params: PyTree,
    tokens: jnp.ndarray,  # (B, L) chunk tokens (L may be bucket-padded)
    pools: Dict[str, PyTree],
    block_tables: jnp.ndarray,  # (B, M) physical block ids
    offsets: jnp.ndarray,  # (B,) tokens already prefilled per sequence
    last_index: Optional[jnp.ndarray] = None,  # (B,) logits position
    mesh=None,  # tensor-parallel serving mesh (DESIGN.md §11)
) -> Tuple[jnp.ndarray, Dict[str, PyTree]]:
    """Chunked prefill on the paged layout. Returns (last logits, pools).

    ``last_index`` supports *bucketed* chunks: the engine pads chunk tokens
    to a power-of-two length (bounding jit retraces exactly like decode
    bucketing) and asks for the logits of the last real token.  Padded
    positions write junk KV only into slots that are overwritten when the
    real tokens arrive, or into the scratch row / clamped tail — never read
    before being rewritten (DESIGN.md §7 garbage tolerance).
    """
    pools = constrain_paged_pools(pools, mesh)
    x = embed(cfg, params, tokens)
    b, l = tokens.shape[:2]
    positions = offsets[:, None] + jnp.arange(l, dtype=jnp.int32)[None, :]
    x, pools, _ = run_periods(
        cfg,
        params["layers"],
        x,
        mode="prefill",
        positions=positions,
        caches=pools,
        block_tables=block_tables,
        capacity_factor=-1.0,
        mesh=mesh,
    )
    pools = constrain_paged_pools(pools, mesh)
    if last_index is None:
        xl = x[:, -1:, :]
    else:
        xl = jax.vmap(
            lambda xi, li: jax.lax.dynamic_slice_in_dim(xi, li, 1, axis=0)
        )(x, last_index)
    return lm_head(cfg, params, xl)[:, 0, :], pools


def decode_step_paged(
    cfg: ModelConfig,
    params: PyTree,
    last_tokens: jnp.ndarray,  # (B,) int32
    pools: Dict[str, PyTree],
    block_tables: jnp.ndarray,  # (B, M)
    seq_lens: jnp.ndarray,  # (B,) current lengths (new token position)
    mesh=None,  # tensor-parallel serving mesh (DESIGN.md §11)
) -> Tuple[jnp.ndarray, Dict[str, PyTree]]:
    """One decode iteration on the paged layout. Returns (logits, pools)."""
    pools = constrain_paged_pools(pools, mesh)
    x = embed(cfg, params, last_tokens[:, None])
    positions = seq_lens[:, None]
    x, pools, _ = run_periods(
        cfg,
        params["layers"],
        x,
        mode="decode",
        positions=positions,
        caches=pools,
        block_tables=block_tables,
        capacity_factor=-1.0,
        mesh=mesh,
    )
    return lm_head(cfg, params, x)[:, 0, :], constrain_paged_pools(pools, mesh)


def run_segment_paged(
    cfg: ModelConfig,
    params: PyTree,
    seg: int,
    x: jnp.ndarray,
    pools: Dict[str, PyTree],
    block_tables: jnp.ndarray,
    positions: jnp.ndarray,
    mesh=None,  # tensor-parallel serving mesh (DESIGN.md §11)
) -> Tuple[jnp.ndarray, Dict[str, PyTree]]:
    """One preemptible decode segment on the paged layout (paper §4.3
    safepoints), addressed by static segment index.

    Pool writes of an aborted iteration land at the not-yet-committed
    position and are overwritten verbatim on re-execution, so aborts stay
    stateless exactly as in the contiguous path."""
    lo, hi = segment_bounds(cfg, seg)
    lp = slice_periods(params["layers"], lo, hi)
    ps = slice_periods(constrain_paged_pools(pools, mesh), lo, hi)
    x, ps_new, _ = run_periods(
        cfg,
        lp,
        x,
        mode="decode",
        positions=positions,
        caches=ps,
        block_tables=block_tables,
        capacity_factor=-1.0,
        mesh=mesh,
    )
    return x, constrain_paged_pools(
        merge_periods(pools, ps_new, lo, hi), mesh
    )


def run_segment_paged_at(
    cfg: ModelConfig,
    params: PyTree,
    seg_periods: int,  # periods in this segment (STATIC under jit)
    lo: jnp.ndarray,  # starting period (traced)
    x: jnp.ndarray,
    pools: Dict[str, PyTree],
    block_tables: jnp.ndarray,
    positions: jnp.ndarray,
    mesh=None,  # tensor-parallel serving mesh (DESIGN.md §11)
) -> Tuple[jnp.ndarray, Dict[str, PyTree]]:
    """``run_segment_paged`` with a *traced* starting period.

    Jitting the static-index variant compiles one program per segment; with
    the start traced, every segment of the same length shares a single
    compiled program, so the safepoint-instrumented decode costs at most
    two compilations per batch bucket (body segments + a shorter tail)
    instead of ``num_segments`` — the same bounded-retrace idea as the
    decode/prefill shape buckets (DESIGN.md §5)."""
    pools = constrain_paged_pools(pools, mesh)
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, lo, seg_periods, axis=0)
    lp = jax.tree.map(sl, params["layers"])
    ps = jax.tree.map(sl, pools)
    x, ps_new, _ = run_periods(
        cfg,
        lp,
        x,
        mode="decode",
        positions=positions,
        caches=ps,
        block_tables=block_tables,
        capacity_factor=-1.0,
        mesh=mesh,
    )
    merged = jax.tree.map(
        lambda a, u: jax.lax.dynamic_update_slice_in_dim(a, u, lo, axis=0),
        pools,
        ps_new,
    )
    return x, constrain_paged_pools(merged, mesh)


# ---------------------------------------------------------------------------
# Fused ragged token-batch entry points (DESIGN.md §12)
#
# These supersede prefill_chunk_paged / decode_step_paged on the serving hot
# path: the scheduler's whole IterationPlan — prefill chunks AND decode
# tokens, online and offline alike — lowers to one flattened ragged batch
# and executes as a single dispatch per K-layer segment.  The split entry
# points above remain the differential oracle (RealEngineConfig.fused_batch
# = False).
# ---------------------------------------------------------------------------


def run_layers_in_place(
    cfg: ModelConfig,
    layers: PyTree,  # the n periods' weights, leaves stacked (n, ...)
    x: jnp.ndarray,  # (1, T, d) flattened ragged activations
    pools: Dict[str, PyTree],  # stacked (P', N, bs, Hkv·D) leaves
    p_lo: jnp.ndarray,  # index of the first period's KV in the pool stack
    block_tables: jnp.ndarray,  # (S, M)
    positions: jnp.ndarray,  # (1, T)
    meta: RaggedMeta,
    mesh=None,
) -> Tuple[jnp.ndarray, Dict[str, PyTree]]:
    """The fused ragged batch through the periods of ``layers``, updating
    the donated pools in place.

    The pools are loop state, not scanned slices: period ``i`` scatters
    its new K/V at ``(p_lo + i, row, slot)`` of the carried stack, and the
    attention kernel takes ``p_lo + i`` as a scalar-prefetch index and DMAs
    that layer's pages straight out of it.  No period slice is cut or
    written back around the loop and no per-layer slice is materialized,
    so the program moves no pool bytes it neither writes nor attends to."""
    pattern = cfg.layer_pattern()

    from repro.distributed.act_sharding import constrain_residual

    def body(i, carry):
        x, pools = carry
        x = constrain_residual(x)  # seq-parallel residual (no-op if inactive)
        lp = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), layers
        )
        new = {}
        for j, spec in enumerate(pattern):
            x, new[str(j)], _ = _apply_layer(
                cfg,
                spec,
                lp[str(j)],
                x,
                pools[str(j)],
                mode="ragged",
                positions=positions,
                valid=None,
                img_x=None,
                capacity_factor=-1.0,
                block_tables=block_tables,
                ragged=meta,
                layer=p_lo + i,
                mesh=mesh,
            )
        return x, new

    n = jax.tree.leaves(layers)[0].shape[0]
    return jax.lax.fori_loop(0, n, body, (x, pools))


def _segment_layers(params: PyTree, lo: jnp.ndarray, n: int) -> PyTree:
    """The weights of periods ``[lo, lo + n)``, cut once per segment."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, lo, n, axis=0),
        params["layers"],
    )


def run_tokens_paged(
    cfg: ModelConfig,
    params: PyTree,
    tokens: jnp.ndarray,  # (T,) flattened ragged token batch (bucket-padded)
    pools: Dict[str, PyTree],
    block_tables: jnp.ndarray,  # (S, M) physical block ids per sequence
    positions: jnp.ndarray,  # (T,) absolute position of each flat token
    meta: RaggedMeta,
    logit_index: jnp.ndarray,  # (S,) flat index of each sequence's last token
    mesh=None,  # tensor-parallel serving mesh (DESIGN.md §11)
) -> Tuple[jnp.ndarray, Dict[str, PyTree]]:
    """Whole-stack fused mixed-batch forward. Returns ((S, V) logits, pools).

    One call executes an entire iteration plan: each sequence contributes
    ``q_len`` consecutive flat tokens (a prefill chunk, or exactly one
    decode token), every layer scatters the new KV into the shared pool
    and runs the single ragged paged-attention op, and the logits of each
    sequence's last real token come back for sampling."""
    pools = constrain_paged_pools(pools, mesh)
    x = embed(cfg, params, tokens[None])
    x, pools = run_layers_in_place(
        cfg, params["layers"], x, pools, jnp.int32(0), block_tables,
        positions[None], meta, mesh=mesh,
    )
    pools = constrain_paged_pools(pools, mesh)
    return ragged_lm_head(cfg, params, x, logit_index), pools


def run_tokens_paged_at(
    cfg: ModelConfig,
    params: PyTree,
    seg_periods: int,  # periods in this segment (STATIC under jit)
    lo: jnp.ndarray,  # starting period (traced)
    x: jnp.ndarray,  # (1, T, d) flattened ragged activations
    pools: Dict[str, PyTree],
    block_tables: jnp.ndarray,  # (S, M)
    positions: jnp.ndarray,  # (1, T)
    meta: RaggedMeta,
    mesh=None,  # tensor-parallel serving mesh (DESIGN.md §11)
) -> Tuple[jnp.ndarray, Dict[str, PyTree]]:
    """One K-layer segment of the fused ragged batch on the whole donated
    pool, with a *traced* starting period — the fused twin of
    ``run_segment_paged_at``: all equal-length segments share one compiled
    program, so the engine's safepoint-instrumented fused iteration costs
    at most two compilations per (token, sequence, query-length) bucket
    triple.  The segment's layers update the pool in place at periods
    ``lo ..`` (``run_layers_in_place``).  Pool writes of an aborted
    iteration land at not-yet-committed positions and are rewritten
    verbatim on re-execution (§12 abort soundness)."""
    pools = constrain_paged_pools(pools, mesh)
    x, pools = run_layers_in_place(
        cfg, _segment_layers(params, lo, seg_periods), x, pools, lo,
        block_tables, positions, meta, mesh=mesh,
    )
    return x, constrain_paged_pools(pools, mesh)


def run_tokens_paged_seg(
    cfg: ModelConfig,
    params: PyTree,
    seg_periods: int,  # periods in this segment (STATIC under jit)
    lo: jnp.ndarray,  # starting period (traced)
    x: jnp.ndarray,  # (1, T, d) flattened ragged activations
    pool_seg: Dict[str, PyTree],  # THIS segment's period slice of the pools
    block_tables: jnp.ndarray,  # (S, M)
    positions: jnp.ndarray,  # (1, T)
    meta: RaggedMeta,
    mesh=None,
) -> Tuple[jnp.ndarray, Dict[str, PyTree]]:
    """One K-layer segment operating on *its own period slice* of the
    pools: takes the slice, returns the updated slice.  The same in-place
    loop as the whole-pool form, with the weights read from period ``lo``
    on and the slice's own layers from 0.

    Segments partition the period axis, so a segment never reads another
    segment's slice — keeping the pools permanently split per segment
    (the pipelined engine, DESIGN.md §13) is bitwise identical to the
    whole-pool form above.  The payoff is donation that composes with
    async dispatch: each slice is donated to the segment that owns it,
    whose previous donation hold (the same segment, one iteration ago)
    has long retired by the time the host enqueues — so the host never
    stalls on the CPU client's donation holds.  Both forms update their
    donated pool in place."""
    pool_seg = constrain_paged_pools(pool_seg, mesh)
    x, pool_seg = run_layers_in_place(
        cfg, _segment_layers(params, lo, seg_periods), x, pool_seg,
        jnp.int32(0), block_tables, positions, meta, mesh=mesh,
    )
    return x, constrain_paged_pools(pool_seg, mesh)


def ragged_lm_head(
    cfg: ModelConfig,
    params: PyTree,
    x: jnp.ndarray,  # (1, T, d) flattened ragged activations
    logit_index: jnp.ndarray,  # (S,)
) -> jnp.ndarray:
    """Logits of each sequence's last real token: gather S rows out of the
    flat axis first, so the LM head prices O(S·V), not O(T·V)."""
    xl = jnp.take(x[0], logit_index, axis=0)[:, None, :]
    return lm_head(cfg, params, xl)[:, 0, :]


def inject_sampled(
    tokens: jnp.ndarray,  # (T,) flat ragged token batch (padded)
    idx: jnp.ndarray,  # (R,) flat slots to overwrite
    sampled: jnp.ndarray,  # (B,) last iteration's sampled tokens (padded)
    rows: jnp.ndarray,  # (R,) row of each slot's value within `sampled`
) -> jnp.ndarray:
    """Deferred-token injection for the pipelined engine (DESIGN.md §13).

    A speculatively built ragged batch cannot know the token values the
    in-flight iteration is still computing — each affected decode slot is
    built with a placeholder, and this one device-side scatter resolves
    them from the previous iteration's sampled-token buffer without any
    host round-trip.  ``idx``/``rows`` pad by *repeating* a real pair
    (never a reserved slot: a full batch has no spare token row), which is
    idempotent under ``.at[].set``.
    """
    return tokens.at[idx].set(jnp.take(sampled, rows, axis=0))


# ---------------------------------------------------------------------------
# Segmented execution (ConServe preemption safepoints)
# ---------------------------------------------------------------------------


def num_segments(cfg: ModelConfig) -> int:
    period = cfg.pattern_period
    periods_per_seg = max(1, cfg.safepoint_interval // period)
    return math.ceil(cfg.num_periods / periods_per_seg)


def segment_bounds(cfg: ModelConfig, seg: int) -> Tuple[int, int]:
    period = cfg.pattern_period
    pps = max(1, cfg.safepoint_interval // period)
    lo = seg * pps
    hi = min(cfg.num_periods, lo + pps)
    return lo, hi


def segment_spans(cfg: ModelConfig) -> list:
    """``(lo, periods)`` per segment — the dispatch list consumed by the
    traced-start segment entry (``run_segment_paged_at``)."""
    spans = []
    for s in range(num_segments(cfg)):
        lo, hi = segment_bounds(cfg, s)
        spans.append((lo, hi - lo))
    return spans


def slice_periods(tree: PyTree, lo: int, hi: int) -> PyTree:
    return jax.tree.map(lambda a: a[lo:hi], tree)


def merge_periods(tree: PyTree, update: PyTree, lo: int, hi: int) -> PyTree:
    return jax.tree.map(
        lambda a, u: a.at[lo:hi].set(u), tree, update
    )


def run_segment(
    cfg: ModelConfig,
    params: PyTree,
    seg: int,
    x: jnp.ndarray,
    caches: Optional[Dict[str, PyTree]],
    *,
    mode: str,
    positions: jnp.ndarray,
    valid: Optional[jnp.ndarray] = None,
    capacity_factor: float = 1.25,
) -> Tuple[jnp.ndarray, Optional[Dict[str, PyTree]]]:
    """Run one preemptible segment (periods [lo, hi))."""
    lo, hi = segment_bounds(cfg, seg)
    lp = slice_periods(params["layers"], lo, hi)
    cs = slice_periods(caches, lo, hi) if caches is not None else None
    x, cs_new, _ = run_periods(
        cfg,
        lp,
        x,
        mode=mode,
        positions=positions,
        caches=cs,
        valid=valid,
        capacity_factor=capacity_factor,
    )
    new_caches = (
        merge_periods(caches, cs_new, lo, hi) if caches is not None else None
    )
    return x, new_caches
