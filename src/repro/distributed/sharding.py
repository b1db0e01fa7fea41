"""Per-architecture sharding rules for the production meshes.

Axes: ``model`` = tensor-parallel (Megatron-style: attention heads / d_ff /
expert-inner dims), ``data`` = batch / FSDP weight-shard axis, ``pod`` =
outer data-parallel axis on the 2-pod mesh (batch + FSDP extend over
``("pod", "data")``).

Rules are name-based over the parameter pytree paths with divisibility
checks; anything that doesn't divide cleanly is replicated (GSPMD handles
mixed sharding).  Training (and serving of models whose TP-sharded weights
would overflow a v5e's 16 GB HBM) additionally shards weights over the FSDP
axis — GSPMD then all-gathers each scanned layer group, which shows up
honestly in the roofline's collective term.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

HBM_BYTES = 16e9  # TPU v5e
FSDP_WEIGHT_THRESHOLD = 12e9  # shard weights over data axis beyond this/chip


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def mesh_axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def fsdp_axes(mesh: Mesh):
    return batch_axes(mesh)


def _assign(
    shape: Sequence[int],
    mesh: Mesh,
    model_dims: Sequence[int],
    fsdp_dim: Optional[int],
    use_fsdp: bool,
) -> P:
    """Build a PartitionSpec: first divisible model-dim candidate gets the
    ``model`` axis; ``fsdp_dim`` gets the (pod,)data axes when enabled."""
    spec: list = [None] * len(shape)
    msize = mesh_axis_size(mesh, "model")
    taken = None
    for d in model_dims:
        if d < len(shape) and shape[d] % msize == 0 and shape[d] > 0:
            spec[d] = "model"
            taken = d
            break
    if use_fsdp and fsdp_dim is not None and fsdp_dim != taken:
        fax = fsdp_axes(mesh)
        fsize = mesh_axis_size(mesh, fax)
        if fsdp_dim < len(shape) and shape[fsdp_dim] % fsize == 0:
            spec[fsdp_dim] = fax if len(fax) > 1 else fax[0]
    return P(*spec)


def _leaf_name(path) -> str:
    for p in reversed(path):
        k = getattr(p, "key", None)
        if isinstance(k, str):
            return k
    return ""


# param rules: name -> (model-dim candidates, fsdp dim), indices are for the
# STACKED leaf (leading period axis) unless the param is top-level.
_STACKED_RULES = {
    # attention: shard the HEAD-count dim only.  head_dim is minor in the
    # (d, H*hd) 2D-projection reshape, so an hd-sharded weight forces a
    # full gather at every use (yi-34b decode: +28 GB/step — §Perf #3);
    # indivisible head counts replicate instead (qwen2 14Q/2KV).
    "wq": ((2,), 1),
    "wk": ((2,), 1),
    "wv": ((2,), 1),
    "wo": ((1,), 3),
    "w_up": ((-1,), -2),
    "w_gate": ((-1,), -2),
    "w_down": ((-2,), -1),
    "router": ((), None),
    "in_proj": ((2,), 1),
    "out_proj": ((1,), 2),
    "conv_w": ((2,), None),
}
_TOP_RULES = {
    "embed": ((0,), 1),
    "lm_head": ((1,), 0),
    "vision_proj": ((1,), None),
}


def _norm_dims(rule, ndim) -> Tuple[Tuple[int, ...], Optional[int]]:
    model_dims, fsdp = rule
    md = tuple(d % ndim for d in model_dims)
    fd = None if fsdp is None else fsdp % ndim
    return md, fd


def param_pspec(path, leaf, mesh: Mesh, use_fsdp: bool) -> P:
    name = _leaf_name(path)
    keys = [getattr(p, "key", None) for p in path]
    stacked = "layers" in keys
    shape = leaf.shape
    if name in _TOP_RULES and not stacked:
        md, fd = _norm_dims(_TOP_RULES[name], len(shape))
        return _assign(shape, mesh, md, fd, use_fsdp)
    if stacked and name in _STACKED_RULES:
        if name in ("w_up", "w_gate", "w_down") and len(shape) == 4:
            # MoE expert weights (P, E, d, f): EXPERT-parallel — shard E over
            # `model` (each chip owns E/16 experts; token routing becomes an
            # all-to-all instead of replicated scatter + all-reduce,
            # §Perf hillclimb #2: 11x collective reduction on OLMoE-64e).
            # Requires >=2 experts per chip — at exactly 1 (Jamba-16e) GSPMD
            # replicated the dispatch compute (+10x FLOPs, refuted) — and
            # falls back to inner-dim TP otherwise (Mixtral's 8 experts).
            inner = 3 if name != "w_down" else 2
            outer = 2 if name != "w_down" else 3  # d_model dim (FSDP)
            msize = mesh_axis_size(mesh, "model")
            if shape[1] >= 2 * msize and shape[1] % msize == 0:
                md, fd = _norm_dims(((1,), outer), len(shape))
            else:
                md, fd = _norm_dims(((inner,), outer), len(shape))
            return _assign(shape, mesh, md, fd, use_fsdp)
        md, fd = _norm_dims(_STACKED_RULES[name], len(shape))
        return _assign(shape, mesh, md, fd, use_fsdp)
    return P()  # norms, biases, scalars: replicate


def params_weight_bytes(params_spec: PyTree) -> int:
    return sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree.leaves(params_spec)
    )


def params_shardings(
    params_spec: PyTree, mesh: Mesh, *, force_fsdp: Optional[bool] = None
) -> PyTree:
    """NamedShardings for the parameter pytree (pass eval_shape output)."""
    if force_fsdp is None:
        tp = mesh_axis_size(mesh, "model")
        per_chip = params_weight_bytes(params_spec) / tp
        use_fsdp = per_chip > FSDP_WEIGHT_THRESHOLD
    else:
        use_fsdp = force_fsdp
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, param_pspec(path, leaf, mesh, use_fsdp)
        ),
        params_spec,
    )


def opt_state_shardings(params_shardings_tree: PyTree, mesh: Mesh):
    """AdamW state: mu/nu shard like params; step replicated."""
    from repro.training.optimizer import AdamWState

    return AdamWState(
        step=NamedSharding(mesh, P()),
        mu=params_shardings_tree,
        nu=params_shardings_tree,
    )


# ---------------------------------------------------------------------------
# activations / batches / caches
# ---------------------------------------------------------------------------


def _batched(shape, mesh: Mesh, extra: dict = {}) -> P:
    """Shard dim0 over the batch axes when divisible; ``extra`` maps
    dim -> axis candidates applied when divisible."""
    bax = batch_axes(mesh)
    spec: list = [None] * len(shape)
    if shape and shape[0] % mesh_axis_size(mesh, bax) == 0 and shape[0] > 1:
        spec[0] = bax if len(bax) > 1 else bax[0]
    for d, axes in extra.items():
        if spec[d] is None and shape[d] % mesh_axis_size(mesh, axes) == 0:
            spec[d] = axes
    return P(*spec)


def batch_shardings(batch_spec: PyTree, mesh: Mesh) -> PyTree:
    return jax.tree.map(
        lambda l: NamedSharding(mesh, _batched(l.shape, mesh)), batch_spec
    )


def cache_pspec(path, leaf, mesh: Mesh) -> P:
    """Caches are stacked (num_periods, B, ...).

    * KV k/v (P, B, C, Hkv, D): batch over data; heads (or head_dim) over
      model; batch=1 (long-context) falls back to sharding the sequence/slot
      dim C over data — context-parallel decode.
    * pos (P, B, C): follow k/v's B/C choice.
    * ssm (P, B, nh, hd, ds) / conv (P, B, W, C'): batch over data when
      divisible, heads/channels over model.
    * cross ck/cv (P, B, Pimg, Hkv, D): like KV without the C fallback.
    """
    name = _leaf_name(path)
    shape = leaf.shape
    bax = batch_axes(mesh)
    bsize = mesh_axis_size(mesh, bax)
    msize = mesh_axis_size(mesh, "model")
    spec: list = [None] * len(shape)
    b_ok = len(shape) > 1 and shape[1] % bsize == 0 and shape[1] > 1
    bspec = bax if len(bax) > 1 else bax[0]
    if name in ("k", "v"):
        if b_ok:
            spec[1] = bspec
        elif shape[2] % bsize == 0:
            spec[2] = bspec  # context-parallel KV for batch=1 long decode
        if shape[3] % msize == 0:
            spec[3] = "model"
        elif shape[4] % msize == 0:
            spec[4] = "model"
    elif name == "pos":
        if b_ok:
            spec[1] = bspec
        elif shape[2] % bsize == 0:
            spec[2] = bspec
    elif name in ("ck", "cv"):
        if b_ok:
            spec[1] = bspec
        if shape[3] % msize == 0:
            spec[3] = "model"
        elif shape[4] % msize == 0:
            spec[4] = "model"
    elif name == "ssm":
        if b_ok:
            spec[1] = bspec
        if shape[2] % msize == 0:
            spec[2] = "model"
    elif name == "conv":
        if b_ok:
            spec[1] = bspec
        if shape[3] % msize == 0:
            spec[3] = "model"
    return P(*spec)


def cache_shardings(cache_spec: PyTree, mesh: Mesh) -> PyTree:
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, cache_pspec(path, leaf, mesh)),
        cache_spec,
    )


# ---------------------------------------------------------------------------
# paged serving pools (tensor-parallel serving, DESIGN.md §11)
# ---------------------------------------------------------------------------


def pool_pspec(shape: Sequence[int], mesh: Mesh) -> P:
    """Paged-pool leaves are (num_periods, num_blocks, block_size, Hkv·D),
    the lanes head-major.

    Tensor-parallel serving shards the lane axis over ``model``: every chip
    owns Hkv/tp whole heads of *every* physical block, so the block table
    stays replicated and identical on all chips and block allocation /
    preemption / checkpoint bookkeeping is mesh-oblivious.  The heads must
    divide the axis (``RealEngine`` refuses a mesh whose axis does not), so
    a shard's lanes are whole heads — never a silent replication, and never
    a head_dim split: D is the contraction dim of the attention dots, and a
    sharded contraction turns into partial-sum all-reduces whose float
    summation order breaks the bitwise token identity the differential
    harness asserts (DESIGN.md §11).
    """
    spec: list = [None] * len(shape)
    if mesh_axis_size(mesh, "model") > 1:
        spec[-1] = "model"
    return P(*spec)


def pool_shardings(pool_spec: PyTree, mesh: Mesh) -> PyTree:
    """NamedShardings for the paged-pool pytree (arrays or ShapeDtypeStructs
    both work — only ``.shape`` is read)."""
    return jax.tree.map(
        lambda l: NamedSharding(mesh, pool_pspec(l.shape, mesh)), pool_spec
    )
