"""Logical-axis sharding rules (MaxText-style) for DP/FSDP/TP/EP/SP.

Two pieces:

1. **Activation constraints** — models call ``constrain(x, logical_axes)``
   at block boundaries; inside a ``logical_rules_context`` (set by the
   launcher) this lowers to ``with_sharding_constraint`` with the active
   mesh; outside any context it is a no-op, so models run unmodified on a
   single device.

2. **Parameter specs** — ``params_partition_specs`` maps every param leaf to
   a PartitionSpec from a name-based rule table:
     * TP   — head/ffn-hidden/expert dims over "model";
     * FSDP — the d_model-ish dim over "data" (ZeRO-3 style weight shard);
     * DP   — batch over ("pod", "data") [pod folds into data-parallelism];
     * SP   — sequence over "data" for long-context activations;
     * EP   — expert dim of MoE stacks over "model".

Logical axis names used by the models:
  "batch", "seq", "embed", "heads", "kv_heads", "ffn", "vocab", "experts",
  "rm_features", "state", None (replicated).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with varying-manual-axes checks off.

    Every shard_map in the repo (MoE expert parallelism, compressed psum,
    the sharded estimator path) goes through this wrapper: their bodies
    mix replicated and per-shard values the VMA checker cannot type."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

# THE feature axis: random-feature columns (and the stacked per-shard
# estimator params backing them) shard over this name — used as both the
# logical axis in the rule table below and the mesh axis name of
# launch.mesh.make_feature_mesh / distributed.estimator.
FEATURE_AXIS = "rm_features"

# logical axis -> mesh axis (or tuple of mesh axes, or None)
DEFAULT_RULES: Dict[str, object] = {
    "batch": ("pod", "data"),
    "seq": None,           # flipped to ("pod", "data") for SP-long-context
    # Megatron-style sequence parallelism: the residual stream between
    # blocks is sharded over the TP axis on the sequence dim, so saved
    # activations (remat carriers) are 1/tp the size; XLA inserts the
    # all-gather before QKV/FFN and the reduce-scatter after the output
    # projections. Falls back to replicated when T % tp != 0 (decode).
    "act_seq": "model",
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "ffn": "model",
    "vocab": "model",
    "experts": "model",
    "expert_ffn": None,
    "fsdp": "data",        # weight dim sharded for ZeRO-style FSDP
    FEATURE_AXIS: None,    # in-model estimator params replicate (§10)
    "state": "model",
    "layers": None,
    # decode KV-cache sequence dim: None = replicated over model (classic);
    # "model" = FlashDecoding-style split-K decode (scores gathered instead
    # of values — evaluated in §Perf).
    "kv_seq": None,
}

_local = threading.local()


def _active() -> Optional[Tuple[Mesh, Dict[str, object]]]:
    return getattr(_local, "ctx", None)


def set_default_rules(rules: Dict[str, object]) -> None:
    DEFAULT_RULES.update(rules)


@contextlib.contextmanager
def logical_rules_context(mesh: Mesh, rules: Optional[Dict[str, object]] = None):
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    # drop mesh axes that don't exist in this mesh (e.g. no "pod" single-pod)
    def _filter(axis):
        if axis is None:
            return None
        if isinstance(axis, tuple):
            kept = tuple(a for a in axis if a in mesh.axis_names)
            return kept if kept else None
        return axis if axis in mesh.axis_names else None

    merged = {k: _filter(v) for k, v in merged.items()}
    prev = _active()
    _local.ctx = (mesh, merged)
    try:
        yield merged
    finally:
        _local.ctx = prev


def spec_for(logical_axes: Tuple[Optional[str], ...],
             rules: Optional[Dict[str, object]] = None) -> P:
    if rules is None:
        ctx = _active()
        if ctx is None:
            return P()
        rules = ctx[1]
    return P(*(rules.get(a) if a is not None else None for a in logical_axes))


def constrain(x: jax.Array, logical_axes: Tuple[Optional[str], ...]):
    ctx = _active()
    if ctx is None:
        return x
    mesh, rules = ctx
    if len(logical_axes) != x.ndim:
        raise ValueError(f"rank mismatch: {logical_axes} vs {x.shape}")
    spec = spec_for(logical_axes, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# parameter rules (matched on the final path component; scanned stacks get a
# leading "layers" axis automatically when leaf rank exceeds the rule).
# ---------------------------------------------------------------------------
_PARAM_RULES: Dict[str, Tuple[Optional[str], ...]] = {
    # embeddings / head
    "embedding": ("vocab", "embed"),
    "unembed": ("fsdp", "vocab"),
    # attention (2D fused-head weights)
    "wq": ("fsdp", "heads"),
    "wk": ("fsdp", "kv_heads"),
    "wv": ("fsdp", "kv_heads"),
    "wo": ("heads", "fsdp"),
    "bq": ("heads",),
    "bk": ("kv_heads",),
    "bv": ("kv_heads",),
    # MLA
    "w_q": ("fsdp", "heads"),
    "w_dkv": ("fsdp", None),
    "w_ukv": (None, "heads"),
    "w_o": ("heads", "fsdp"),
    "kv_norm_scale": (None,),
    # MLP
    "w_gate": ("fsdp", "ffn"),
    "w_up": ("fsdp", "ffn"),
    "w_down": ("ffn", "fsdp"),
    "b_up": ("ffn",),
    "b_down": (None,),
    # MoE (expert-stacked 3D) — matched by rank below
    "router": (None, None),
    "shared_gate": ("fsdp", "ffn"),
    "shared_up": ("fsdp", "ffn"),
    "shared_down": ("ffn", "fsdp"),
    # mamba
    "w_in": ("fsdp", "state"),
    "conv_w": (None, "state"),
    "conv_b": ("state",),
    "x_proj": ("state", None),
    "dt_proj": (None, "state"),
    "dt_bias": ("state",),
    "a_log": ("state", None),
    "d_skip": ("state",),
    "w_out": ("state", "fsdp"),
    # xlstm
    "w_if": ("fsdp", None),
    "b_if": (None,),
    "r_rec": (None, None, None, None),
    "gn_scale": (None,),
    "ff_up": ("fsdp", "ffn"),
    "ff_down": ("ffn", "fsdp"),
    # estimator params ("rm_est" subtree): replicated (small, frozen).
    # "omegas" = RM Rademacher rows; "h"/"s" = TensorSketch hash tables;
    # "wr"/"wi" = CTR complex Rademacher real/imag parts.
    "rm_omegas": (None, None),
    "omegas": (None, None),
    "h": (None, None),
    "s": (None, None),
    "wr": (None, None),
    "wi": (None, None),
    "rm_scale": (),
    # norms
    "scale": (None,),
    "bias": (None,),
    "pos_embedding": (None, "embed"),
}

# MoE expert-stacked weights share names with dense MLP ("w_gate" etc.) but
# have an extra leading expert dim; scanned stacks additionally prepend a
# "layers" dim. The pad order depends on whether the leaf lives under a MoE
# module (path component "moe"), which ``_leaf_spec`` receives.
def _leaf_spec(path: Tuple[str, ...], ndim: int,
               rules: Dict[str, object]) -> P:
    name = path[-1]
    base = _PARAM_RULES.get(name)
    if base is None:
        base = tuple(None for _ in range(ndim))
    logical = list(base)
    in_moe = any(p == "moe" for p in path)
    pad_order = ("experts", "layers") if in_moe else ("layers",)
    pad_i = 0
    while len(logical) < ndim and pad_i < len(pad_order):
        logical.insert(0, pad_order[pad_i])
        pad_i += 1
    while len(logical) < ndim:
        logical.insert(0, None)
    logical = logical[-ndim:] if len(logical) > ndim else logical
    return P(*(rules.get(a) if a is not None else None for a in logical))


def _dedupe_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop mesh axes that appear twice or don't divide the dim."""
    used = set()
    out = []
    for dim, axis in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axis is None:
            out.append(None)
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        kept = []
        size = 1
        for a in axes:
            if a in used or a not in mesh.axis_names:
                continue
            size *= mesh.shape[a]
            kept.append(a)
        if not kept or dim % np.prod([mesh.shape[a] for a in kept]) != 0:
            out.append(None)
            continue
        used.update(kept)
        out.append(tuple(kept) if len(kept) > 1 else kept[0])
    return P(*out)


def params_partition_specs(params_tree, mesh: Mesh,
                           rules: Optional[Dict[str, object]] = None):
    """Pytree of PartitionSpecs matching ``params_tree`` (arrays or
    ShapeDtypeStructs)."""
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)

    def _walk(path, node):
        if isinstance(node, dict):
            return {k: _walk(path + (k,), v) for k, v in node.items()}
        spec = _leaf_spec(path, len(node.shape), merged)
        return _dedupe_spec(spec, tuple(node.shape), mesh)

    return _walk((), params_tree)


# ---------------------------------------------------------------------------
# estimator param subtrees
# ---------------------------------------------------------------------------
# Two distinct layouts, one per serving regime (DESIGN.md §10):
#
#   * REPLICATED — the in-model ``rm_est`` subtree (RM omegas / CountSketch
#     "h"/"s" hash tensors) during data-parallel decode: small, frozen,
#     needed in full by every shard. Covered by the name rules above
#     ("omegas"/"h"/"s" -> replicated).
#   * FEATURE-SHARDED — the stacked per-shard params of the sharded
#     estimator construction (repro.distributed.estimator): leaves carry a
#     leading shard dim that lives on the "rm_features" mesh axis; shard s
#     owns the s-th sub-map's draws and feature columns.
def estimator_param_specs(params_stacked, mesh: Mesh,
                          axis: str = FEATURE_AXIS):
    """PartitionSpecs for stacked per-shard estimator params.

    Every leaf of ``params_stacked`` has shape ``[num_shards, ...]``; the
    leading dim is sharded over ``axis`` and everything else is replicated.
    Leading dims that don't divide the axis size fall back to replicated via
    ``_dedupe_spec`` (e.g. a host-built stack inspected on one device).
    """

    def _one(leaf):
        spec = P(axis, *(None for _ in range(leaf.ndim - 1)))
        return _dedupe_spec(spec, tuple(leaf.shape), mesh)

    return jax.tree_util.tree_map(_one, params_stacked)


# decode-cache leaves, matched by name (rank WITHOUT the scanned-groups dim;
# leaves under "groups" carry one extra leading layer axis).
_CACHE_RULES: Dict[str, Tuple[Optional[str], ...]] = {
    "k": ("batch", "kv_seq", "kv_heads", None),
    "v": ("batch", "kv_seq", "kv_heads", None),
    "c_kv": ("batch", "kv_seq", None),
    "k_pe": ("batch", "kv_seq", None),
    "rm_s": ("batch", "heads", None, None),
    "rm_n": ("batch", "heads", None),
    "conv": ("batch", None, "state"),
    "ssm": ("batch", "state", None),
    "c": ("batch", "heads", None, None),
    "n": ("batch", "heads", None),
    "m": ("batch", "heads", None),   # slstm rank-3; mlstm rank-2 handled below
    "h": ("batch", "heads", None),
}


def cache_partition_specs(cache_tree, mesh: Mesh,
                          rules: Optional[Dict[str, object]] = None):
    """PartitionSpecs for decode caches: batch over DP axes, heads/state over
    "model". Indivisible dims (e.g. batch=1 in long_500k) fall back to
    replicated via _dedupe_spec."""
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)

    def _walk(path, node):
        if isinstance(node, dict):
            return {k: _walk(path + (k,), v) for k, v in node.items()}
        name = path[-1]
        base = _CACHE_RULES.get(name)
        nd = len(node.shape)
        stacked = "groups" in path          # scanned stacks: leading layer dim
        if base is None:
            logical = ([None] if stacked else []) + ["batch"]
            logical += [None] * (nd - len(logical))
        else:
            logical = ([None] if stacked else []) + list(base)
            logical = logical[:nd]
            while len(logical) < nd:
                logical.append(None)
        spec = P(*(merged.get(a) if a is not None else None
                   for a in logical))
        return _dedupe_spec(spec, tuple(node.shape), mesh)

    return _walk((), cache_tree)


def batch_partition_specs(batch_tree, mesh: Mesh,
                          rules: Optional[Dict[str, object]] = None,
                          seq_sharded: bool = False):
    """Input batch specs: batch dim over ("pod","data"); optionally SP."""
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    batch_axes = merged.get("batch")

    def _one(node):
        nd = len(node.shape)
        if nd == 0:
            return P()
        axes = [batch_axes]
        if seq_sharded and nd >= 2:
            axes.append(merged.get("seq"))
        while len(axes) < nd:
            axes.append(None)
        return _dedupe_spec(P(*axes), tuple(node.shape), mesh)

    return jax.tree_util.tree_map(_one, batch_tree)
