"""Mesh-aware sharding rules for params, batches and decode caches.

Axis strategy (DESIGN.md §5):
  - ``pod``   : DCN axis — pure data parallelism (batch only; weights
                replicated across pods so all-gathers stay on ICI)
  - ``data``  : ICI — batch DP + FSDP/ZeRO weight+optimizer sharding
  - ``model`` : ICI — tensor parallel (heads / d_ff / vocab / experts) and
                sequence-parallel KV caches for decode
Divisibility fallbacks (batch not divisible by dp, kv_heads narrower than
TP, ...) demote the corresponding dim to replicated; every demotion is a
deliberate rule, not an error.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import params as param_lib

Pytree = Any


def shard_map(f, *, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking (``check_vma``) off —
    the callers do their own psum bookkeeping the checker cannot
    follow."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


PROFILES = ("2d", "fsdp", "tp", "cp")
# '2d'  : FSDP over 'data' x TP over 'model' (Megatron-style, the default)
# 'fsdp': the whole mesh is one ZeRO/DP axis — no tensor parallelism.
#         Wins for models whose TP collectives dominate (small-to-mid dense
#         archs) or whose head counts don't divide the TP degree.
# 'tp'  : serving layout — weights TP-sharded in their USE layout over
#         'model', replicated over 'data' (no FSDP): decode steps re-read
#         weights every token, so per-step FSDP all-gathers dominate the
#         decode wire profile (h2o-danube decode: 20.5 MB lm-head gather
#         per token).  Batch stays on ('pod','data').


def dp_axes(mesh: Mesh, profile: str = "2d") -> Tuple[str, ...]:
    """Data-parallel mesh axes, outermost first."""
    names = ("pod", "data", "model") if profile == "fsdp" \
        else ("pod", "data")
    return tuple(a for a in names if a in mesh.axis_names)


def dp_size(mesh: Mesh, profile: str = "2d") -> int:
    sizes = mesh_axis_sizes(mesh)
    out = 1
    for a in dp_axes(mesh, profile):
        out *= sizes[a]
    return out


def _batch_axis(mesh: Mesh, global_batch: int, profile: str = "2d"):
    """The PartitionSpec entry for the batch dim (None if not divisible)."""
    axes = dp_axes(mesh, profile)
    sizes = mesh_axis_sizes(mesh)
    # use the largest prefix of dp axes that divides the batch
    chosen = []
    prod = 1
    for a in axes:
        if global_batch % (prod * sizes[a]) == 0:
            chosen.append(a)
            prod *= sizes[a]
    if not chosen:
        return None
    return tuple(chosen) if len(chosen) > 1 else chosen[0]


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def model_param_pspecs(cfg: ModelConfig, mesh: Mesh, defs: Pytree,
                       *, fsdp: bool = True,
                       profile: str = "2d") -> Pytree:
    """PartitionSpec tree for a model's ParamDef tree on this mesh."""
    sizes = mesh_axis_sizes(mesh)
    if profile == "fsdp":
        fsdp_axes = tuple(a for a in ("data", "model")
                          if a in mesh.axis_names)
        fsdp_axes = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
        tp_axis = None
    elif profile == "tp":
        fsdp_axes = None
        tp_axis = "model" if "model" in mesh.axis_names else None
    elif profile == "cp":
        fsdp_axes = "data" if "data" in mesh.axis_names else None
        tp_axis = None
    else:
        fsdp_axes = "data" if "data" in mesh.axis_names else None
        tp_axis = "model" if "model" in mesh.axis_names else None
    rules = param_lib.resolve_rules(
        sizes, kv_heads=cfg.num_kv_heads, num_heads=cfg.num_heads,
        fsdp=fsdp and fsdp_axes is not None,
        fsdp_axes=fsdp_axes, tp_axis=tp_axis)
    # divisibility demotions beyond heads: check every leaf, demote axis
    # rules that would not divide (e.g. odd d_ff, lru widths).
    def check_leaf(d: param_lib.ParamDef):
        for ax, dim in zip(d.axes, d.shape):
            mesh_ax = rules.get(ax or "null")
            if mesh_ax is not None and \
                    dim % param_lib._rule_size(mesh_ax, sizes) != 0:
                rules[ax] = None
    param_lib.tree_map_defs(check_leaf, defs)
    return param_lib.param_pspecs(defs, rules)


def named(mesh: Mesh, tree: Pytree) -> Pytree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree, is_leaf=lambda x: isinstance(x, PartitionSpec_cls))


PartitionSpec_cls = P


# ---------------------------------------------------------------------------
# Batches
# ---------------------------------------------------------------------------


def sizes_of(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_pspecs(cfg: ModelConfig, mesh: Mesh, batch_struct: Dict[str, Any],
                 profile: str = "2d") -> Dict[str, Any]:
    """PartitionSpecs for an input batch dict keyed by entry name."""
    out: Dict[str, Any] = {}
    for k, v in batch_struct.items():
        nb = _batch_axis(mesh, v.shape[0] if k != "positions" or v.ndim == 2
                         else v.shape[1], profile)
        sq = "model" if (profile == "cp" and v.ndim >= 2
                         and v.shape[1] % sizes_of(mesh).get("model", 1)
                         == 0) else None
        if k in ("tokens", "labels", "loss_mask"):
            out[k] = P(nb, sq, *([None] * (v.ndim - 2))) if v.ndim >= 2 \
                else P(nb)
        elif k == "inputs_embeds":
            out[k] = P(nb, sq, None)
        elif k == "positions" and v.ndim == 3:      # m-rope [3,B,S]
            out[k] = P(None, nb, sq)
        elif k == "positions":
            out[k] = P(nb, sq)
        else:
            out[k] = P(*([None] * v.ndim))
    return out


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------


def cache_pspecs(cfg: ModelConfig, mesh: Mesh, cache_struct: Any,
                 profile: str = "2d") -> Any:
    """PartitionSpec tree for a decode cache (family-specific NamedTuple).

    KV caches shard batch over the dp axes and the *sequence* dim over the
    TP axis (flash-decoding split-S) — GQA archs with kv_heads < TP would
    otherwise replicate the multi-GB cache per chip.  Attention-free state
    shards its head dim over TP.  Dispatch is by NamedTuple field name
    (cache pytrees flatten positionally, so path-based matching would see
    only indices).
    """
    sizes = mesh_axis_sizes(mesh)
    tp = sizes.get("model", 1)

    def tpax(dim: int):
        if profile == "fsdp":      # 'model' belongs to the batch/dp group
            return None
        return "model" if dim % tp == 0 else None

    def spec_leaf(field: str, leaf) -> P:
        if not hasattr(leaf, "shape") or leaf.ndim == 0:
            return P()
        shape = leaf.shape
        b_dim = 1 if leaf.ndim >= 4 or field.startswith("shift") else 0
        nb = _batch_axis(mesh, shape[b_dim], profile)
        if field in ("self_k", "self_v", "cross_k", "cross_v"):
            # seamless [L,B,H,S,D]: MHA heads divide TP -> shard heads,
            # else fall back to sequence sharding
            if profile != "fsdp" and shape[2] % tp == 0:
                return P(None, nb, "model", None, None)
            return P(None, nb, None, tpax(shape[3]), None)
        if field in ("k", "v"):                   # transformer [L,B,Hkv,S,D]
            return P(None, nb, None, tpax(shape[3]), None)
        if field in ("attn_k", "attn_v"):         # rg [B,Hkv,W,D]
            nb0 = _batch_axis(mesh, shape[0], profile)
            return P(nb0, None, tpax(shape[2]), None)
        if field == "state":                      # rwkv [L,B,H,K,V]
            return P(None, nb, tpax(shape[2]), None, None)
        if field.startswith("shift"):             # rwkv [L,B,D]
            return P(None, nb, tpax(shape[2]))
        if field == "rec_h":                      # rg [B,W]
            nb0 = _batch_axis(mesh, shape[0], profile)
            return P(nb0, tpax(shape[1]))
        if field == "conv_state":                 # rg [B,cw-1,W]
            nb0 = _batch_axis(mesh, shape[0], profile)
            return P(nb0, None, tpax(shape[2]))
        return P(*([None] * leaf.ndim))

    assert hasattr(cache_struct, "_fields"), type(cache_struct)
    out = {}
    for field in cache_struct._fields:
        sub = getattr(cache_struct, field)
        out[field] = jax.tree.map(lambda lf, f=field: spec_leaf(f, lf), sub)
    return type(cache_struct)(**out)
