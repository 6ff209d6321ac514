"""Parameter-definition trees.

Models declare their parameters as a pytree of ``ParamDef`` (shape, dtype,
logical axes, initializer).  From one definition tree we derive:

- ``init_params``     : materialized arrays (smoke tests / real training)
- ``abstract_params`` : ``jax.ShapeDtypeStruct`` stand-ins (compiles, no alloc)
- ``param_pspecs``    : ``PartitionSpec`` per leaf via logical→mesh axis rules

Logical axis names used across the model zoo:

  ``embed``    d_model rows of weight matrices         → FSDP axis ("data")
  ``ff``       FFN hidden / per-head fanout columns    → TP axis ("model")
  ``heads``    attention Q-head dim                    → TP axis ("model")
  ``kv_heads`` attention KV-head dim                   → TP axis iff divisible
  ``vocab``    vocabulary dim                          → TP axis ("model")
  ``expert``   MoE expert dim                          → TP axis (expert parallel)
  ``layers``   stacked-layer (scan) dim                → never sharded
  ``null``     anything else                           → never sharded
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

Pytree = Any


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones | embed | uniform_decay
    scale: Optional[float] = None  # stddev override; default fan-in scaling
    dtype: Any = jnp.float32

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_def(x) -> bool:
    return isinstance(x, ParamDef)


def tree_map_defs(fn: Callable[[ParamDef], Any], defs: Pytree) -> Pytree:
    return jax.tree_util.tree_map(fn, defs, is_leaf=_is_def)


def _fan_in(shape: Tuple[int, ...]) -> int:
    if len(shape) == 0:
        return 1
    if len(shape) == 1:
        return shape[0]
    # contraction dims = all but the last
    return max(1, math.prod(shape[:-1]))


def _init_leaf(key: jax.Array, d: ParamDef) -> jax.Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    if d.init == "uniform_decay":
        # decay-parameter init in (-6, -3) log space (RWKV/LRU style)
        u = jax.random.uniform(key, d.shape, jnp.float32)
        return (-6.0 + 3.0 * u).astype(d.dtype)
    scale = d.scale
    if scale is None:
        if d.init == "embed":
            scale = 1.0
        else:
            scale = 1.0 / math.sqrt(_fan_in(d.shape))
    return (scale * jax.random.normal(key, d.shape, jnp.float32)).astype(d.dtype)


def init_params(rng: jax.Array, defs: Pytree) -> Pytree:
    leaves, treedef = jax.tree_util.tree_flatten(defs, is_leaf=_is_def)
    keys = jax.random.split(rng, len(leaves))
    out = [_init_leaf(k, d) for k, d in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def abstract_params(defs: Pytree) -> Pytree:
    return tree_map_defs(lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), defs)


# ---------------------------------------------------------------------------
# Logical axis → mesh axis rules
# ---------------------------------------------------------------------------


DEFAULT_RULES: Dict[str, Optional[str]] = {
    "embed": "data",      # FSDP / ZeRO weight sharding
    "ff": "model",        # tensor parallel
    "heads": "model",
    "kv_heads": "model",  # demoted to None when not divisible (resolve_rules)
    "vocab": "model",
    "expert": "model",    # expert parallel
    "layers": None,
    "null": None,
    "seq": None,
}


def resolve_rules(
    mesh_axis_sizes: Dict[str, int],
    *,
    kv_heads: int = 0,
    num_heads: int = 0,
    fsdp: bool = True,
    fsdp_axes: Any = "data",
    tp_axis: Optional[str] = "model",
    extra: Optional[Dict[str, Optional[str]]] = None,
) -> Dict[str, Any]:
    """Specialize DEFAULT_RULES to a mesh + arch (divisibility aware).

    ``fsdp_axes`` may be a tuple (e.g. ("data", "model") for the pure-FSDP
    profile where the whole mesh acts as one ZeRO axis); ``tp_axis=None``
    disables tensor parallelism (heads/ff/vocab/expert replicated).
    """
    rules: Dict[str, Any] = dict(DEFAULT_RULES)
    rules["embed"] = fsdp_axes if fsdp else None
    for k in ("ff", "heads", "kv_heads", "vocab", "expert"):
        rules[k] = tp_axis
    tp = mesh_axis_sizes.get(tp_axis, 1) if tp_axis else 1
    if kv_heads and tp > 1 and kv_heads % tp != 0:
        rules["kv_heads"] = None  # replicate KV heads (GQA narrower than TP)
    if num_heads and tp > 1 and num_heads % tp != 0:
        rules["heads"] = None     # replicate Q heads (head count < / ∤ TP)
    if extra:
        rules.update(extra)
    return rules


def _rule_size(rule, sizes: Dict[str, int]) -> int:
    if rule is None:
        return 1
    if isinstance(rule, tuple):
        n = 1
        for a in rule:
            n *= sizes.get(a, 1)
        return n
    return sizes.get(rule, 1)


def _leaf_pspec(d: ParamDef, rules: Dict[str, Any]) -> PartitionSpec:
    spec = []
    used = set()
    for ax, size in zip(d.axes, d.shape):
        mesh_ax = rules.get(ax or "null")
        atoms = (mesh_ax if isinstance(mesh_ax, tuple)
                 else (mesh_ax,) if mesh_ax else ())
        if mesh_ax is None or used & set(atoms):
            spec.append(None)
        else:
            spec.append(mesh_ax)
            used |= set(atoms)
    return PartitionSpec(*spec)


def param_pspecs(defs: Pytree, rules: Dict[str, Optional[str]]) -> Pytree:
    return tree_map_defs(lambda d: _leaf_pspec(d, rules), defs)


def count_params(defs: Pytree) -> int:
    leaves = jax.tree_util.tree_leaves(defs, is_leaf=_is_def)
    return sum(math.prod(d.shape) for d in leaves)


def validate_pspecs(defs: Pytree, rules: Dict[str, Any],
                    mesh_axis_sizes: Dict[str, int]) -> None:
    """Check every sharded dim is divisible by its mesh-axis size."""
    def check(d: ParamDef):
        spec = _leaf_pspec(d, rules)
        for dim, ax in zip(d.shape, spec):
            n = _rule_size(ax, mesh_axis_sizes)
            if ax is not None and dim % n != 0:
                raise ValueError(
                    f"param {d.shape} axis {ax} size {dim} not divisible "
                    f"by mesh axes {ax} ({n})")
    tree_map_defs(check, defs)
