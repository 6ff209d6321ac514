"""Activation-sharding anchors.

GSPMD propagates weight shardings into activations; with a 2-D
(FSDP x TP) weight sharding the embedding gather is ambiguous and the
partitioner can pick batch-REPLICATED, d_model-SHARDED activations — which
turns every residual-stream op into a full-batch collective (observed:
13 GB all-gathers on the LM head of olmo-1b on a 16x16 mesh).  These helpers
pin the canonical activation layout [batch=dp, seq=None, d_model=None] at
the few places that anchor propagation (embedding output, scan carry,
final hidden, logits).

The launcher installs the mesh via ``set_mesh``; without it every
helper is a no-op, so tests and single-device examples are unaffected.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: Optional[Mesh] = None
_DP: Tuple[str, ...] = ()
_TP: Optional[str] = None
_DP_SIZE: int = 1
_PROFILE: str = "2d"


def set_mesh(mesh: Optional[Mesh], profile: str = "2d") -> None:
    """Install (or clear, with None) the activation-sharding mesh."""
    global _MESH, _DP, _TP, _DP_SIZE, _PROFILE
    if mesh is None:
        _MESH, _DP, _TP, _DP_SIZE, _PROFILE = None, (), None, 1, "2d"
        return
    _MESH = mesh
    _PROFILE = profile
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_names = ("pod", "data", "model") if profile == "fsdp" \
        else ("pod", "data")
    _DP = tuple(a for a in dp_names if a in sizes)
    _TP = "model" if ("model" in sizes and profile in ("2d", "tp")) else None
    _DP_SIZE = 1
    for a in _DP:
        _DP_SIZE *= sizes[a]


def current_profile() -> str:
    return _PROFILE


def current_mesh() -> Optional[Mesh]:
    return _MESH


def _constrain(x: jax.Array, spec: P) -> jax.Array:
    if _MESH is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(_MESH, spec))


def _dp_entry(batch: int):
    """The batch-dim spec entry: largest dp-axis prefix that divides."""
    if not _DP:
        return None
    chosen = []
    prod = 1
    sizes = dict(zip(_MESH.axis_names, _MESH.devices.shape))
    for a in _DP:
        if batch % (prod * sizes[a]) == 0:
            chosen.append(a)
            prod *= sizes[a]
    if not chosen:
        return None
    return tuple(chosen) if len(chosen) > 1 else chosen[0]


def batch_sharded(x: jax.Array) -> jax.Array:
    """[B, ...] -> batch over dp; under the 'cp' profile the sequence
    dim additionally shards over 'model' (context parallelism)."""
    if _MESH is None:
        return x
    nb = _dp_entry(x.shape[0])
    if _PROFILE == "cp" and x.ndim >= 2:
        sizes = dict(zip(_MESH.axis_names, _MESH.devices.shape))
        if "model" in sizes and x.shape[1] % sizes["model"] == 0:
            return _constrain(
                x, P(nb, "model", *([None] * (x.ndim - 2))))
    return _constrain(x, P(nb, *([None] * (x.ndim - 1))))


def attn_out_sharded(x: jax.Array) -> jax.Array:
    """[B, H, S, D] attention output: batch over dp, heads over TP when
    divisible (replicating heads here would force redundant projection
    compute on every TP shard — observed +2.3x flops on starcoder2
    prefill with a plain batch anchor)."""
    if _MESH is None:
        return x
    nb = _dp_entry(x.shape[0])
    h_ax = None
    if _TP is not None:
        sizes = dict(zip(_MESH.axis_names, _MESH.devices.shape))
        if x.shape[1] % sizes[_TP] == 0:
            h_ax = _TP
    return _constrain(x, P(nb, h_ax, *([None] * (x.ndim - 2))))


def logits_sharded(x: jax.Array) -> jax.Array:
    """[B, S, V] -> batch over dp, vocab over tp."""
    if _MESH is None:
        return x
    nb = _dp_entry(x.shape[0])
    tp = _TP if (_TP and x.shape[-1] %
                 dict(zip(_MESH.axis_names, _MESH.devices.shape))[_TP] == 0) \
        else None
    return _constrain(x, P(nb, *([None] * (x.ndim - 2)), tp))
