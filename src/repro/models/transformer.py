"""Generic decoder-only TransformerLM (dense / MoE / VLM / SWA).

Layers are stacked along a leading ``layers`` axis and executed with
``lax.scan`` — this keeps the HLO size O(1) in depth (critical for compiles
on a large mesh) and is what enables XLA to overlap the FSDP weight
all-gathers of layer i+1 with the compute of layer i.

Entry points:
  param_defs(cfg)                         -> ParamDef tree
  forward(cfg, params, batch, ...)        -> final hidden states [B,S,D], aux
  prefill(cfg, params, batch, ...)        -> (hidden, Cache)
  decode_step(cfg, params, cache, batch)  -> (logits [B,V], Cache)
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import actshard
from repro.models import layers as L

Params = Dict[str, Any]


class Cache(NamedTuple):
    """Decode-time state: KV ring/linear caches + step counter."""
    k: jax.Array          # [L, B, Hkv, S, D]
    v: jax.Array          # [L, B, Hkv, S, D]
    step: jax.Array       # scalar int32 — absolute decode position


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


def param_defs(cfg: ModelConfig) -> Params:
    ld = (cfg.num_layers,)
    block: Params = {
        "ln1": L.norm_defs(cfg, ld),
        "attn": L.attention_defs(cfg, ld),
        "ln2": L.norm_defs(cfg, ld),
    }
    if cfg.moe.enabled:
        block["moe"] = L.moe_defs(cfg, ld)
    else:
        block["mlp"] = L.mlp_defs(cfg, ld)
    return {
        "embed": L.embedding_defs(cfg),
        "blocks": block,
        "ln_f": L.norm_defs(cfg),
    }


# ---------------------------------------------------------------------------
# Transformer block (one scan step)
# ---------------------------------------------------------------------------


def _block(cfg: ModelConfig, bp: Params, x: jax.Array, positions: jax.Array,
           *, use_flash: bool, ibn_chunks: int,
           moe_capacity: float) -> Tuple[jax.Array, jax.Array]:
    h = L.norm_apply(cfg, bp["ln1"], x)
    h = L.attention_apply(cfg, bp["attn"], h, positions, use_flash=use_flash)
    x = x + h
    h = L.norm_apply(cfg, bp["ln2"], x)
    if cfg.moe.enabled:
        h, aux = L.moe_apply_auto(cfg, bp["moe"], h, capacity_factor=moe_capacity)
    else:
        h = L.mlp_apply(cfg, bp["mlp"], h, ibn_chunks=ibn_chunks)
        aux = jnp.zeros((), jnp.float32)
    return x + h, aux


# ---------------------------------------------------------------------------
# Forward (train / eval): full sequence, no cache
# ---------------------------------------------------------------------------


def _embed_inputs(cfg: ModelConfig, params: Params, batch: Dict[str, Any]):
    if cfg.embedding_inputs and "inputs_embeds" in batch:
        x = batch["inputs_embeds"].astype(cfg.compute_dtype)
    else:
        x = L.embed_tokens(params["embed"], batch["tokens"], cfg.compute_dtype)
    B, S = x.shape[0], x.shape[1]
    if "positions" in batch:
        positions = batch["positions"]
    elif cfg.rope == "mrope":
        positions = jnp.broadcast_to(jnp.arange(S)[None, None], (3, B, S))
    else:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    return x, positions


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, Any], *,
            use_flash: bool = True, remat: bool = True,
            ibn_chunks: int = 0, moe_capacity: float = 1.25,
            scan_unroll: int = 1,
            ) -> Tuple[jax.Array, jax.Array]:
    """Returns (final hidden states [B,S,D] post-ln_f, moe aux loss)."""
    x, positions = _embed_inputs(cfg, params, batch)

    x = actshard.batch_sharded(x)

    def body(carry, bp):
        x, aux = carry
        x = actshard.batch_sharded(x)
        x, aux_i = _block(cfg, bp, x, positions, use_flash=use_flash,
                          ibn_chunks=ibn_chunks, moe_capacity=moe_capacity)
        return (x, aux + aux_i), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                           params["blocks"], unroll=scan_unroll)
    x = actshard.batch_sharded(x)
    x = L.norm_apply(cfg, params["ln_f"], x)
    return x, aux / cfg.num_layers


def logits_fn(cfg: ModelConfig, params: Params, hidden: jax.Array) -> jax.Array:
    return actshard.logits_sharded(L.lm_logits(params["embed"], hidden))


# ---------------------------------------------------------------------------
# Prefill: forward + build KV cache
# ---------------------------------------------------------------------------


def _to_ring(arr: jax.Array, window: int) -> jax.Array:
    """[B,H,S,D] -> ring cache [B,H,W,D] holding the last `window` positions
    at slots (pos % window)."""
    S = arr.shape[2]
    last = arr[:, :, S - window:, :]
    slots = (jnp.arange(S - window, S)) % window
    out = jnp.zeros(arr.shape[:2] + (window,) + arr.shape[3:], arr.dtype)
    return out.at[:, :, slots, :].set(last)


def cache_len(cfg: ModelConfig, seq_len: int) -> int:
    return min(cfg.window, seq_len) if cfg.window else seq_len


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, Any], *,
            use_flash: bool = True, scan_unroll: int = 1,
            **_) -> Tuple[jax.Array, Cache]:
    """Run the full prompt, return (last hidden [B,D], cache)."""
    x, positions = _embed_inputs(cfg, params, batch)
    S = x.shape[1]
    W = cache_len(cfg, S)

    def body(x, bp):
        x = actshard.batch_sharded(x)
        h = L.norm_apply(cfg, bp["ln1"], x)
        q, k, v = L.qkv_project(cfg, bp["attn"], h, positions)
        G = cfg.q_per_kv
        kr = jnp.repeat(k, G, axis=1) if G > 1 else k
        vr = jnp.repeat(v, G, axis=1) if G > 1 else v
        if use_flash:
            if cfg.window is not None and cfg.window < S:
                o = L.attn_lib.flash_attention_banded(q, kr, vr, cfg.window)
            else:
                o = L.attn_lib.flash_attention(q, kr, vr, cfg.causal,
                                               cfg.window)
        else:
            o = L.attn_lib.reference_attention(q, kr, vr, causal=cfg.causal,
                                               window=cfg.window)
        o = actshard.attn_out_sharded(o)     # see layers.attention_apply
        x = x + actshard.batch_sharded(
            L.out_project(bp["attn"], o, x.dtype))
        h = L.norm_apply(cfg, bp["ln2"], x)
        if cfg.moe.enabled:
            h, _ = L.moe_apply_auto(cfg, bp["moe"], h)
        else:
            h = L.mlp_apply(cfg, bp["mlp"], h)
        if cfg.window is not None and cfg.window < S:
            k, v = _to_ring(k, W), _to_ring(v, W)
        return x + h, (k, v)

    x, (ck, cv) = lax.scan(body, x, params["blocks"], unroll=scan_unroll)
    x = L.norm_apply(cfg, params["ln_f"], x)
    cache = Cache(k=ck, v=cv, step=jnp.array(S, jnp.int32))
    return x[:, -1, :], cache


def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> Cache:
    W = cache_len(cfg, seq_len)
    shape = (cfg.num_layers, batch, cfg.num_kv_heads, W, cfg.head_dim)
    return Cache(
        k=jnp.zeros(shape, cfg.compute_dtype),
        v=jnp.zeros(shape, cfg.compute_dtype),
        step=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Decode: one token, cache update
# ---------------------------------------------------------------------------


def decode_step(cfg: ModelConfig, params: Params, cache: Cache,
                batch: Dict[str, Any], *, scan_unroll: int = 1,
                **_) -> Tuple[jax.Array, Cache]:
    """batch: {"tokens": [B,1]} (or {"inputs_embeds": [B,1,D]}).
    Returns (logits [B,V] for the new token, updated cache)."""
    if cfg.embedding_inputs and "inputs_embeds" in batch:
        x = batch["inputs_embeds"].astype(cfg.compute_dtype)
    else:
        x = L.embed_tokens(params["embed"], batch["tokens"], cfg.compute_dtype)
    step = cache.step

    def body(x, scanned):
        bp, ck, cv = scanned
        h = L.norm_apply(cfg, bp["ln1"], x)
        h, ck, cv = L.attention_decode_apply(
            cfg, bp["attn"], h, step, ck, cv, step, window=cfg.window)
        x = x + h
        h = L.norm_apply(cfg, bp["ln2"], x)
        if cfg.moe.enabled:
            h, _ = L.moe_apply_auto(cfg, bp["moe"], h)
        else:
            h = L.mlp_apply(cfg, bp["mlp"], h)
        return x + h, (ck, cv)

    x, (ck, cv) = lax.scan(body, x, (params["blocks"], cache.k, cache.v),
                           unroll=scan_unroll)
    x = L.norm_apply(cfg, params["ln_f"], x)
    logits = L.lm_logits(params["embed"], x)[:, 0, :]
    return logits, Cache(k=ck, v=cv, step=step + 1)
