"""ShapeDtypeStruct stand-ins for every model input — compiles without
a chip lower against these (weak-type-correct, shardable, zero allocation)."""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import get_module


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """The batch dict for one (arch x shape) cell, as ShapeDtypeStructs.

    train   : full-sequence tokens+labels (teacher forcing)
    prefill : the prompt batch
    decode  : one new token per sequence (the KV cache is a separate arg —
              see ``cache_specs``)
    """
    B, S = shape.global_batch, shape.seq_len
    kind = shape.kind
    tok = jnp.int32

    if kind == "train":
        batch: Dict[str, Any] = {}
        if cfg.family == "audio":
            # enc-dec: source frames (stub frontend) + target tokens
            batch["inputs_embeds"] = _sds((B, S, cfg.d_model), jnp.bfloat16)
            batch["tokens"] = _sds((B, S), tok)
        elif cfg.embedding_inputs:
            batch["inputs_embeds"] = _sds((B, S, cfg.d_model), jnp.bfloat16)
        else:
            batch["tokens"] = _sds((B, S), tok)
        if cfg.rope == "mrope":
            batch["positions"] = _sds((3, B, S), tok)
        batch["labels"] = _sds((B, S), tok)
        return batch

    if kind == "prefill":
        batch = {}
        if cfg.family == "audio":
            batch["inputs_embeds"] = _sds((B, S, cfg.d_model), jnp.bfloat16)
            batch["tokens"] = _sds((B, 1), tok)
        elif cfg.embedding_inputs:
            batch["inputs_embeds"] = _sds((B, S, cfg.d_model), jnp.bfloat16)
        else:
            batch["tokens"] = _sds((B, S), tok)
        if cfg.rope == "mrope":
            batch["positions"] = _sds((3, B, S), tok)
        return batch

    if kind == "decode":
        return {"tokens": _sds((B, 1), tok)}

    raise ValueError(kind)


def cache_specs(cfg: ModelConfig, shape: ShapeConfig) -> Any:
    """Abstract decode-cache pytree for a decode cell (no allocation)."""
    mod = get_module(cfg)
    B, S = shape.global_batch, shape.seq_len
    return jax.eval_shape(lambda: mod.init_cache(cfg, B, S))


def param_specs(cfg: ModelConfig, *, serve_bf16: bool = False) -> Any:
    """Abstract params.  ``serve_bf16``: matrices held in bf16 — the
    serving layout (weights are read every decode step; bf16 halves the
    dominant HBM term).  Scalars/norm vectors stay f32."""
    from repro.models.params import abstract_params, tree_map_defs
    mod = get_module(cfg)
    defs = mod.param_defs(cfg)
    if not serve_bf16:
        return abstract_params(defs)
    return tree_map_defs(
        lambda d: jax.ShapeDtypeStruct(
            d.shape, jnp.bfloat16 if len(d.shape) >= 2 else d.dtype), defs)
