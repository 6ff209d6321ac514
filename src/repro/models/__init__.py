"""Model zoo registry: dispatch an arch family to its module.

Every module exposes the same functional surface:
  param_defs(cfg)                      -> ParamDef pytree
  forward(cfg, params, batch, ...)     -> (hidden [B,S,D], aux_loss)
  logits_fn(cfg, params, hidden)       -> [B,S,V_padded] (transformer-family)
  prefill(cfg, params, batch, ...)     -> (last_hidden [B,D], Cache)
  decode_step(cfg, params, cache, b)   -> (logits [B,V_padded], Cache)
  init_cache(cfg, batch, seq_len)      -> Cache
"""
from __future__ import annotations

from repro.configs.base import ModelConfig
from repro.models import (mamba_hybrid, recurrentgemma, rwkv6, seamless,
                          transformer)

FAMILY_MODULES = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "hybrid": recurrentgemma,
    "ssm": rwkv6,
    "audio": seamless,
    "mamba_hybrid": mamba_hybrid,
}


def get_module(cfg: ModelConfig):
    return FAMILY_MODULES[cfg.family]
