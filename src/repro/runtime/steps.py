"""train / prefill / decode step builders.

Each builder returns a pure function suitable for ``jax.jit`` with explicit
in/out shardings (the launchers and the compile tests consume these).  The
steps are model-family agnostic via the module registry.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import get_module
from repro.optim import adamw_update, clip_by_global_norm

Pytree = Any

MOE_AUX_WEIGHT = 0.01


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def loss_from_logits(cfg: ModelConfig, logits: jax.Array, labels: jax.Array,
                     loss_mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token cross entropy.  ``logits`` may be vocab-padded; the
    pad region is masked to -inf before the logsumexp."""
    vp = logits.shape[-1]
    if vp != cfg.vocab_size:
        pad = lax.iota(jnp.int32, vp) >= cfg.vocab_size
        logits = jnp.where(pad[None, None, :], -1e30, logits)
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)                     # [B,S]
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if loss_mask is not None:
        nll = nll * loss_mask
        return nll.sum() / jnp.maximum(loss_mask.sum(), 1.0)
    return nll.mean()


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def build_train_step(
    cfg: ModelConfig,
    *,
    lr_schedule: Callable[[jax.Array], jax.Array],
    clip_norm: float = 1.0,
    weight_decay: float = 0.1,
    use_flash: bool = True,
    remat: bool = True,
    ibn_chunks: int = 0,
    scan_unroll: int = 1,
    cast_params: bool = True,
) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``cast_params``: cast f32 master weights to the model compute dtype
    ONCE, before the layer scan — the FSDP all-gathers inside the scan
    then move bf16 instead of f32 (2x less wire), and the cast is
    amortized across layers instead of re-done at every use.
    """
    mod = get_module(cfg)

    def _cast(params):
        if not cast_params or cfg.compute_dtype == jnp.float32:
            return params
        return jax.tree.map(
            lambda p: p.astype(cfg.compute_dtype)
            if p.dtype == jnp.float32 and p.ndim >= 2 else p, params)

    def loss_fn(params, batch):
        params = _cast(params)
        hidden, aux = mod.forward(cfg, params, batch, use_flash=use_flash,
                                  remat=remat, scan_unroll=scan_unroll,
                                  **({"ibn_chunks": ibn_chunks}
                                     if cfg.family in ("dense", "moe", "vlm")
                                     else {}))
        logits = mod.logits_fn(cfg, params, hidden)
        ce = loss_from_logits(cfg, logits, batch["labels"],
                              batch.get("loss_mask"))
        loss = ce + MOE_AUX_WEIGHT * aux
        return loss, {"ce": ce, "aux": aux}

    def train_step(params, opt_state, batch):
        (loss, parts), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        lr = lr_schedule(opt_state.count)
        params, opt_state = adamw_update(grads, opt_state, params, lr=lr,
                                         weight_decay=weight_decay)
        metrics = {"loss": loss, "ce": parts["ce"], "aux": parts["aux"],
                   "grad_norm": gnorm, "lr": lr}
        return params, opt_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# Inference steps
# ---------------------------------------------------------------------------


def build_prefill_step(cfg: ModelConfig, *, use_flash: bool = True,
                       decode_len: Optional[int] = None,
                       scan_unroll: int = 1) -> Callable:
    """(params, batch) -> (last_hidden [B,D], cache)."""
    mod = get_module(cfg)

    def prefill_step(params, batch):
        kw = {}
        if cfg.family in ("audio", "mamba_hybrid") and \
                decode_len is not None:
            kw["decode_len"] = decode_len
        return mod.prefill(cfg, params, batch, use_flash=use_flash,
                           scan_unroll=scan_unroll, **kw)

    return prefill_step


def build_decode_step(cfg: ModelConfig, *, sample: str = "greedy",
                      scan_unroll: int = 1) -> Callable:
    """(params, cache, batch) -> (token [B], logits [B,Vp], cache)."""
    mod = get_module(cfg)

    def decode_step(params, cache, batch):
        logits, cache = mod.decode_step(cfg, params, cache, batch,
                                        scan_unroll=scan_unroll)
        # mask vocab padding before the argmax
        vp = logits.shape[-1]
        if vp != cfg.vocab_size:
            pad = lax.iota(jnp.int32, vp) >= cfg.vocab_size
            logits = jnp.where(pad[None, :], -jnp.inf, logits)
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return token, logits, cache

    return decode_step
