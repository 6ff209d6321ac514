"""Shared layer library: norms, MLP variants, MoE, RoPE/M-RoPE, GQA attention.

All layers follow the same convention: ``<layer>_defs(cfg, ...)`` returns a
ParamDef tree, ``<layer>_apply(params, x, ...)`` is the pure function.  The
MLP exposes both the plain (baseline) path and the chunked inverted-bottleneck
path (paper contribution C3 at the XLA level; the Pallas kernel in
``repro.kernels.fused_ibn`` is the TPU-target realization).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models.params import ParamDef

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------


def norm_defs(cfg: ModelConfig, layers_dim: Tuple[int, ...] = ()) -> Params:
    d = cfg.d_model
    ax = ("layers",) * len(layers_dim)
    if cfg.norm == "rmsnorm":
        return {"scale": ParamDef(layers_dim + (d,), ax + ("embed",), "ones")}
    if cfg.norm == "layernorm":
        return {
            "scale": ParamDef(layers_dim + (d,), ax + ("embed",), "ones"),
            "bias": ParamDef(layers_dim + (d,), ax + ("embed",), "zeros"),
        }
    if cfg.norm == "nonparam_ln":  # OLMo: LN without learnable params
        return {}
    raise ValueError(cfg.norm)


def norm_apply(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * lax.rsqrt(var + cfg.rms_norm_eps) * \
            params["scale"].astype(jnp.float32)
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + 1e-5)
        if cfg.norm == "layernorm":
            y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(
                jnp.float32)
    return y.astype(x.dtype)


def rms_head_norm(x: jax.Array, scale: jax.Array) -> jax.Array:
    """QK-norm: RMS over the head dim."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + 1e-6) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def activation(name: str, x: jax.Array) -> jax.Array:
    if name in ("gelu", "geglu"):
        return jax.nn.gelu(x, approximate=True)
    if name in ("swiglu", "silu"):
        return jax.nn.silu(x)
    if name == "relu2":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(name)


# ---------------------------------------------------------------------------
# MLP (inverted bottleneck) — plain and chunked (C3) paths
# ---------------------------------------------------------------------------


def mlp_defs(cfg: ModelConfig, layers_dim: Tuple[int, ...] = (),
             d_model: Optional[int] = None,
             d_ff: Optional[int] = None) -> Params:
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    ax = ("layers",) * len(layers_dim)
    gated = cfg.mlp in ("swiglu", "geglu")
    defs: Params = {
        "wi": ParamDef(layers_dim + (d, f), ax + ("embed", "ff")),
        "wo": ParamDef(layers_dim + (f, d), ax + ("ff", "embed")),
    }
    if gated:
        defs["wg"] = ParamDef(layers_dim + (d, f), ax + ("embed", "ff"))
    return defs


def mlp_apply(cfg: ModelConfig, params: Params, x: jax.Array,
              ibn_chunks: int = 0) -> jax.Array:
    """FFN.  ``ibn_chunks > 1`` enables the depth-first inverted-bottleneck
    schedule (contribution C3): the d_ff intermediate is produced and consumed
    one tile at a time, bounding the live intermediate to d_ff/ibn_chunks.
    """
    dtype = x.dtype
    wi = params["wi"].astype(dtype)
    wo = params["wo"].astype(dtype)
    wg = params.get("wg")
    gated = wg is not None
    if gated:
        wg = wg.astype(dtype)

    if ibn_chunks <= 1:
        h = x @ wi
        if gated:
            h = activation(cfg.mlp, x @ wg) * h
        else:
            h = activation(cfg.mlp, h)
        return h @ wo

    f = wi.shape[-1]
    assert f % ibn_chunks == 0, (f, ibn_chunks)
    tile = f // ibn_chunks
    wi_t = wi.reshape(wi.shape[0], ibn_chunks, tile).transpose(1, 0, 2)
    wo_t = wo.reshape(ibn_chunks, tile, wo.shape[-1])
    if gated:
        wg_t = wg.reshape(wg.shape[0], ibn_chunks, tile).transpose(1, 0, 2)

    def step(acc, ws):
        if gated:
            wi_c, wo_c, wg_c = ws
            t = activation(cfg.mlp, x @ wg_c) * (x @ wi_c)
        else:
            wi_c, wo_c = ws
            t = activation(cfg.mlp, x @ wi_c)
        return acc + t @ wo_c, None

    xs = (wi_t, wo_t, wg_t) if gated else (wi_t, wo_t)
    out0 = jnp.zeros(x.shape[:-1] + (wo.shape[-1],), dtype)
    # fully unrolled: XLA schedules the chunk sequence freely when it
    # is straight-line code
    out, _ = lax.scan(step, out0, xs, unroll=ibn_chunks)
    return out


# ---------------------------------------------------------------------------
# Mixture of Experts (token-choice, capacity-bounded, expert-parallel)
# ---------------------------------------------------------------------------


def moe_defs(cfg: ModelConfig, layers_dim: Tuple[int, ...] = ()) -> Params:
    m = cfg.moe
    d = cfg.d_model
    e = m.num_experts_padded
    f = m.d_ff_expert
    ax = ("layers",) * len(layers_dim)
    gated = cfg.mlp in ("swiglu", "geglu")
    defs: Params = {
        "router": ParamDef(layers_dim + (d, e), ax + ("embed", "expert")),
        "wi": ParamDef(layers_dim + (e, d, f), ax + ("expert", "embed", "ff")),
        "wo": ParamDef(layers_dim + (e, f, d), ax + ("expert", "ff", "embed")),
    }
    if gated:
        defs["wg"] = ParamDef(layers_dim + (e, d, f),
                              ax + ("expert", "embed", "ff"))
    if m.num_shared_experts:
        shared_cfg = cfg
        defs["shared"] = mlp_defs(shared_cfg, layers_dim, d_model=d,
                                  d_ff=m.d_ff_shared)
        defs["shared_gate"] = ParamDef(layers_dim + (d, 1),
                                       ax + ("embed", None))
    return defs


def moe_apply_auto(cfg: ModelConfig, params: Params, x: jax.Array,
                   capacity_factor: float = 1.25
                   ) -> Tuple[jax.Array, jax.Array]:
    """Pick the shard-local (shard_map) MoE when a production mesh is
    installed — GSPMD partitions the data-dependent dispatch scatter
    catastrophically — else the plain pjit path."""
    from repro.models import actshard, moe_sharded
    mesh = actshard.current_mesh()
    if mesh is not None and "model" in mesh.axis_names \
            and actshard.current_profile() in ("2d", "tp"):
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if cfg.moe.num_experts_padded % sizes["model"] == 0:
            return moe_sharded.moe_apply_sharded(
                cfg, params, x, mesh=mesh, capacity_factor=capacity_factor)
    return moe_apply(cfg, params, x, capacity_factor=capacity_factor)


def moe_apply(cfg: ModelConfig, params: Params, x: jax.Array,
              capacity_factor: float = 1.25) -> Tuple[jax.Array, jax.Array]:
    """Token-choice top-k MoE with capacity-bounded sort-free dispatch.

    x: [..., N, d] flattened internally to [N, d].  Returns (out, aux_loss).
    Padded experts (num_experts..num_experts_padded) are masked out of routing.
    """
    m = cfg.moe
    orig_shape = x.shape
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    n = xt.shape[0]
    e_pad = m.num_experts_padded
    e_real = m.num_experts
    k = m.top_k
    dtype = x.dtype

    logits = (xt @ params["router"].astype(dtype)).astype(jnp.float32)
    if e_pad > e_real:
        pad_mask = lax.iota(jnp.int32, e_pad) >= e_real
        logits = jnp.where(pad_mask[None, :], attn_lib.NEG_INF, logits)
    probs = jax.nn.softmax(logits, axis=-1)                  # [N, E]
    gate_vals, expert_idx = lax.top_k(probs, k)              # [N, k]
    if m.norm_topk_prob:
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    # load-balancing aux loss (Switch-style), over real experts only
    me = probs[:, :e_real].mean(axis=0)
    ce = jnp.zeros((e_pad,), jnp.float32).at[expert_idx.reshape(-1)].add(
        1.0 / (n * k))[:e_real]
    aux_loss = e_real * jnp.sum(me * ce)

    # capacity-bounded dispatch: slot = expert * C + position_in_expert
    capacity = int(max(1, (k * n * capacity_factor) // e_pad))
    flat_expert = expert_idx.reshape(-1)                     # [N*k]
    onehot_pos = jnp.zeros((n * k, e_pad), jnp.int32).at[
        jnp.arange(n * k), flat_expert].set(1)
    pos_in_expert = (jnp.cumsum(onehot_pos, axis=0) - 1)[
        jnp.arange(n * k), flat_expert]                      # [N*k]
    keep = pos_in_expert < capacity
    slot = jnp.where(keep, flat_expert * capacity + pos_in_expert,
                     e_pad * capacity)                       # drop sentinel

    token_idx = jnp.repeat(jnp.arange(n), k)
    buf = jnp.zeros((e_pad * capacity, d), dtype).at[slot].set(
        xt[token_idx], mode="drop")
    buf = buf.reshape(e_pad, capacity, d)

    wi = params["wi"].astype(dtype)
    wo = params["wo"].astype(dtype)
    h = jnp.einsum("ecd,edf->ecf", buf, wi)
    if "wg" in params:
        g = jnp.einsum("ecd,edf->ecf", buf, params["wg"].astype(dtype))
        h = activation(cfg.mlp, g) * h
    else:
        h = activation(cfg.mlp, h)
    expert_out = jnp.einsum("ecf,efd->ecd", h, wo).reshape(
        e_pad * capacity, d)

    gathered = jnp.take(expert_out, jnp.minimum(slot, e_pad * capacity - 1),
                        axis=0)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    weighted = gathered * gate_vals.reshape(-1, 1).astype(dtype)
    out = weighted.reshape(n, k, d).sum(axis=1)

    if m.num_shared_experts:
        shared = mlp_apply(cfg, params["shared"], xt)
        sg = jax.nn.sigmoid(
            (xt @ params["shared_gate"].astype(dtype)).astype(jnp.float32))
        out = out + shared * sg.astype(dtype)

    return out.reshape(orig_shape), aux_loss


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B,H,S,D], positions: [B,S] (int). GPT-NeoX half rotation."""
    D = x.shape[-1]
    freqs = rope_freqs(D, theta)                             # [D/2]
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs  # [B,1,S,D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: Tuple[int, ...]) -> jax.Array:
    """M-RoPE (Qwen2-VL): positions [3,B,S] (t/h/w streams), the head_dim/2
    frequency slots are partitioned into `sections` (e.g. 16/24/24), each
    rotated by its own position stream."""
    D = x.shape[-1]
    half = D // 2
    freqs = rope_freqs(D, theta)                             # [half]
    sec_id = jnp.repeat(jnp.arange(len(sections)),
                        jnp.array(sections), total_repeat_length=half)  # [half]
    pos_sel = positions[sec_id]                              # [half, B, S]
    angles = pos_sel.transpose(1, 2, 0).astype(jnp.float32) * freqs  # [B,S,half]
    cos, sin = jnp.cos(angles[:, None]), jnp.sin(angles[:, None])  # [B,1,S,half]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def positional_rotate(cfg: ModelConfig, x: jax.Array,
                      positions: jax.Array) -> jax.Array:
    if cfg.rope == "rope":
        return apply_rope(x, positions, cfg.rope_theta)
    if cfg.rope == "mrope":
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return x


# ---------------------------------------------------------------------------
# GQA attention layer (projections + flash / decode core)
# ---------------------------------------------------------------------------


def attention_defs(cfg: ModelConfig, layers_dim: Tuple[int, ...] = (),
                   cross: bool = False) -> Params:
    d = cfg.d_model
    h, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ax = ("layers",) * len(layers_dim)
    defs: Params = {
        "wq": ParamDef(layers_dim + (d, h, hd), ax + ("embed", "heads", None)),
        "wk": ParamDef(layers_dim + (d, hk, hd), ax + ("embed", "kv_heads", None)),
        "wv": ParamDef(layers_dim + (d, hk, hd), ax + ("embed", "kv_heads", None)),
        "wo": ParamDef(layers_dim + (h, hd, d), ax + ("heads", None, "embed")),
    }
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef(layers_dim + (hd,), ax + (None,), "ones")
        defs["k_norm"] = ParamDef(layers_dim + (hd,), ax + (None,), "ones")
    return defs


def qkv_project(cfg: ModelConfig, params: Params, x: jax.Array,
                positions: Optional[jax.Array],
                kv_x: Optional[jax.Array] = None,
                kv_positions: Optional[jax.Array] = None):
    """Returns q:[B,H,S,D], k,v:[B,Hkv,Skv,D] (rope applied, qk-norm applied)."""
    dtype = x.dtype
    kv_src = x if kv_x is None else kv_x
    kv_pos = positions if kv_positions is None else kv_positions
    q = jnp.einsum("bsd,dhe->bhse", x, params["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhe->bhse", kv_src, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhe->bhse", kv_src, params["wv"].astype(dtype))
    if cfg.qk_norm:
        q = rms_head_norm(q, params["q_norm"])
        k = rms_head_norm(k, params["k_norm"])
    if positions is not None and cfg.rope != "none":
        q = positional_rotate(cfg, q, positions)
        k = positional_rotate(cfg, k, kv_pos)
    return q, k, v


def out_project(params: Params, o: jax.Array, dtype) -> jax.Array:
    return jnp.einsum("bhse,hed->bsd", o, params["wo"].astype(dtype))


def attention_apply(cfg: ModelConfig, params: Params, x: jax.Array,
                    positions: jax.Array, *, causal: Optional[bool] = None,
                    window: Optional[int] = None,
                    use_flash: bool = True,
                    kv_x: Optional[jax.Array] = None,
                    kv_positions: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence attention (train / prefill)."""
    causal_ = cfg.causal if causal is None else causal
    window_ = cfg.window if window is None else window
    q, k, v = qkv_project(cfg, params, x, positions, kv_x=kv_x,
                          kv_positions=kv_positions)
    G = cfg.q_per_kv
    if G > 1:
        k = jnp.repeat(k, G, axis=1)
        v = jnp.repeat(v, G, axis=1)
    if use_flash:
        o = attn_lib.flash_attention(q, k, v, causal_, window_)
    else:
        o = attn_lib.reference_attention(q, k, v, causal=causal_,
                                         window=window_)
    # anchor: with replicated heads (count ∤ TP) + FSDP-sharded wo, the
    # partitioner otherwise all-gathers the FULL batch of o ([B,H,S,hd],
    # 10.7 GB/layer on recurrentgemma prefill) to d-shard the projection
    from repro.models import actshard
    o = actshard.attn_out_sharded(o)
    return actshard.batch_sharded(out_project(params, o, x.dtype))


def attention_decode_apply(cfg: ModelConfig, params: Params, x: jax.Array,
                           position: jax.Array, cache_k: jax.Array,
                           cache_v: jax.Array, cache_index: jax.Array,
                           window: Optional[int] = None):
    """Single-token decode.  x: [B,1,d].  cache_k/v: [B,Hkv,S,D].

    Returns (out [B,1,d], new_cache_k, new_cache_v).  ``cache_index`` is the
    absolute decode step; ring addressing is used iff window is not None.
    """
    S = cache_k.shape[2]
    if cfg.rope == "mrope":
        # text-token M-RoPE: all three streams advance with the step
        positions = jnp.broadcast_to(position.reshape(1, 1, 1),
                                     (3, x.shape[0], 1))
    else:
        positions = jnp.broadcast_to(position.reshape(1, 1), (x.shape[0], 1))
    q, k, v = qkv_project(cfg, params, x, positions)
    write_idx = (cache_index % S) if window is not None else cache_index
    cache_k = lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype),
                                              write_idx, axis=2)
    cache_v = lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype),
                                              write_idx, axis=2)
    valid = jnp.minimum(cache_index + 1, S)
    o = attn_lib.decode_attention(q, cache_k, cache_v, valid,
                                  ring=window is not None)
    return out_project(params, o, x.dtype), cache_k, cache_v


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------


def embedding_defs(cfg: ModelConfig) -> Params:
    v = cfg.padded_vocab
    defs: Params = {
        "embedding": ParamDef((v, cfg.d_model),
                              ("vocab", "embed"), "embed", scale=1.0),
    }
    if not cfg.tie_embeddings:
        defs["unembed"] = ParamDef((cfg.d_model, v), ("embed", "vocab"))
    return defs


def embed_tokens(params: Params, tokens: jax.Array, dtype) -> jax.Array:
    return jnp.take(params["embedding"], tokens, axis=0).astype(dtype)


def lm_logits(params: Params, x: jax.Array) -> jax.Array:
    if "unembed" in params:
        w = params["unembed"]
    else:
        w = params["embedding"].T
    return jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                      w.astype(jnp.float32))
