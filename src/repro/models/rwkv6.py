"""RWKV-6 "Finch" [arXiv:2404.05892] — attention-free LM.

TPU adaptation: the WKV6 recurrence (data-dependent diagonal decay) is
executed in *chunked* form — within a chunk of C tokens the recurrence is
re-expressed as three MXU matmuls plus a C×C intra-chunk score matrix, and the
[K,V] state is carried across chunks: on one device in VMEM, by the Pallas
kernel ``kernels.rwkv_chunk``; under a mesh, and for the gradient, by
``wkv_chunked_xla``'s scan.  All decay factors appear as
``exp(b_t - b_s)`` with ``t >= s`` and ``b`` a running cumsum of log-decays
(always <= 0), so every exponent is <= 0 — numerically safe without
renormalization.

This is the paper-technique transfer for the attention-free arch (DESIGN.md
§Arch-applicability): like the inverted-bottleneck fusion, the chunked form
keeps the outer-product intermediates in fast memory instead of streaming the
full-state recurrence through HBM per token.

Every op sits in a ``jax.named_scope`` spelled as the scheduler's layer
(``repro.core.workload.rwkv6_workload``) without its block index, since
one scan body serves every block: ``ln1``, ``tmix``/``rkvg``, ``wkv``,
``gn``, ``out``, ``res1``, ``ln2``, ``cmix``/``key``, ``act``, ``value``,
``res2``; outside the scan ``embed``, ``head.ln`` and ``head.logits``.
A small leaf's cast sits in the scope of the layer that uses it, so a
cast that XLA hoists out of the scan still counts to its layer.  Scopes
are metadata: the compiled program is the same without them.

On one device the layer scan runs over the layer index.  The eight
projection weights (``STACKED``) stay whole ``[L, K, N]`` stacks in their
stored dtype and ``kernels.ops.stacked_proj`` reads layer ``i``'s tiles
from them, casting each in VMEM.  Sliced in the scan's ``xs`` and cast by
XLA, they would be cast whole, every layer at once, on every call: XLA
hoists the cast of a loop-invariant stack out of the loop.  The small
per-layer leaves (mixes, LoRAs, decay, norms) are scanned.  Under an
``actshard`` mesh every leaf is scanned and XLA casts each projection's
slice: the SPMD partitioner cannot split a Pallas call, and would gather
the stacks and the whole batch onto every chip to run it.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import actshard
from repro.models import layers as L
from repro.models.params import ParamDef

Params = Dict[str, Any]

LORA_MIX = 32     # token-shift LoRA rank
LORA_DECAY = 64   # decay LoRA rank

# the scopes ``forward`` and ``logits_fn`` open around work the
# scheduler's chain (``core.workload.rwkv6_workload``) leaves out
OUTSIDE_CHAIN_SCOPES = ("embed", "head.logits")

# the projection weights the layer scan leaves whole: ``blocks[g][n]``
STACKED = {"tm": ("wr", "wk", "wv", "wg", "wo"), "cm": ("wk", "wv", "wr")}


def chain_scope(layer: str) -> str:
    """The scope path that spells a chain layer in ``forward``: one scan
    body serves every block, so ``blk3.tmix.wkv`` runs as ``tmix.wkv``."""
    block, _, rest = layer.partition(".")
    return rest if block.startswith("blk") and block[3:].isdigit() else layer


class RWKVCache(NamedTuple):
    state: jax.Array      # [L, B, H, K, V] wkv state
    shift_tm: jax.Array   # [L, B, D] previous token (time-mix)
    shift_cm: jax.Array   # [L, B, D] previous token (channel-mix)
    step: jax.Array


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


def param_defs(cfg: ModelConfig) -> Params:
    d = cfg.d_model
    f = cfg.d_ff
    h = d // cfg.wkv_head_dim
    k = cfg.wkv_head_dim
    nl = cfg.num_layers
    ld = (nl,)
    ax = ("layers",)

    def vec(init="zeros"):
        return ParamDef(ld + (d,), ax + ("embed",), init)

    tm = {
        "maa_x": vec(), "maa_w": vec(), "maa_k": vec(), "maa_v": vec(),
        "maa_r": vec(), "maa_g": vec(),
        "maa_w1": ParamDef(ld + (d, 5 * LORA_MIX), ax + ("embed", None)),
        "maa_w2": ParamDef(ld + (5, LORA_MIX, d), ax + (None, None, "embed")),
        "decay": ParamDef(ld + (d,), ax + ("embed",), "uniform_decay"),
        "td_w1": ParamDef(ld + (d, LORA_DECAY), ax + ("embed", None)),
        "td_w2": ParamDef(ld + (LORA_DECAY, d), ax + (None, "embed")),
        "faaaa": ParamDef(ld + (h, k), ax + ("heads", None)),
        "wr": ParamDef(ld + (d, d), ax + ("embed", "ff")),
        "wk": ParamDef(ld + (d, d), ax + ("embed", "ff")),
        "wv": ParamDef(ld + (d, d), ax + ("embed", "ff")),
        "wg": ParamDef(ld + (d, d), ax + ("embed", "ff")),
        "wo": ParamDef(ld + (d, d), ax + ("ff", "embed")),
        # ln_x acts on the head-grouped (TP-sharded) dim — shard to match
        "lnx_scale": ParamDef(ld + (d,), ax + ("ff",), "ones"),
        "lnx_bias": ParamDef(ld + (d,), ax + ("ff",), "zeros"),
    }
    cm = {
        "maa_k": vec(), "maa_r": vec(),
        "wk": ParamDef(ld + (d, f), ax + ("embed", "ff")),
        "wv": ParamDef(ld + (f, d), ax + ("ff", "embed")),
        "wr": ParamDef(ld + (d, d), ax + ("embed", "ff")),
    }
    block = {
        "ln1": L.norm_defs(cfg, ld), "tm": tm,
        "ln2": L.norm_defs(cfg, ld), "cm": cm,
    }
    return {
        "embed": L.embedding_defs(cfg),
        "ln0": L.norm_defs(cfg),
        "blocks": block,
        "ln_f": L.norm_defs(cfg),
    }


# ---------------------------------------------------------------------------
# WKV6 core — chunked (train/prefill) and recurrent (decode)
# ---------------------------------------------------------------------------


def wkv_chunked(r, k, v, logw, u, state, chunk: int):
    """r,k,logw: [B,T,H,K]; v: [B,T,H,V]; u: [H,K]; state: [B,H,K,V].

    Returns (out [B,T,H,V], new_state).  logw = log(decay) <= 0.  On one
    device this is the Pallas kernel ``kernels.rwkv_chunk``, which reads
    the operands as the projections wrote them and keeps each head's
    state in VMEM across the chunks; its gradient is that of
    ``wkv_chunked_xla``.  Under an ``actshard`` mesh it is
    ``wkv_chunked_xla``: the partitioner cannot split a Pallas call.
    """
    if actshard.current_mesh() is not None:
        return wkv_chunked_xla(r, k, v, logw, u, state, chunk)
    return _wkv_kernel(r, k, v, logw, u, state, chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _wkv_kernel(r, k, v, logw, u, state, chunk):
    # imported where the WKV is traced, as in ``_proj``
    from repro.kernels import ops
    return ops.wkv(r, k, v, logw, u, state, chunk=chunk)


def _wkv_kernel_fwd(r, k, v, logw, u, state, chunk):
    return (_wkv_kernel(r, k, v, logw, u, state, chunk),
            (r, k, v, logw, u, state))


def _wkv_kernel_bwd(chunk, res, g):
    """The XLA form's gradient, its forward recomputed."""
    _, vjp = jax.vjp(lambda *a: wkv_chunked_xla(*a, chunk), *res)
    return vjp(g)


_wkv_kernel.defvjp(_wkv_kernel_fwd, _wkv_kernel_bwd)


def wkv_chunked_xla(r, k, v, logw, u, state, chunk: int):
    """``wkv_chunked`` as XLA's chunked form: the chunks in a
    ``lax.scan``, the intra-chunk scores as a [B, H, C, C, K] tensor."""
    B, T, H, K = r.shape
    V = v.shape[-1]
    C = min(chunk, T)
    while T % C != 0:
        C //= 2
    n = T // C

    def resh(x):
        return x.reshape(B, n, C, H, -1).transpose(1, 0, 3, 2, 4)  # [n,B,H,C,*]

    rs, ks, vs, ws = resh(r), resh(k), resh(v), resh(logw)
    rs = rs.astype(jnp.float32)
    ks = ks.astype(jnp.float32)
    vs = vs.astype(jnp.float32)
    ws = ws.astype(jnp.float32)

    tri_lower = jnp.tril(jnp.ones((C, C), bool), k=-1)       # s < t

    def chunk_step(S, inp):
        rc, kc, vc, wc = inp                                  # [B,H,C,K/V]
        b = jnp.cumsum(wc, axis=2)                            # [B,H,C,K]
        b_prev = b - wc                                       # cumsum up to t-1
        # inter-chunk: (r_t * exp(b_{t-1})) @ S
        r_decayed = rc * jnp.exp(b_prev)
        inter = jnp.einsum("bhck,bhkv->bhcv", r_decayed, S)
        # intra-chunk scores: A[t,s] = sum_k r_t k_s exp(b_{t-1}-b_s), s<t
        # (exponent <= 0 since b decreasing and s < t)
        expo = jnp.exp(
            jnp.clip(b_prev[:, :, :, None, :] - b[:, :, None, :, :],
                     max=0.0))                              # [B,H,t,s,K]
        A = jnp.einsum("bhtk,bhsk,bhtsk->bhts", rc, kc, expo)
        A = jnp.where(tri_lower[None, None], A, 0.0)
        # diagonal (current-token bonus u)
        diag = jnp.einsum("bhck,hk,bhck->bhc", rc, u.astype(jnp.float32), kc)
        intra = jnp.einsum("bhts,bhsv->bhtv", A, vc) + \
            diag[..., None] * vc
        out_c = inter + intra
        # state update: S' = diag(exp(b_C)) S + (k_s * exp(b_C - b_s))^T @ v
        b_end = b[:, :, -1:, :]                               # [B,H,1,K]
        k_decayed = kc * jnp.exp(b_end - b)
        S_new = jnp.exp(b_end.squeeze(2))[..., None] * S + \
            jnp.einsum("bhck,bhcv->bhkv", k_decayed, vc)
        return S_new, out_c

    state, outs = lax.scan(chunk_step, state.astype(jnp.float32),
                           (rs, ks, vs, ws))
    # outs: [n,B,H,C,V] -> [B,T,H,V]
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, T, H, V)
    return out.astype(r.dtype), state


def wkv_recurrent_step(r, k, v, logw, u, state):
    """Single-token recurrence.  r,k,logw: [B,H,K]; v: [B,H,V];
    state: [B,H,K,V] -> (out [B,H,V], new_state)."""
    rf = r.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    at = kf[..., :, None] * vf[..., None, :]                  # [B,H,K,V]
    full = state + u.astype(jnp.float32)[None, :, :, None] * at
    out = jnp.einsum("bhk,bhkv->bhv", rf, full)
    state = jnp.exp(logw.astype(jnp.float32))[..., None] * state + at
    return out.astype(r.dtype), state


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _token_shift(x: jax.Array, x_prev: jax.Array) -> jax.Array:
    """shifted(x)[t] = x[t-1]; x_prev fills t=0.  x: [B,T,D], x_prev: [B,D]."""
    return jnp.concatenate([x_prev[:, None, :], x[:, :-1, :]], axis=1)


def _ddlerp(tm: Params, x, sx):
    """RWKV6 data-dependent token-shift interpolation.
    Returns xw, xk, xv, xr, xg  (each [B,T,D])."""
    dtype = x.dtype
    xxx = x + sx * tm["maa_x"].astype(dtype)
    flat = jnp.tanh(xxx @ tm["maa_w1"].astype(dtype))         # [B,T,5*R]
    B, T, _ = flat.shape
    flat = flat.reshape(B, T, 5, LORA_MIX).transpose(2, 0, 1, 3)
    mix = jnp.einsum("pbtr,prd->pbtd", flat, tm["maa_w2"].astype(dtype))
    names = ["maa_w", "maa_k", "maa_v", "maa_r", "maa_g"]
    outs = []
    for i, nm in enumerate(names):
        outs.append(x + sx * (tm[nm].astype(dtype) + mix[i]))
    return outs


def _group_norm(x: jax.Array, scale, bias, heads: int) -> jax.Array:
    """Per-head LayerNorm over the head dim (RWKV ln_x). x: [B,T,D]."""
    B, T, D = x.shape
    xh = x.reshape(B, T, heads, D // heads).astype(jnp.float32)
    mean = xh.mean(-1, keepdims=True)
    var = xh.var(-1, keepdims=True)
    xh = (xh - mean) * lax.rsqrt(var + 1e-5)
    out = xh.reshape(B, T, D) * scale.astype(jnp.float32) + \
        bias.astype(jnp.float32)
    return out.astype(x.dtype)


def _proj(h: jax.Array, w: jax.Array, layer) -> jax.Array:
    """``h @ w[layer]`` in h's dtype for a whole ``STACKED`` stack ``w``;
    ``h @ w`` for the layer's own slice where ``layer`` is None."""
    if layer is None:
        return h @ w.astype(h.dtype)
    # imported where a projection is traced, not with the model registry:
    # a process that runs another model never loads Pallas
    from repro.kernels import ops
    return ops.stacked_proj(h, w, layer)


def time_mix(cfg: ModelConfig, tm: Params, layer, x: jax.Array,
             x_prev: jax.Array, state, chunk: int):
    """Returns (out [B,T,D], new_x_prev [B,D], new_state).  ``tm`` and
    ``layer`` as ``_layer_scan`` hands them to its body."""
    dtype = x.dtype
    B, T, D = x.shape
    H = D // cfg.wkv_head_dim
    K = cfg.wkv_head_dim
    with jax.named_scope("rkvg"):
        sx = _token_shift(x, x_prev) - x
        xw, xk, xv, xr, xg = _ddlerp(tm, x, sx)

        r = _proj(xr, tm["wr"], layer).reshape(B, T, H, K)
        k = _proj(xk, tm["wk"], layer).reshape(B, T, H, K)
        v = _proj(xv, tm["wv"], layer).reshape(B, T, H, K)
        g = jax.nn.silu(_proj(xg, tm["wg"], layer))

        ww = tm["decay"].astype(jnp.float32) + (
            jnp.tanh(xw @ tm["td_w1"].astype(dtype)).astype(jnp.float32)
            @ tm["td_w2"].astype(jnp.float32))
        logw = -jnp.exp(ww).reshape(B, T, H, K)               # log decay <= 0

    with jax.named_scope("wkv"):
        if T == 1:
            out1, state = wkv_recurrent_step(
                r[:, 0], k[:, 0], v[:, 0], logw[:, 0], tm["faaaa"], state)
            out = out1[:, None]
        else:
            out, state = wkv_chunked(r, k, v, logw, tm["faaaa"], state,
                                     chunk)
    with jax.named_scope("gn"):
        out = out.reshape(B, T, D)
        out = _group_norm(out, tm["lnx_scale"], tm["lnx_bias"], H)
    with jax.named_scope("out"):
        out = _proj(out * g, tm["wo"], layer)
    return out, x[:, -1, :], state


def channel_mix(cm: Params, layer, x: jax.Array, x_prev: jax.Array):
    """Squared-ReLU channel mix.  ``key`` holds the token shift and the
    expanding projection, ``act`` the squared ReLU, ``value`` the
    projection back and the receptance gate.  ``cm`` as ``time_mix``'s
    ``tm``."""
    dtype = x.dtype
    with jax.named_scope("key"):
        sx = _token_shift(x, x_prev) - x
        xk = x + sx * cm["maa_k"].astype(dtype)
        xr = x + sx * cm["maa_r"].astype(dtype)
        kk = _proj(xk, cm["wk"], layer)
    with jax.named_scope("act"):
        kk = jax.nn.relu(kk)
        kk = kk * kk
    with jax.named_scope("value"):
        kv = _proj(kk, cm["wv"], layer)
        rr = _proj(xr, cm["wr"], layer)
        return jax.nn.sigmoid(rr) * kv, x[:, -1, :]


# ---------------------------------------------------------------------------
# Model entry points
# ---------------------------------------------------------------------------


def _layer_scan(cfg: ModelConfig, params: Params, body, x, xs=(),
                unroll: int = 1):
    """``lax.scan`` of ``body(x, layer, bp, *xs_i)`` over the layers.  On
    one device ``layer`` is the layer index and ``bp`` holds the layer's
    slice of the small leaves and the whole ``STACKED`` stacks; under an
    ``actshard`` mesh ``layer`` is None and ``bp`` the layer's slice of
    every leaf (module docstring)."""
    blocks = params["blocks"]
    if actshard.current_mesh() is not None:
        def sliced(x, scanned):
            bp, *rest = scanned
            return body(x, None, bp, *rest)
        return lax.scan(sliced, x, (blocks, *xs), unroll=unroll)

    stacks = {g: {n: blocks[g][n] for n in names}
              for g, names in STACKED.items()}
    small = {g: {n: v for n, v in p.items() if n not in STACKED.get(g, ())}
             for g, p in blocks.items()}

    def step(x, scanned):
        layer, bp, *rest = scanned
        bp = {g: {**p, **stacks.get(g, {})} for g, p in bp.items()}
        return body(x, layer, bp, *rest)

    layers = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    return lax.scan(step, x, (layers, small, *xs), unroll=unroll)


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, Any], *,
            remat: bool = True, scan_unroll: int = 1,
            **_) -> Tuple[jax.Array, jax.Array]:
    with jax.named_scope("embed"):
        x = L.embed_tokens(params["embed"], batch["tokens"],
                           cfg.compute_dtype)
        x = actshard.batch_sharded(x)
        x = L.norm_apply(cfg, params["ln0"], x)
    B, T, D = x.shape
    H = D // cfg.wkv_head_dim
    zeros_prev = jnp.zeros((B, D), cfg.compute_dtype)
    zeros_state = jnp.zeros((B, H, cfg.wkv_head_dim, cfg.wkv_head_dim),
                            jnp.float32)

    def body(x, layer, bp):
        x = actshard.batch_sharded(x)
        with jax.named_scope("ln1"):
            h = L.norm_apply(cfg, bp["ln1"], x)
        with jax.named_scope("tmix"):
            h, _, _ = time_mix(cfg, bp["tm"], layer, h, zeros_prev,
                               zeros_state, cfg.wkv_chunk)
        with jax.named_scope("res1"):
            x = x + h
        with jax.named_scope("ln2"):
            h = L.norm_apply(cfg, bp["ln2"], x)
        with jax.named_scope("cmix"):
            h, _ = channel_mix(bp["cm"], layer, h, zeros_prev)
        with jax.named_scope("res2"):
            return x + h, None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = _layer_scan(cfg, params, body, x, unroll=scan_unroll)
    with jax.named_scope("head.ln"):
        x = L.norm_apply(cfg, params["ln_f"], x)
    return x, jnp.zeros((), jnp.float32)


def logits_fn(cfg: ModelConfig, params: Params, hidden: jax.Array):
    with jax.named_scope("head.logits"):
        return actshard.logits_sharded(L.lm_logits(params["embed"], hidden))


def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> RWKVCache:
    del seq_len  # state size is O(1) in sequence length
    D = cfg.d_model
    H = D // cfg.wkv_head_dim
    K = cfg.wkv_head_dim
    nl = cfg.num_layers
    return RWKVCache(
        state=jnp.zeros((nl, batch, H, K, K), jnp.float32),
        shift_tm=jnp.zeros((nl, batch, D), cfg.compute_dtype),
        shift_cm=jnp.zeros((nl, batch, D), cfg.compute_dtype),
        step=jnp.zeros((), jnp.int32),
    )


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, Any],
            scan_unroll: int = 1, **_) -> Tuple[jax.Array, RWKVCache]:
    x = L.embed_tokens(params["embed"], batch["tokens"], cfg.compute_dtype)
    x = actshard.batch_sharded(x)
    x = L.norm_apply(cfg, params["ln0"], x)
    B, T, D = x.shape
    H = D // cfg.wkv_head_dim
    zeros_prev = jnp.zeros((B, D), cfg.compute_dtype)
    zeros_state = jnp.zeros((B, H, cfg.wkv_head_dim, cfg.wkv_head_dim),
                            jnp.float32)

    def body(x, layer, bp):
        x = actshard.batch_sharded(x)
        h = L.norm_apply(cfg, bp["ln1"], x)
        h, sh_tm, st = time_mix(cfg, bp["tm"], layer, h, zeros_prev,
                                zeros_state, cfg.wkv_chunk)
        x = x + h
        h = L.norm_apply(cfg, bp["ln2"], x)
        h, sh_cm = channel_mix(bp["cm"], layer, h, zeros_prev)
        return x + h, (st, sh_tm, sh_cm)

    x, (st, sh_tm, sh_cm) = _layer_scan(cfg, params, body, x,
                                        unroll=scan_unroll)
    x = L.norm_apply(cfg, params["ln_f"], x)
    cache = RWKVCache(state=st, shift_tm=sh_tm, shift_cm=sh_cm,
                      step=jnp.array(T, jnp.int32))
    return x[:, -1, :], cache


def decode_step(cfg: ModelConfig, params: Params, cache: RWKVCache,
                batch: Dict[str, Any], *, scan_unroll: int = 1,
                **_) -> Tuple[jax.Array, RWKVCache]:
    x = L.embed_tokens(params["embed"], batch["tokens"], cfg.compute_dtype)
    x = L.norm_apply(cfg, params["ln0"], x)

    def body(x, layer, bp, st, sh_tm, sh_cm):
        h = L.norm_apply(cfg, bp["ln1"], x)
        h, sh_tm, st = time_mix(cfg, bp["tm"], layer, h, sh_tm, st,
                                cfg.wkv_chunk)
        x = x + h
        h = L.norm_apply(cfg, bp["ln2"], x)
        h, sh_cm = channel_mix(bp["cm"], layer, h, sh_cm)
        return x + h, (st, sh_tm, sh_cm)

    x, (st, sh_tm, sh_cm) = _layer_scan(
        cfg, params, body, x,
        (cache.state, cache.shift_tm, cache.shift_cm), unroll=scan_unroll)
    x = L.norm_apply(cfg, params["ln_f"], x)
    logits = L.lm_logits(params["embed"], x)[:, 0, :]
    return logits, RWKVCache(state=st, shift_tm=sh_tm, shift_cm=sh_cm,
                             step=cache.step + 1)
