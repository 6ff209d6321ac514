"""Plain float32 reference of RWKV-6 "Finch", written from the equations.

It imports nothing of the program.  It reads the benchmark's weight tree
(``rwkv6-1.6b.py`` makes it from the seed) and computes the next-token
logits of each prompt, one layer at a time, with the WKV6 recurrence
token by token:

    out_t = r_t . (S + diag(u) k_t v_t^T),  S <- diag(w_t) S + k_t v_t^T

Every matmul is float32 at ``HIGHEST`` precision.  ``mode="int8"`` or
``"fp8"`` computes every matmul with its operands in that type instead
(activations scaled per row, weights per output column, exact sums): the
controls one precision step below the configuration's bf16.

Departure from the released RWKV-6, kept because the program has it and
listed in PERF.md: the per-head GroupNorm after the WKV uses eps 1e-5
(the release uses 64e-5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
LN_EPS = 1e-5
GN_EPS = 1e-5


def _quantize(x, axis, mode):
    """x scaled per slice along ``axis`` into int8 or fp8 (e4m3), and the
    scale: the value is q * scale."""
    top = 127.0 if mode == "int8" else 448.0
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if mode == "int8":
        return jnp.round(x / scale).astype(jnp.int8), scale
    return (x / scale).astype(jnp.float8_e4m3fn), scale


def matmul(a, w, mode):
    """a [..., K] @ w [K, N]; modes "int8" and "fp8" scale a per row and
    w per column and sum in int32 or float32."""
    if mode == "exact":
        return jnp.matmul(a, w, precision=HIGHEST)
    qa, sa = _quantize(a, -1, mode)
    qw, sw = _quantize(w, 0, mode)
    acc = lax.dot_general(qa, qw, (((qa.ndim - 1,), (0,)), ((), ())),
                          preferred_element_type=jnp.int32
                          if mode == "int8" else jnp.float32)
    return acc.astype(jnp.float32) * sa * sw


def layer_norm(x, p, eps=LN_EPS):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def shifted(x):
    """x[t-1], zeros at t = 0.  x: [B, T, D]."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def wkv(r, k, v, w, u):
    """r, k, w: [B, T, H, K]; v: [B, T, H, V]; u: [H, K]."""
    B, T, H, K = r.shape
    S0 = jnp.zeros((B, H, K, v.shape[-1]), jnp.float32)

    def step(S, x):
        rt, kt, vt, wt = x                                  # [B, H, *]
        kv = kt[..., :, None] * vt[..., None, :]
        out = jnp.einsum("bhk,bhkv->bhv", rt, S + u[..., None] * kv,
                         precision=HIGHEST)
        return wt[..., None] * S + kv, out

    xs = tuple(a.transpose(1, 0, 2, 3) for a in (r, k, v, w))
    _, out = lax.scan(step, S0, xs)
    return out.transpose(1, 0, 2, 3)                        # [B, T, H, V]


def time_mix(p, x, s, mode):
    B, T, D = x.shape
    H = D // s["wkv_head_dim"]
    R = s["lora_mix"]
    dx = shifted(x) - x
    lora = jnp.tanh(matmul(x + dx * p["maa_x"], p["maa_w1"], mode))
    lora = lora.reshape(B, T, 5, R)
    mixed = [x + dx * (p[name] + matmul(lora[:, :, i], p["maa_w2"][i], mode))
             for i, name in enumerate(("maa_w", "maa_k", "maa_v", "maa_r",
                                       "maa_g"))]
    xw, xk, xv, xr, xg = mixed

    def heads(a):
        return a.reshape(B, T, H, -1)
    r = heads(matmul(xr, p["wr"], mode))
    k = heads(matmul(xk, p["wk"], mode))
    v = heads(matmul(xv, p["wv"], mode))
    g = jax.nn.silu(matmul(xg, p["wg"], mode))
    d = p["decay"] + matmul(jnp.tanh(matmul(xw, p["td_w1"], mode)),
                            p["td_w2"], mode)
    w = heads(jnp.exp(-jnp.exp(d)))
    y = wkv(r, k, v, w, p["faaaa"])                         # [B, T, H, K]
    mu = y.mean(-1, keepdims=True)
    var = ((y - mu) ** 2).mean(-1, keepdims=True)
    y = ((y - mu) / jnp.sqrt(var + GN_EPS)).reshape(B, T, D)
    y = y * p["lnx_scale"] + p["lnx_bias"]
    return matmul(y * g, p["wo"], mode)


def channel_mix(p, x, mode):
    dx = shifted(x) - x
    k = jnp.square(jax.nn.relu(matmul(x + dx * p["maa_k"], p["wk"], mode)))
    r = jax.nn.sigmoid(matmul(x + dx * p["maa_r"], p["wr"], mode))
    return r * matmul(k, p["wv"], mode)


def block(s, mode, p, x):
    x = x + time_mix(p["tm"], layer_norm(x, p["ln1"]), s, mode)
    return x + channel_mix(p["cm"], layer_norm(x, p["ln2"]), mode)


def logits(sizes, w, tokens, mode="exact"):
    """int32 tokens [B, T] -> float32 next-token logits [B, vocab],
    layer by layer (each layer's weights sliced out in turn)."""
    x = layer_norm(jnp.take(w["embed"]["embedding"], tokens, axis=0),
                   w["ln0"])
    step = jax.jit(functools.partial(block, sizes, mode))
    for i in range(sizes["num_layers"]):
        x = step(jax.tree.map(lambda a: a[i], w["blocks"]), x)
    x = layer_norm(x[:, -1], w["ln_f"])
    return matmul(x, w["embed"]["unembed"], mode)

