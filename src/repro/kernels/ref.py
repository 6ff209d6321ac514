"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _act(name: str, x: jax.Array) -> jax.Array:
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if name == "silu":
        return jax.nn.silu(x)
    if name == "relu2":
        r = jnp.maximum(x, 0.0)
        return r * r
    raise ValueError(name)


def fused_ibn_ref(x, w1, w2, wg=None, *, activation: str = "gelu"):
    xf = x.astype(jnp.float32)
    up = xf @ w1.astype(jnp.float32)
    if wg is not None:
        t = _act(activation, xf @ wg.astype(jnp.float32)) * up
    else:
        t = _act(activation, up)
    out = t.astype(x.dtype).astype(jnp.float32) @ w2.astype(jnp.float32)
    return out.astype(x.dtype)


def matmul_ln_ref(x, w, b, gamma, beta, *, eps: float = 1e-6):
    y = x.astype(jnp.float32) @ w.astype(jnp.float32) \
        + b.astype(jnp.float32)
    mean = y.mean(-1, keepdims=True)
    var = jnp.square(y - mean).mean(-1, keepdims=True)
    yn = (y - mean) * lax.rsqrt(var + eps)
    yn = yn * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return yn.astype(x.dtype)


def attention_ref(q, k, v, *, causal: bool = True,
                  window: Optional[int] = None,
                  scale: Optional[float] = None):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    scale_ = scale if scale is not None else D ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale_
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def depthwise_conv2d_ref(x, w, b):
    """x: [B,H,W,C]; w: [fy,fx,C]; b: [C] — SAME padding."""
    C = x.shape[-1]
    y = lax.conv_general_dilated(
        x.astype(jnp.float32), w[:, :, None, :].astype(jnp.float32),
        window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=C)
    return (y + b.astype(jnp.float32)).astype(x.dtype)


def wkv_ref(r, k, v, logw, u, state=None):
    """Naive per-token WKV6 recurrence.  r,k,logw: [BH,T,K]; v: [BH,T,V];
    u: [BH,K]; state: [BH,K,V], zeros if None.  Returns (out [BH,T,V],
    final_state [BH,K,V] f32)."""
    BH, T, K = r.shape
    V = v.shape[-1]
    rf = r.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    wf = logw.astype(jnp.float32)
    uf = u.astype(jnp.float32)

    def step(S, t):
        rt, kt, vt, wt = rf[:, t], kf[:, t], vf[:, t], wf[:, t]
        at = kt[..., :, None] * vt[..., None, :]          # [BH,K,V]
        out_t = jnp.einsum("bk,bkv->bv", rt,
                           S + uf[:, :, None] * at)
        S = jnp.exp(wt)[..., None] * S + at
        return S, out_t

    S0 = (jnp.zeros((BH, K, V), jnp.float32) if state is None
          else state.astype(jnp.float32))
    S, outs = lax.scan(step, S0, jnp.arange(T))
    return outs.transpose(1, 0, 2).astype(r.dtype), S
