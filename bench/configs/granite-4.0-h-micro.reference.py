"""Plain float32 reference of IBM Granite 4.0-H Micro, written from the
layer equations of HF ``granitemoehybrid`` (dense).

It imports nothing of the program.  It reads the benchmark's weight tree
(``granite-4.0-h-micro.py`` makes it from the seed) and computes the
next-token logits of each prompt, one prompt and one layer at a time:

    h = E[tokens] * 12
    h = h + 0.22 * Mixer_i(RMSNorm(h));  h = h + 0.22 * MLP(RMSNorm(h))
    logits = RMSNorm(h) @ E^T / 8

The Mamba-2 mixer applies the published in_proj ``[z | xBC | dt]``
(``w_zx``, ``w_bc`` and ``w_dt`` side by side), the causal depthwise
conv with its bias and silu, and the SSD as the sequential recurrence,
token by token:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,   y_t = S_t C_t + D x_t

then ``RMSNorm(y * silu(z))`` over all 4096 channels.  Attention is
causal softmax(q k^T / 64) v with each KV head shared by four query
heads, a block of queries at a time so that the scores fit.

Every matmul is float32 at ``HIGHEST`` precision.  ``mode="int8"`` or
``"fp8"`` computes every weight matmul with its operands in that type
instead (activations scaled per row, weights per output column, exact
sums): the controls one precision step below the configuration's bf16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
Q_BLOCK = 512              # attention queries per block


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


def rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def causal_conv(x, w, b):
    """x: [B, T, C]; w: [width, C]: y_t = sum_i w_i x_{t - width + 1 + i}
    + b, zeros before the first token."""
    width, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(xp[:, i:i + T] * w[i] for i in range(width)) + b


def ssd(x, dt, A, B, C):
    """x: [B, T, H, P]; dt: [B, T, H]; A: [H]; B, C: [B, T, N]."""
    b, _, H, P = x.shape
    S0 = jnp.zeros((b, H, P, B.shape[-1]), jnp.float32)

    def step(S, inp):
        xt, dtt, bt, ct = inp
        S = jnp.exp(dtt * A)[..., None, None] * S + \
            (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
        return S, jnp.einsum("bhpn,bn->bhp", S, ct, precision=HIGHEST)

    _, y = lax.scan(step, S0, tuple(a.swapaxes(0, 1) for a in (x, dt, B, C)))
    return y.swapaxes(0, 1)                                 # [B, T, H, P]


def mamba(s, mode, p, x):
    H, P, N = s["mamba_n_heads"], s["mamba_d_head"], s["mamba_d_state"]
    di = H * P
    b, T, _ = x.shape
    w_in = jnp.concatenate([p["w_zx"], p["w_bc"], p["w_dt"]], axis=-1)
    zxbcdt = matmul(x, w_in, mode)
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * N],
                  zxbcdt[..., 2 * di + 2 * N:])
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xs = xbc[..., :di].reshape(b, T, H, P)
    B, C = xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd(xs, dt, -jnp.exp(p["A_log"]), B, C) + p["D"][:, None] * xs
    y = rms_norm(y.reshape(b, T, di) * jax.nn.silu(z), p["gnorm"]["scale"],
                 s["rms_norm_eps"])
    return matmul(y, p["w_out"], mode)


def attention(s, mode, p, x):
    h, hk, hd = s["num_heads"], s["num_kv_heads"], s["head_dim"]
    b, T, _ = x.shape
    q = matmul(x, p["wq"], mode).reshape(b, T, h, hd)
    k = jnp.repeat(matmul(x, p["wk"], mode).reshape(b, T, hk, hd),
                   h // hk, axis=2)
    v = jnp.repeat(matmul(x, p["wv"], mode).reshape(b, T, hk, hd),
                   h // hk, axis=2)
    nq = -(-T // Q_BLOCK)
    qp = jnp.pad(q, ((0, 0), (0, nq * Q_BLOCK - T), (0, 0), (0, 0)))

    def block(i):
        qi = lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK, axis=1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision=HIGHEST) \
            * s["attention_multiplier"]
        pos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(pos[:, None] >= jnp.arange(T)[None, :], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                          precision=HIGHEST)

    o = lax.map(block, jnp.arange(nq))                      # [nq, b, Qb, h, hd]
    o = o.swapaxes(0, 1).reshape(b, nq * Q_BLOCK, h * hd)[:, :T]
    return matmul(o, p["wo"], mode)


def mlp(s, mode, p, x):
    gu = matmul(x, p["w_in"], mode)
    f = s["d_ff"]
    return matmul(jax.nn.silu(gu[..., :f]) * gu[..., f:], p["w_out"], mode)


def layer(s, mode, kind, pm, pl, x):
    """One layer: its mixer's weights ``pm``, its MLP's ``pl``."""
    r, eps = s["residual_multiplier"], s["rms_norm_eps"]
    mix = mamba if kind == "mamba" else attention
    x = x + r * mix(s, mode, pm, rms_norm(x, pm["ln"]["scale"], eps))
    return x + r * mlp(s, mode, pl, rms_norm(x, pl["ln"]["scale"], eps))


def logits(sizes, w, tokens, mode="exact"):
    """int32 tokens [B, T] -> float32 next-token logits [B, vocab], one
    prompt and one layer at a time (each layer's weights sliced out and
    made float32 in turn)."""
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    E = w["embed"]["embedding"]
    step = {kind: jax.jit(functools.partial(layer, sizes, mode, kind))
            for kind in ("mamba", "attention")}
    out = []
    for prompt in tokens:
        x = jnp.take(E, prompt[None], axis=0).astype(jnp.float32) \
            * sizes["embedding_multiplier"]
        seen = {"mamba": 0, "attention": 0}
        for li, kind in enumerate(sizes["layer_types"]):
            stack = w["mamba" if kind == "mamba" else "attn"]
            i = seen[kind]
            seen[kind] += 1
            x = step[kind](f32(jax.tree.map(lambda a: a[i], stack)),
                           f32(jax.tree.map(lambda a: a[li], w["mlp"])), x)
        x = rms_norm(x[:, -1], w["ln_f"]["scale"].astype(jnp.float32),
                     sizes["rms_norm_eps"])
        out.append(matmul(x, E.astype(jnp.float32).T, mode)
                   / sizes["logits_scaling"])
    return jnp.concatenate(out)
