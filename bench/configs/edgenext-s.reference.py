"""Plain float32 reference of EdgeNeXt-S, written from the equations.

It imports nothing of the program.  It reads the benchmark's weight tree
(``edgenext-s.py`` makes it from the seed) and computes the logits of a
batch of uint8 images channels-last, every contraction at ``HIGHEST``
precision.  ``mode="high"`` computes every matmul and convolution in
three bf16 passes instead (``"high"`` on a TPU, written out on other
backends): the control that a comparison at the configuration's
precision has to fail.

The model is EdgeNeXt as this repository defines it.  Where that departs
from the released EdgeNeXt-S, the departure is kept here too and listed in
PERF.md: no LayerNorm after the stem, the first SDTA split passes
through and the narrower remainder split adds the leading channels of the
previous one, the SDTA MLP's residual is the XCA branch's output, GELU is
the tanh form, and there is no positional encoding.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
LN_EPS = 1e-6


def _split3(x):
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _three_pass(op, a, b):
    """op(a, b) in three bf16 passes with f32 sums: on a TPU the
    hardware's own ("high"), elsewhere written out (XLA on a TPU would
    fold the written-out split away)."""
    if jax.default_backend() == "tpu":
        return op(a, b, jnp.float32, lax.Precision.HIGH)
    ah, al = _split3(a)
    bh, bl = _split3(b)
    f = jnp.float32
    return op(ah, bh, f) + op(ah, bl, f) + op(al, bh, f)


def matmul(a, b, mode):
    def op(x, y, t, p=HIGHEST):
        return jnp.matmul(x, y, precision=p, preferred_element_type=t)
    if mode == "high":
        return _three_pass(op, a, b)
    return op(a, b, jnp.float32)


def einsum(spec, a, b, mode):
    def op(x, y, t, p=HIGHEST):
        return jnp.einsum(spec, x, y, precision=p, preferred_element_type=t)
    if mode == "high":
        return _three_pass(op, a, b)
    return op(a, b, jnp.float32)


def conv(x, w, stride, padding, groups, mode):
    """x [B,H,W,Cin] channels-last, w [kh,kw,Cin/groups,Cout]."""
    def op(a, b, t, p=HIGHEST):
        return lax.conv_general_dilated(
            a, b, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=p,
            preferred_element_type=t)
    if mode == "high":
        return _three_pass(op, x, w)
    return op(x, w, jnp.float32)


def layer_norm(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def depthwise(x, w, b, mode):
    """Per-channel k x k convolution, zero padding keeps the size."""
    k = w.shape[0]
    pad = ((k // 2, k // 2), (k // 2, k // 2))
    return conv(x, w[:, :, None, :], 1, pad, x.shape[-1], mode) + b


def mlp(p, x, mode):
    h = gelu(matmul(x, p["pw1_w"], mode) + p["pw1_b"])
    return matmul(h, p["pw2_w"], mode) + p["pw2_b"]


def conv_block(p, x, mode):
    h = depthwise(x, p["dw_w"], p["dw_b"], mode)
    h = mlp(p, layer_norm(h, p["ln"]), mode)
    return x + p["gamma"] * h


def xca(p, x, heads, mode):
    """Attention across channels: per head a (C/h x C/h) map over the
    L2-normalised token axis, scaled by a learned temperature."""
    B, N, C = x.shape
    d = C // heads
    qkv = matmul(x, p["qkv_w"], mode) + p["qkv_b"]          # [B,N,3C]
    q, k, v = (qkv[..., i * C:(i + 1) * C].reshape(B, N, heads, d)
               .transpose(0, 2, 3, 1) for i in range(3))     # [B,h,d,N]
    q = q / (jnp.sqrt((q * q).sum(-1, keepdims=True)) + 1e-6)
    k = k / (jnp.sqrt((k * k).sum(-1, keepdims=True)) + 1e-6)
    a = jax.nn.softmax(einsum("bhcn,bhen->bhce", q, k, mode) * p["temp"],
                       axis=-1)
    out = einsum("bhce,bhen->bhcn", a, v, mode)              # [B,h,d,N]
    out = out.transpose(0, 3, 1, 2).reshape(B, N, C)
    return matmul(out, p["proj_w"], mode) + p["proj_b"]


def sdta_block(p, x, heads, scales, mode):
    B, H, W, C = x.shape
    width = -(-C // scales)
    bounds = [min(i * width, C) for i in range(scales)] + [C]
    parts = [x[..., bounds[i]:bounds[i + 1]] for i in range(scales)]
    outs, prev = [parts[0]], None
    for i, part in enumerate(parts[1:]):
        inp = part if prev is None else part + prev[..., :part.shape[-1]]
        prev = depthwise(inp, p["dw"][i]["w"], p["dw"][i]["b"], mode)
        outs.append(prev)
    h = jnp.concatenate(outs, -1).reshape(B, H * W, C)
    h = h + p["gamma_x"] * xca(p, layer_norm(h, p["ln_x"]), heads, mode)
    h = h + p["gamma_m"] * mlp(p, layer_norm(h, p["ln_m"]), mode)
    return h.reshape(B, H, W, C)


def forward(sizes, w, images, mode="exact"):
    """uint8 images [B, img, img, 3] -> float32 logits [B, classes]."""
    x = images.astype(jnp.float32)
    for si, st in enumerate(w["stages"]):
        if si == 0:
            x = conv(x, st["down_w"], 4, "VALID", 1, mode) + st["down_b"]
        else:
            x = layer_norm(x, st["down_ln"])
            x = conv(x, st["down_w"], 2, "VALID", 1, mode) + st["down_b"]
        for p in st["conv_blocks"]:
            x = conv_block(p, x, mode)
        for p in st["sdta_blocks"]:
            x = sdta_block(p, x, sizes["heads"], sizes["sdta_scales"][si],
                           mode)
    x = layer_norm(x.mean((1, 2)), w["head_ln"])
    return matmul(x, w["head_w"], mode) + w["head_b"]
