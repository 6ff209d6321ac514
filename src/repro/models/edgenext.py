"""EdgeNeXt-S [arXiv:2206.10589] — the paper's benchmark hybrid ViT.

Stem (4x4 s4 patchify) -> 4 stages of Conv encoder blocks (ConvNeXt-style
inverted bottlenecks with kxk depthwise conv) with an SDTA block (split
depthwise + transposed channel attention, XCA) at the end of stages 2-4;
2x2 s2 downsample layers between stages; global-pool classifier head.

All tensors are channels-last [B, H, W, C].  The inverted-bottleneck MLP in
every block can run through three schedules:
  - "plain"  : materialize the 4x-expanded intermediate (the paper's baseline)
  - "chunked": depth-first tiles over d_ff (paper contribution C3, XLA level)
  - the Pallas kernel in ``repro.kernels.fused_ibn`` is the TPU realization
The depthwise convolutions map to the ``C|FX`` dataflow (contribution C1,
kernels/depthwise_conv.py).

Simplifications vs the released checkpoints (documented in DESIGN.md):
no stochastic depth, no positional embedding on the first SDTA block.

Every op sits in a ``jax.named_scope`` spelled as the scheduler's layer
(``repro.core.workload.edgenext_workload``): ``forward`` opens the block
scope (``s1.conv0``, ``s2.sdta0``) and the block its layers' (``dw``,
``pw1``, ...), so an op's HLO ``op_name`` holds ``s1.conv0/pw1``, which
``repro.obs.layers`` reads back as the layer ``s1.conv0.pw1``.  A
weight's cast sits in the scope of the layer that uses it.  Scopes are
metadata: the compiled program is the same without them.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.edgenext_s import EdgeNeXtConfig
from repro.models.params import ParamDef

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Functional conv / norm helpers (channels-last)
# ---------------------------------------------------------------------------


def conv2d(x: jax.Array, w: jax.Array, b: jax.Array, stride: int = 1,
           padding: str = "SAME") -> jax.Array:
    """x: [B,H,W,Cin], w: [kh,kw,Cin,Cout]."""
    y = lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def depthwise_conv2d(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """x: [B,H,W,C], w: [kh,kw,C] — per-channel (C|FX dataflow) conv."""
    C = x.shape[-1]
    y = lax.conv_general_dilated(
        x, w[:, :, None, :], window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=C)
    return y + b


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + 1e-6)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _ln_defs(c: int) -> Params:
    return {"scale": ParamDef((c,), ("embed",), "ones"),
            "bias": ParamDef((c,), ("embed",), "zeros")}


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


def _conv_block_defs(c: int, k: int, expan: int) -> Params:
    return {
        "dw_w": ParamDef((k, k, c), (None, None, "embed")),
        "dw_b": ParamDef((c,), ("embed",), "zeros"),
        "ln": _ln_defs(c),
        "pw1_w": ParamDef((c, expan * c), ("embed", "ff")),
        "pw1_b": ParamDef((expan * c,), ("ff",), "zeros"),
        "pw2_w": ParamDef((expan * c, c), ("ff", "embed")),
        "pw2_b": ParamDef((c,), ("embed",), "zeros"),
        "gamma": ParamDef((c,), ("embed",), "ones", scale=1e-6),
    }


def _sdta_defs(c: int, heads: int, scales: int, expan: int) -> Params:
    # hierarchical dw convs act on the (scales-1) later channel splits
    widths = _split_widths(c, scales)
    dw = [{
        "w": ParamDef((3, 3, w), (None, None, "embed")),
        "b": ParamDef((w,), ("embed",), "zeros"),
    } for w in widths[1:]]
    return {
        "dw": dw,
        "ln_x": _ln_defs(c),
        "qkv_w": ParamDef((c, 3 * c), ("embed", "ff")),
        "qkv_b": ParamDef((3 * c,), ("ff",), "zeros"),
        "temp": ParamDef((heads, 1, 1), (None, None, None), "ones"),
        "proj_w": ParamDef((c, c), ("ff", "embed")),
        "proj_b": ParamDef((c,), ("embed",), "zeros"),
        "gamma_x": ParamDef((c,), ("embed",), "ones", scale=1e-6),
        "ln_m": _ln_defs(c),
        "pw1_w": ParamDef((c, expan * c), ("embed", "ff")),
        "pw1_b": ParamDef((expan * c,), ("ff",), "zeros"),
        "pw2_w": ParamDef((expan * c, c), ("ff", "embed")),
        "pw2_b": ParamDef((c,), ("embed",), "zeros"),
        "gamma_m": ParamDef((c,), ("embed",), "ones", scale=1e-6),
    }


def _split_widths(c: int, scales: int) -> List[int]:
    """Res2Net-style channel split widths (last split takes the remainder)."""
    if scales == 1:
        return [c]
    base = int(math.ceil(c / scales))
    widths = [base] * (scales - 1)
    widths.append(c - base * (scales - 1))
    return widths


def param_defs(cfg: EdgeNeXtConfig) -> Params:
    stages: List[Params] = []
    for si in range(4):
        c = cfg.dims[si]
        k = cfg.kernel_sizes[si]
        n_conv = cfg.depths[si] - cfg.sdta_blocks[si]
        stage: Params = {
            "conv_blocks": [_conv_block_defs(c, k, cfg.expan_ratio)
                            for _ in range(n_conv)],
            "sdta_blocks": [_sdta_defs(c, cfg.heads, cfg.sdta_scales[si],
                                       cfg.expan_ratio)
                            for _ in range(cfg.sdta_blocks[si])],
        }
        if si == 0:
            stage["down_w"] = ParamDef((4, 4, cfg.in_channels, c),
                                       (None, None, None, "embed"))
            stage["down_b"] = ParamDef((c,), ("embed",), "zeros")
        else:
            cp = cfg.dims[si - 1]
            stage["down_ln"] = _ln_defs(cp)
            stage["down_w"] = ParamDef((2, 2, cp, c),
                                       (None, None, "embed", "ff"))
            stage["down_b"] = ParamDef((c,), ("ff",), "zeros")
        stages.append(stage)
    return {
        "stages": stages,
        "head_ln": _ln_defs(cfg.dims[-1]),
        "head_w": ParamDef((cfg.dims[-1], cfg.num_classes),
                           ("embed", "vocab")),
        "head_b": ParamDef((cfg.num_classes,), ("vocab",), "zeros"),
    }


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _ibn_mlp(bp: Params, x: jax.Array, ibn_chunks: int = 0) -> jax.Array:
    """Pointwise inverted bottleneck: pw-expand -> GELU -> pw-project.

    ``ibn_chunks > 1`` = depth-first C3 schedule (intermediate tiled over
    the expanded channel dim, live tile bounded to d_ff/ibn_chunks).
    """
    dtype = x.dtype
    with jax.named_scope("pw1"):
        w1 = bp["pw1_w"].astype(dtype)
        b1 = bp["pw1_b"].astype(dtype)
    with jax.named_scope("pw2"):
        w2 = bp["pw2_w"].astype(dtype)
        b2 = bp["pw2_b"].astype(dtype)
    if ibn_chunks <= 1:
        with jax.named_scope("pw1"):
            t = x @ w1 + b1
        with jax.named_scope("act"):
            t = jax.nn.gelu(t, approximate=True)
        with jax.named_scope("pw2"):
            return t @ w2 + b2
    f = w1.shape[-1]
    assert f % ibn_chunks == 0
    tile = f // ibn_chunks
    with jax.named_scope("pw1"):
        w1_t = w1.reshape(-1, ibn_chunks, tile).transpose(1, 0, 2)
        b1_t = b1.reshape(ibn_chunks, tile)
    with jax.named_scope("pw2"):
        w2_t = w2.reshape(ibn_chunks, tile, -1)
        out0 = jnp.broadcast_to(b2, x.shape[:-1] + (w2.shape[-1],)
                                ).astype(dtype)

    def step(acc, ws):
        w1c, b1c, w2c = ws
        with jax.named_scope("pw1"):
            t = x @ w1c + b1c
        with jax.named_scope("act"):
            t = jax.nn.gelu(t, approximate=True)
        with jax.named_scope("pw2"):
            return acc + t @ w2c, None

    out, _ = lax.scan(step, out0, (w1_t, b1_t, w2_t))
    return out


def conv_encoder_block(bp: Params, x: jax.Array,
                       ibn_chunks: int = 0) -> jax.Array:
    """dw conv kxk -> LN -> pw 4x -> GELU -> pw -> layer scale -> residual."""
    with jax.named_scope("dw"):
        h = depthwise_conv2d(x, bp["dw_w"].astype(x.dtype),
                             bp["dw_b"].astype(x.dtype))
    with jax.named_scope("ln"):
        h = layer_norm(h, bp["ln"]["scale"], bp["ln"]["bias"])
    h = _ibn_mlp(bp, h, ibn_chunks)
    with jax.named_scope("res"):
        return x + bp["gamma"].astype(x.dtype) * h


def xca(bp: Params, x: jax.Array, heads: int) -> jax.Array:
    """Cross-covariance (transposed) attention over the channel dim.

    x: [B,N,C].  Attention matrix is [C/h, C/h] per head — channel mixing
    with token-dim reduction, the transformer piece of SDTA.
    """
    B, N, C = x.shape
    dtype = x.dtype
    with jax.named_scope("qkv"):
        qkv = x @ bp["qkv_w"].astype(dtype) + bp["qkv_b"].astype(dtype)
        qkv = qkv.reshape(B, N, 3, heads, C // heads)
        q, k, v = [qkv[:, :, i].transpose(0, 2, 3, 1) for i in range(3)]
    # q,k,v: [B, h, C/h, N] — channels are the "tokens" of this attention
    with jax.named_scope("qk"):
        qf = q.astype(jnp.float32)
        kf = k.astype(jnp.float32)
        qf = qf / (jnp.linalg.norm(qf, axis=-1, keepdims=True) + 1e-6)
        kf = kf / (jnp.linalg.norm(kf, axis=-1, keepdims=True) + 1e-6)
        scores = jnp.einsum("bhcn,bhdn->bhcd", qf, kf) \
            * bp["temp"].astype(jnp.float32)
    with jax.named_scope("sm"):
        attn = jax.nn.softmax(scores, axis=-1)
    with jax.named_scope("av"):
        out = jnp.einsum("bhcd,bhdn->bhcn", attn.astype(dtype), v)
    with jax.named_scope("proj"):
        out = out.transpose(0, 3, 1, 2).reshape(B, N, C)
        return out @ bp["proj_w"].astype(dtype) + bp["proj_b"].astype(dtype)


def sdta_block(bp: Params, x: jax.Array, heads: int, scales: int,
               ibn_chunks: int = 0) -> jax.Array:
    """Split-depthwise cascade + XCA + inverted-bottleneck MLP."""
    B, H, W, C = x.shape
    dtype = x.dtype
    widths = _split_widths(C, scales)
    if scales > 1:
        with jax.named_scope("dw0"):
            splits = jnp.split(x, np_cumsum(widths)[:-1], axis=-1)
        outs = [splits[0]]
        prev = None
        for i, sp in enumerate(splits[1:]):
            with jax.named_scope(f"dw{i}"):
                # the remainder split can be narrower than the one before
                # it (160 = 54 + 54 + 52): it adds the leading channels
                inp = sp if prev is None else sp + prev[..., :sp.shape[-1]]
                prev = depthwise_conv2d(inp,
                                        bp["dw"][i]["w"].astype(dtype),
                                        bp["dw"][i]["b"].astype(dtype))
            outs.append(prev)
        with jax.named_scope(f"dw{len(splits) - 2}"):
            h = jnp.concatenate(outs, axis=-1)
    else:
        h = x
    # transposed attention on flattened tokens
    with jax.named_scope("ln_x"):
        hn = h.reshape(B, H * W, C)
        a = layer_norm(hn, bp["ln_x"]["scale"], bp["ln_x"]["bias"])
    a = xca(bp, a, heads)
    with jax.named_scope("res"):
        hn = hn + bp["gamma_x"].astype(dtype) * a
    # inverted-bottleneck MLP
    with jax.named_scope("ln_m"):
        m = layer_norm(hn, bp["ln_m"]["scale"], bp["ln_m"]["bias"])
    m = _ibn_mlp(bp, m, ibn_chunks)
    with jax.named_scope("res"):
        hn = hn + bp["gamma_m"].astype(dtype) * m
        return hn.reshape(B, H, W, C)


def np_cumsum(widths: List[int]) -> List[int]:
    out, s = [], 0
    for w in widths:
        s += w
        out.append(s)
    return out


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


def forward(cfg: EdgeNeXtConfig, params: Params, images: jax.Array, *,
            ibn_chunks: int = 0) -> jax.Array:
    """images: [B, img, img, 3] -> logits [B, num_classes]."""
    for si in range(4):
        sp = params["stages"][si]
        if si == 0:
            with jax.named_scope("stem"):
                x = images.astype(jnp.dtype(cfg.dtype))
                x = conv2d(x, sp["down_w"].astype(x.dtype),
                           sp["down_b"].astype(x.dtype), stride=4,
                           padding="VALID")
        else:
            with jax.named_scope(f"s{si}.down_ln"):
                x = layer_norm(x, sp["down_ln"]["scale"],
                               sp["down_ln"]["bias"])
            with jax.named_scope(f"s{si}.down"):
                x = conv2d(x, sp["down_w"].astype(x.dtype),
                           sp["down_b"].astype(x.dtype), stride=2,
                           padding="VALID")
        for bi, bp in enumerate(sp["conv_blocks"]):
            with jax.named_scope(f"s{si}.conv{bi}"):
                x = conv_encoder_block(bp, x, ibn_chunks)
        for bi, bp in enumerate(sp["sdta_blocks"]):
            with jax.named_scope(f"s{si}.sdta{bi}"):
                x = sdta_block(bp, x, cfg.heads, cfg.sdta_scales[si],
                               ibn_chunks)
    with jax.named_scope("head.ln"):
        x = x.mean(axis=(1, 2))                               # global pool
        x = layer_norm(x, params["head_ln"]["scale"],
                       params["head_ln"]["bias"])
    with jax.named_scope("head.fc"):
        return (x @ params["head_w"].astype(x.dtype)
                + params["head_b"].astype(x.dtype)).astype(jnp.float32)
