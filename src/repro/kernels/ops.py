"""jit'd public wrappers for the Pallas kernels.

Each wrapper pads inputs to kernel tile multiples, dispatches
``interpret=True`` automatically on non-TPU backends (the kernels are
written for TPU BlockSpec tiling; interpret mode executes the kernel body
in Python for correctness validation on CPU), and unpads the result.

Ragged extents are first-class: a block size that does not divide the
extent is honored, not shrunk — the wrapper pads the operand to the next
block multiple and forwards the true extent (``valid_f`` / ``valid_k`` /
``kv_len``) so the kernel's in-kernel edge predication masks the padded
final block.  This is what lets ``search.lower`` emit the searched tile
sizes unchanged on EdgeNeXt's odd channel/pixel extents.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import (depthwise_conv as _dw, flash_attention as _fa,
                           fused_ibn as _ibn, matmul_ln as _mln,
                           rwkv_chunk as _wkv, ssd_chunk as _ssd,
                           stacked_proj as _sp)


_LANE = 128      # TPU lane width: the last block dim's granularity


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def fused_ibn(x: jax.Array, w1: jax.Array, w2: jax.Array,
              wg: Optional[jax.Array] = None, *, activation: str = "gelu",
              block_m: int = 256, block_f: int = 512,
              interpret: Optional[bool] = None) -> jax.Array:
    """act(x @ w1 [* gate]) @ w2 for x of any leading shape [..., D]."""
    interp = (not _on_tpu()) if interpret is None else interpret
    lead = x.shape[:-1]
    D = x.shape[-1]
    xf = x.reshape(-1, D)
    M = xf.shape[0]
    F = w1.shape[1]
    bm = min(block_m, M)
    xp = _pad_to(xf, 0, bm)
    bf = min(block_f, F)
    w1p = _pad_to(w1, 1, bf)
    w2p = _pad_to(w2, 0, bf)
    wgp = _pad_to(wg, 1, bf) if wg is not None else None
    out = _ibn.fused_ibn(xp, w1p, w2p, wgp, activation=activation,
                         block_m=bm, block_f=bf, interpret=interp,
                         valid_f=F)
    return out[:M].reshape(*lead, w2.shape[1])


# stacked_proj's blocks, chosen on a v5e at M = 512 (PERF.md section 6):
# a 512 x 2048 f32 weight tile, double-buffered, fits the default scoped
# VMEM; 1024 x 2048 does not
_PROJ_BM, _PROJ_BK, _PROJ_BN = 512, 512, 2048


def _lane_block(n: int, block: int) -> int:
    """A weight block for extent ``n``: the whole extent if it fits in
    ``block``, else the largest multiple of 128 up to ``block`` that
    divides ``n``.  A weight stack is read in place and never padded:
    a pad would copy the whole stack on every call."""
    if n <= block:
        return n
    for b in range(block // _LANE * _LANE, 0, -_LANE):
        if n % b == 0:
            return b
    raise ValueError(f"no multiple of {_LANE} up to {block} divides {n}")


def stacked_proj(x: jax.Array, w: jax.Array, layer: jax.Array, *,
                 interpret: Optional[bool] = None) -> jax.Array:
    """x [..., K] @ w[layer] for a weight stack w [L, K, N] in its stored
    dtype, in x's dtype: each weight tile is cast to x's dtype in VMEM,
    with f32 accumulation.  Differentiable in x and w.  Unlike the other
    wrappers it pads only x: its weight blocks divide K and N or are
    whole (``_lane_block``)."""
    interp = (not _on_tpu()) if interpret is None else interpret
    lead = x.shape[:-1]
    out = _stacked_proj(x.reshape(-1, x.shape[-1]), w,
                        jnp.asarray(layer, jnp.int32), interp)
    return out.reshape(*lead, w.shape[2])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _stacked_proj(x, w, layer, interp):
    (M, K), N = x.shape, w.shape[2]
    bm = min(_PROJ_BM, M)
    out = _sp.stacked_proj(_pad_to(x, 0, bm), w, layer, block_m=bm,
                           block_k=_lane_block(K, _PROJ_BK),
                           block_n=_lane_block(N, _PROJ_BN),
                           interpret=interp)
    return out[:M]


def _stacked_proj_fwd(x, w, layer, interp):
    return _stacked_proj(x, w, layer, interp), (x, w, layer)


def _stacked_proj_bwd(interp, res, g):
    """dx through the cast layer; dw is that layer's gradient in a zeroed
    stack, which XLA folds into the layer scan's gradient accumulation as
    an in-place update of the one layer."""
    x, w, layer = res
    wl = lax.dynamic_index_in_dim(w, layer, keepdims=False)
    dx = jnp.dot(g, wl.astype(x.dtype).T, preferred_element_type=jnp.float32)
    dwl = jnp.dot(x.T, g, preferred_element_type=jnp.float32)
    dw = lax.dynamic_update_index_in_dim(jnp.zeros_like(w),
                                         dwl.astype(w.dtype), layer, 0)
    return dx.astype(x.dtype), dw, None


_stacked_proj.defvjp(_stacked_proj_fwd, _stacked_proj_bwd)


def matmul_ln(x: jax.Array, w: jax.Array, b: jax.Array, gamma: jax.Array,
              beta: jax.Array, *, block_m: int = 256, block_k: int = 512,
              eps: float = 1e-6,
              interpret: Optional[bool] = None) -> jax.Array:
    interp = (not _on_tpu()) if interpret is None else interpret
    lead = x.shape[:-1]
    K = x.shape[-1]
    xf = x.reshape(-1, K)
    M = xf.shape[0]
    bm = min(block_m, M)
    xp = _pad_to(xf, 0, bm)
    bk = min(block_k, K)
    xp = _pad_to(xp, 1, bk)
    wp = _pad_to(w, 0, bk)
    out = _mln.matmul_ln(xp, wp, b, gamma, beta, block_m=bm, block_k=bk,
                         eps=eps, interpret=interp, valid_k=K)
    return out[:M].reshape(*lead, w.shape[1])


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None, block_q: int = 512,
                    block_k: int = 512,
                    interpret: Optional[bool] = None) -> jax.Array:
    interp = (not _on_tpu()) if interpret is None else interpret
    Sq, Sk = q.shape[2], k.shape[2]
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    qp = _pad_to(q, 2, bq)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    out = _fa.flash_attention(qp, kp, vp, causal=causal, window=window,
                              scale=scale, block_q=bq, block_k=bk,
                              interpret=interp, kv_len=Sk)
    return out[:, :, :Sq]


def depthwise_conv2d(x: jax.Array, w: jax.Array, b: jax.Array, *,
                     block_c: int = 128,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Channels ride the lane axis, so the channel block is the whole C
    or a multiple of the 128-wide lane; C is zero-padded to a block
    multiple (padded channels are independent and sliced off)."""
    interp = (not _on_tpu()) if interpret is None else interpret
    C = x.shape[-1]
    bc = max(_LANE, block_c // _LANE * _LANE)
    if bc >= C:
        bc = C
    out = _dw.depthwise_conv2d(_pad_to(x, 3, bc), _pad_to(w, 2, bc),
                               _pad_to(b, 0, bc), block_c=bc,
                               interpret=interp)
    return out[..., :C]


# the WKV kernel's step, chosen on a v5e at RWKV-6 1.6B's 512-token
# prefill (tools/tune_wkv.py, PERF.md section 6): eight heads of a chunk
# a step, chunks on the grid, sub-chunks of 16.  All 32 heads a step run
# 10% faster but unroll 16 lane groups, which costs ~3 s of tracing and
# Mosaic compiling in set-up.
_WKV_HEADS, _WKV_CHUNKS, _WKV_SUB = 8, 1, 16


def wkv_chunked(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
                u: jax.Array, *, chunk: int = 64,
                interpret: Optional[bool] = None):
    """Chunked WKV over per-head rows: r,k,logw: [BH, T, K]; v: [BH, T,
    V = K]; u: [BH, K], from a zero state.  Returns (out [BH, T, V],
    final state [BH, K, V] f32).  At any T: the kernel pads T and masks
    the ragged tail in-kernel, so the requested chunk is honored
    verbatim (it is the searched schedule parameter, never shrunk)."""
    interp = (not _on_tpu()) if interpret is None else interpret
    BH, T, K = r.shape
    out, state = _wkv.wkv(r, k, v, logw, u[:, None, :],
                          jnp.zeros((BH, 1, K, K), jnp.float32), K=K,
                          chunk=chunk, heads_per_step=1,
                          chunks_per_step=_WKV_CHUNKS, sub=_WKV_SUB,
                          interpret=interp)
    return out, state[:, 0].swapaxes(-1, -2)


def wkv(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
        u: jax.Array, state: jax.Array, *, chunk: int,
        interpret: Optional[bool] = None):
    """Chunked WKV in the model's layout: r, k, v, logw: [B, T, H, K]
    (read as the projections wrote them, [B, T, H*K]); u: [H, K]; state:
    [B, H, K, V = K] f32.  Returns (out [B, T, H, K] in r's dtype, final
    state [B, H, K, K] f32).  The chunk is C verbatim, as above."""
    interp = (not _on_tpu()) if interpret is None else interpret
    B, T, H, K = r.shape
    G = _wkv.group_width(H, K)
    heads = max(_WKV_HEADS, G // K)
    if H % heads or (heads * K) % _LANE:
        heads = H
    flat = [x.reshape(B, T, H * K) for x in (r, k, v)]
    out, groups = _wkv.wkv(
        *flat, logw.reshape(B, T, H * K).astype(jnp.float32),
        jnp.broadcast_to(u.reshape(1, 1, H * K), (B, 1, H * K)).astype(
            jnp.float32),
        _wkv.state_to_groups(state.astype(jnp.float32), G), K=K,
        chunk=chunk, heads_per_step=heads, chunks_per_step=_WKV_CHUNKS,
        sub=_WKV_SUB, interpret=interp)
    return (out.reshape(B, T, H, K),
            _wkv.state_from_groups(groups, K))


# the SSD kernel's step, chosen on a v5e at Granite 4.0-H Micro's
# 8192-token prefill (tools/tune_ssd.py, PERF.md section 6): 16 heads a
# step, sub-chunks of 64.  All 64 heads a step run 6% faster but take
# ~4 s more of Mosaic compiling in set-up; sub-chunks of 16 or 32 and
# the elementwise form (256) are slower at every step.
_SSD_HEADS, _SSD_SUB = 16, 64


def ssd(xbc: jax.Array, dt: jax.Array, A: jax.Array, D: jax.Array,
        state: Optional[jax.Array] = None, *, d_state: int, chunk: int,
        interpret: Optional[bool] = None):
    """Mamba-2's chunked SSD with the D skip, in the model's layout:
    xbc [b, T, H*P + 2N] the conv's output (xs | B | C), read in place;
    dt [b, T, H] after softplus; A (< 0), D: [H]; state [b, H, P, N]
    entering, or None (zeros).  Returns (y [b, T, H*P] f32, final state
    [b, H, P, N] f32).  The chunk is Q verbatim (``ssd_chunk.ssd``)."""
    interp = (not _on_tpu()) if interpret is None else interpret
    H = dt.shape[-1]
    P = (xbc.shape[-1] - 2 * d_state) // H
    heads = _SSD_HEADS
    if H % heads or (heads * P) % _LANE:
        heads = H
    if state is not None:
        state = _ssd.state_to_blocks(state.astype(jnp.float32), heads)
    y, state = _ssd.ssd(xbc, dt, A, D, state, d_state=d_state, chunk=chunk,
                        heads_per_step=heads, sub=_SSD_SUB,
                        interpret=interp)
    return y, _ssd.state_from_blocks(state, P)
