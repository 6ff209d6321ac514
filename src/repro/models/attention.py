"""Blocked (flash) attention in pure JAX + decode attention.

This is the XLA-level realization of the paper's contribution C2 (pixelwise
temporal loop ordering): softmax statistics are computed *while* the producer
matmul streams block-by-block, so the [Sq, Sk] score intermediate never
materializes in HBM — the online-softmax state (m, l, acc) is the TPU
analogue of the paper's writeback line buffer.

The online-softmax loop opens the scopes ``qk``, ``sm`` and ``av``
around the scores, the softmax statistics and the value product, so a
model's layer scopes (``repro.obs.layers``) can split its attention.

Three entry points:

- ``flash_attention``        : fwd+bwd (custom_vjp), causal/window masks, full scan
- ``flash_attention_banded`` : fwd-only banded variant for sliding-window prefill
                               (O(S*W) FLOPs instead of O(S^2))
- ``decode_attention``       : single-token GQA decode against a (possibly
                               sequence-sharded) KV cache, ring-buffer aware

All functions take q:[B,H,Sq,D], k/v:[B,H,Sk,D] with H already expanded to the
full query-head count (GQA repeat happens in the caller; jnp.repeat's VJP sums
KV-head gradients over the group automatically).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _pick_block(s: int, preferred: int) -> int:
    b = min(s, preferred)
    while s % b != 0:
        b //= 2
    return max(b, 1)


def _block_mask(q_start, k_start, bq: int, bk: int, causal: bool,
                window: Optional[int]) -> jax.Array:
    """[bq, bk] boolean mask for a (q_block, k_block) tile."""
    q_pos = q_start + lax.iota(jnp.int32, bq)[:, None]
    k_pos = k_start + lax.iota(jnp.int32, bk)[None, :]
    mask = jnp.ones((bq, bk), dtype=bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    return mask


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _flash_fwd(q, k, v, causal: bool, window: Optional[int], scale: float,
               block_q: int, block_k: int):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    nq, nk = Sq // bq, Sk // bk

    qr = q.reshape(B, H, nq, bq, D)
    kr = k.reshape(B, H, nk, bk, D)
    vr = v.reshape(B, H, nk, bk, D)

    def q_block_step(_, i):
        qi = qr[:, :, i].astype(jnp.float32) * scale      # [B,H,bq,D]

        def kv_step(carry, j):
            m, l, acc = carry
            with jax.named_scope("qk"):
                kj = kr[:, :, j].astype(jnp.float32)       # [B,H,bk,D]
                s = jnp.einsum("bhqd,bhkd->bhqk", qi, kj)  # [B,H,bq,bk]
                mask = _block_mask(i * bq, j * bk, bq, bk, causal, window)
                s = jnp.where(mask[None, None], s, NEG_INF)
            with jax.named_scope("sm"):
                m_new = jnp.maximum(m, s.max(axis=-1))
                p = jnp.exp(s - m_new[..., None])
                alpha = jnp.exp(m - m_new)
                l_new = l * alpha + p.sum(axis=-1)
            with jax.named_scope("av"):
                vj = vr[:, :, j].astype(jnp.float32)
                acc_new = acc * alpha[..., None] + jnp.einsum(
                    "bhqk,bhkd->bhqd", p, vj)
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((B, H, bq), NEG_INF, jnp.float32),
            jnp.zeros((B, H, bq), jnp.float32),
            jnp.zeros((B, H, bq, D), jnp.float32),
        )
        (m, l, acc), _ = lax.scan(kv_step, init, jnp.arange(nk))
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out_i = acc / l_safe[..., None]
        lse_i = m + jnp.log(l_safe)
        return None, (out_i, lse_i)

    _, (out_blocks, lse_blocks) = lax.scan(q_block_step, None, jnp.arange(nq))
    # out_blocks: [nq, B, H, bq, D] -> [B, H, Sq, D]
    out = out_blocks.transpose(1, 2, 0, 3, 4).reshape(B, H, Sq, D)
    lse = lse_blocks.transpose(1, 2, 0, 3).reshape(B, H, Sq)
    return out.astype(q.dtype), lse


# ---------------------------------------------------------------------------
# Backward (recomputes scores block-by-block; nothing O(S^2) is stored)
# ---------------------------------------------------------------------------


def _flash_bwd(q, k, v, out, lse, dout, causal, window, scale,
               block_q, block_k):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    nq, nk = Sq // bq, Sk // bk

    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    dof = dout.astype(jnp.float32)
    delta = (dof * out.astype(jnp.float32)).sum(-1)        # [B,H,Sq]

    qr = qf.reshape(B, H, nq, bq, D)
    kr = kf.reshape(B, H, nk, bk, D)
    vr = vf.reshape(B, H, nk, bk, D)
    dor = dof.reshape(B, H, nq, bq, D)
    lser = lse.reshape(B, H, nq, bq)
    deltar = delta.reshape(B, H, nq, bq)

    def p_and_ds(i, j):
        """Recompute p_ij and dS_ij for a tile pair."""
        qi = qr[:, :, i] * scale
        kj = kr[:, :, j]
        s = jnp.einsum("bhqd,bhkd->bhqk", qi, kj)
        mask = _block_mask(i * bq, j * bk, bq, bk, causal, window)
        s = jnp.where(mask[None, None], s, NEG_INF)
        p = jnp.exp(s - lser[:, :, i][..., None])           # [B,H,bq,bk]
        dp = jnp.einsum("bhqd,bhkd->bhqk", dor[:, :, i], vr[:, :, j])
        ds = p * (dp - deltar[:, :, i][..., None])
        return p, ds

    # dq: loop q blocks outer, k blocks inner
    def dq_step(_, i):
        def inner(acc, j):
            _, ds = p_and_ds(i, j)
            return acc + jnp.einsum("bhqk,bhkd->bhqd", ds, kr[:, :, j]), None
        dq_i, _ = lax.scan(inner, jnp.zeros((B, H, bq, D), jnp.float32),
                           jnp.arange(nk))
        return None, dq_i * scale

    _, dq_blocks = lax.scan(dq_step, None, jnp.arange(nq))
    dq = dq_blocks.transpose(1, 2, 0, 3, 4).reshape(B, H, Sq, D)

    # dk/dv: loop k blocks outer, q blocks inner
    def dkv_step(_, j):
        def inner(carry, i):
            dk_j, dv_j = carry
            p, ds = p_and_ds(i, j)
            dv_j = dv_j + jnp.einsum("bhqk,bhqd->bhkd", p, dor[:, :, i])
            dk_j = dk_j + jnp.einsum("bhqk,bhqd->bhkd", ds, qr[:, :, i])
            return (dk_j, dv_j), None
        init = (jnp.zeros((B, H, bk, D), jnp.float32),
                jnp.zeros((B, H, bk, D), jnp.float32))
        (dk_j, dv_j), _ = lax.scan(inner, init, jnp.arange(nq))
        return None, (dk_j * scale, dv_j)

    _, (dk_blocks, dv_blocks) = lax.scan(dkv_step, None, jnp.arange(nk))
    dk = dk_blocks.transpose(1, 2, 0, 3, 4).reshape(B, H, Sk, D)
    dv = dv_blocks.transpose(1, 2, 0, 3, 4).reshape(B, H, Sk, D)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512):
    """Fused-softmax attention.  q,k,v: [B, H, S, D] (H = full query heads)."""
    scale_ = scale if scale is not None else q.shape[-1] ** -0.5
    out, _ = _flash_fwd(q, k, v, causal, window, scale_, block_q, block_k)
    return out


def _fa_fwd(q, k, v, causal, window, scale, block_q, block_k):
    scale_ = scale if scale is not None else q.shape[-1] ** -0.5
    out, lse = _flash_fwd(q, k, v, causal, window, scale_, block_q, block_k)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, window, scale, block_q, block_k, res, dout):
    q, k, v, out, lse = res
    scale_ = scale if scale is not None else q.shape[-1] ** -0.5
    return _flash_bwd(q, k, v, out, lse, dout, causal, window, scale_,
                      block_q, block_k)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# Banded sliding-window forward (prefill): O(S*W) instead of O(S^2)
# ---------------------------------------------------------------------------


def flash_attention_banded(q, k, v, window: int,
                           scale: Optional[float] = None,
                           block_q: int = 512, block_k: int = 512):
    """Causal sliding-window attention touching only the KV band per q block.

    For each q block starting at position qs, the reachable kv positions are
    [qs - window + 1, qs + bq), a band of static width; we dynamic-slice that
    band (clamped at 0) and mask.  FLOPs scale with S*window.
    """
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    scale_ = scale if scale is not None else D ** -0.5
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    # band width rounded up to block_k multiple, plus one block of slack for
    # clamping alignment
    band = ((window + bq + bk - 1) // bk + 1) * bk
    band = min(band, Sk)
    nq = Sq // bq

    qr = q.reshape(B, H, nq, bq, D)

    def q_step(_, i):
        qs = i * bq
        qi = qr[:, :, i].astype(jnp.float32) * scale_
        # band start (aligned down to bk, clamped to valid range)
        start = jnp.maximum(qs - window + 1, 0)
        start = (start // bk) * bk
        start = jnp.minimum(start, Sk - band)
        kb = lax.dynamic_slice_in_dim(k, start, band, axis=2).astype(jnp.float32)
        vb = lax.dynamic_slice_in_dim(v, start, band, axis=2).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qi, kb)          # [B,H,bq,band]
        q_pos = qs + lax.iota(jnp.int32, bq)[:, None]
        k_pos = start + lax.iota(jnp.int32, band)[None, :]
        mask = (q_pos >= k_pos) & ((q_pos - k_pos) < window)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m = s.max(axis=-1)
        p = jnp.exp(s - m[..., None])
        l = p.sum(axis=-1)
        out_i = jnp.einsum("bhqk,bhkd->bhqd", p, vb) / l[..., None]
        return None, out_i

    _, out_blocks = lax.scan(q_step, None, jnp.arange(nq))
    out = out_blocks.transpose(1, 2, 0, 3, 4).reshape(B, H, Sq, D)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention (single new token, KV cache)
# ---------------------------------------------------------------------------


def decode_attention(q, k_cache, v_cache, cur_index,
                     scale: Optional[float] = None,
                     ring: bool = False) -> jax.Array:
    """GQA decode: q [B,Hq,1,D] against cache [B,Hkv,S,D].

    ``cur_index`` is the number of valid cache positions (scalar int32).
    If ``ring`` the cache is a ring buffer (all positions valid once full;
    before that, positions >= cur_index are invalid).

    The S dim of the cache may be sharded over the ``model`` mesh axis; the
    softmax + output reductions then partition into per-shard partials with
    XLA-inserted collectives (flash-decoding-style split-S).
    """
    B, Hq, _, D = q.shape
    Hkv = k_cache.shape[1]
    S = k_cache.shape[2]
    G = Hq // Hkv
    scale_ = scale if scale is not None else D ** -0.5

    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32) * scale_
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)

    logits = jnp.einsum("bhgd,bhsd->bhgs", qg, kf)          # [B,Hkv,G,S]
    # caller passes cur_index = min(step + 1, S); for ring buffers every slot
    # is valid once the ring has wrapped, which that clamp already encodes.
    pos = lax.iota(jnp.int32, S)
    mask = pos[None, None, None, :] < cur_index
    logits = jnp.where(mask, logits, NEG_INF)
    m = logits.max(axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhgs,bhsd->bhgd", p / l, vf)
    return out.reshape(B, Hq, 1, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Naive reference (oracle for tests)
# ---------------------------------------------------------------------------


def reference_attention(q, k, v, causal: bool = True,
                        window: Optional[int] = None,
                        scale: Optional[float] = None) -> jax.Array:
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    scale_ = scale if scale is not None else D ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale_
    mask = _block_mask(0, 0, Sq, Sk, causal, window)
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
