"""C2 — fused-softmax (flash) attention Pallas kernel.

The online-softmax state (m, l, acc) kept in VMEM scratch across the KV
grid axis is the streaming generalization of the paper's pixelwise
writeback buffer: softmax statistics are computed while the producer
(QK^T) streams block-by-block, so the [Sq, Sk] score matrix never exists
in HBM.  Supports causal and sliding-window masking (GQA is handled by
the caller expanding KV heads).

Grid: (batch*heads, q_tiles, k_tiles) — k innermost; the (m, l, acc)
scratch carries across k tiles and the output block is finalized on the
last one.

BlockSpecs:
  q   : (1, bq, D)  at (h, i, 0)
  k,v : (1, bk, D)  at (h, 0, j)
  out : (1, bq, D)  at (h, i, 0)

Ragged edges: block sizes need not divide the true sequence lengths.
The ``ops`` wrapper pads Q/K/V to block multiples and passes the true
KV length via ``kv_len``; the kernel folds ``k_pos < kv_len`` into the
score mask (in-kernel edge predication) so padded keys get -inf scores
and contribute nothing to the online softmax.  Padded query rows are
row-independent and sliced off by the caller.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# f32 operands contract in full f32: Mosaic's default precision rounds
# them to bf16 (max|d| ~1e-2 against the f32 oracle on a TPU v5e)
HIGHEST = jax.lax.Precision.HIGHEST

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  bq: int, bk: int, n_k: int, kv_len: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale              # [bq, D]
    k = k_ref[0].astype(jnp.float32)                      # [bk, D]
    s = jnp.dot(q, k.T, precision=HIGHEST,
                preferred_element_type=jnp.float32)   # [bq, bk]

    q_pos = i * bq + jax.lax.iota(jnp.int32, bq)[:, None]
    k_pos = j * bk + jax.lax.iota(jnp.int32, bk)[None, :]
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    if kv_len % bk:         # ragged final KV block: padded keys get -inf
        mask &= k_pos < kv_len
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v_ref.dtype), v_ref[0],
        precision=HIGHEST,
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _done():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k",
                                             "interpret", "kv_len"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None, block_q: int = 512,
                    block_k: int = 512, interpret: bool = False,
                    kv_len: Optional[int] = None) -> jax.Array:
    """q,k,v: [B, H, S, D] (H = full query heads) -> [B, H, Sq, D].

    Sq must divide by block_q and Sk by block_k — ``ops.flash_attention``
    pads ragged sequences and passes the true KV length via ``kv_len``
    so padded keys are masked out of the softmax.
    """
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    scale_ = scale if scale is not None else D ** -0.5
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    n_q, n_k = Sq // bq, Sk // bk
    kv = Sk if kv_len is None else kv_len
    assert Sk - bk < kv <= Sk, (Sk, bk, kv)

    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * H, Sk, D)
    vf = v.reshape(B * H, Sk, D)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale_, causal=causal,
                          window=window, bq=bq, bk=bk, n_k=n_k,
                          kv_len=kv),
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom l
            pltpu.VMEM((bq, D), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, D)
