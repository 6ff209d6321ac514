"""C2 — matmul + LayerNorm epilogue Pallas kernel (paper §III on TPU).

``y = LayerNorm(x @ w + b)`` with the normalization statistics computed
in VMEM before the result ever reaches HBM — the TPU analogue of the
paper's pixelwise temporal loop ordering + writeback line buffer: a row
block ("pixels") accumulates across the K grid axis in a VMEM scratch
accumulator; on the last K tile the per-row mean/variance are computed
and applied in-register, then the normalized block is written out once.
The baseline (unfused) path costs an extra HBM round trip of the full
[M, N] tensor.

Grid: (m_tiles, k_tiles), K innermost so the accumulator stays resident.
BlockSpecs:
  x   : (bm, bk)  at (i, k)
  w   : (bk, N)   at (k, 0)
  b   : (N,)      at (0,)      — bias (broadcast over rows)
  g,o : (N,)      at (0,)      — LN scale / offset
  out : (bm, N)   at (i, 0)

Ragged edges: block_k need not divide the true reduction extent.  The
``ops`` wrapper pads x / w to block multiples and passes the true K via
``valid_k``; the kernel zero-masks the padded reduction columns of the
x block (in-kernel edge predication) so the ragged final k block adds
nothing to the accumulator — and hence nothing to the LN statistics.
Padded M rows are row-independent and sliced off by the caller.
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


def _matmul_ln_kernel(x_ref, w_ref, b_ref, g_ref, o_ref, out_ref, acc_ref,
                      *, n_k: int, bk: int, valid_k: int, eps: float):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if valid_k % bk:        # ragged final reduction block: zero-mask the
        #                     padded columns (static no-op when perfect)
        k_idx = k * bk + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(k_idx < valid_k, x, 0)
    acc_ref[...] += jnp.dot(x, w_ref[...],
                            precision=HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        # the "writeback line buffer": full rows are resident, so channel
        # statistics are computed before anything is written back
        y = acc_ref[...] + b_ref[...].astype(jnp.float32)
        mean = jnp.mean(y, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(y - mean), axis=-1, keepdims=True)
        yn = (y - mean) * jax.lax.rsqrt(var + eps)
        yn = yn * g_ref[...].astype(jnp.float32) \
            + o_ref[...].astype(jnp.float32)
        out_ref[...] = yn.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_k",
                                             "interpret", "eps",
                                             "valid_k"))
def matmul_ln(x: jax.Array, w: jax.Array, b: jax.Array, gamma: jax.Array,
              beta: jax.Array, *, block_m: int = 256, block_k: int = 512,
              eps: float = 1e-6, interpret: bool = False,
              valid_k: Optional[int] = None) -> jax.Array:
    """x: [M, K]; w: [K, N]; b/gamma/beta: [N] -> LN(x @ w + b) [M, N].

    M must divide by block_m and K by block_k — ``ops.matmul_ln`` pads
    ragged operands and passes the true reduction extent via
    ``valid_k`` so the kernel masks the padded columns.
    """
    M, K = x.shape
    N = w.shape[1]
    bm = min(block_m, M)
    bk = min(block_k, K)
    assert M % bm == 0 and K % bk == 0, (M, K, bm, bk)
    n_m, n_k = M // bm, K // bk
    vk = K if valid_k is None else valid_k
    assert K - bk < vk <= K, (K, bk, vk)

    return pl.pallas_call(
        functools.partial(_matmul_ln_kernel, n_k=n_k, bk=bk, valid_k=vk,
                          eps=eps),
        grid=(n_m, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, k: (i, k)),
            pl.BlockSpec((bk, N), lambda i, k: (k, 0)),
            pl.BlockSpec((N,), lambda i, k: (0,)),
            pl.BlockSpec((N,), lambda i, k: (0,)),
            pl.BlockSpec((N,), lambda i, k: (0,)),
        ],
        out_specs=pl.BlockSpec((bm, N), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, N), jnp.float32)],
        interpret=interpret,
    )(x, w, b, gamma, beta)
