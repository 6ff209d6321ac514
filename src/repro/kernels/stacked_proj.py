"""Projection by one layer of a stacked weight, cast in VMEM.

A model that scans over its layers keeps each projection's weights as
one ``[L, K, N]`` stack in the dtype it stores them in (f32), and
computes in a narrower one (bf16).  Sliced and cast by XLA, the weights
of every layer are converted on every call: XLA's TPU pipeline hoists
the cast of the whole stack out of the layer loop, writes a bf16 copy of
it, and then slices that copy layer by layer.  This kernel instead takes
the whole stack and the layer index.  The index arrives by scalar
prefetch, so the weight BlockSpec reads the ``(bk, bn)`` tile of that
layer straight from the stack in HBM; the tile is cast to the compute
dtype in VMEM and contracted on the MXU with f32 accumulation.  The
stack is read once, in its stored dtype, and no copy is written.

Grid: (m_tiles, n_tiles, k_tiles); k is the innermost axis, so the
output block stays resident while the K tiles stream through VMEM.

BlockSpecs (VMEM tiles):
  x   : (bm, bk)        at (i, k)
  w   : (-, bk, bn)     at (layer, k, j)   — the layer dim squeezed
  out : (bm, bn)        at (i, j)          — f32 scratch, cast on exit

Each extent must divide by its block: ``ops.stacked_proj`` picks weight
blocks that divide K and N (or are whole), and pads only x's rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _proj_kernel(layer_ref, x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    del layer_ref                       # read by the weight's index map
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    # one MXU pass on compute-dtype operands, as XLA's own bf16 matmul
    acc_ref[...] += jnp.dot(x, w_ref[...].astype(x.dtype),
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_k",
                                             "block_n", "interpret"))
def stacked_proj(x: jax.Array, w: jax.Array, layer: jax.Array, *,
                 block_m: int, block_k: int, block_n: int,
                 interpret: bool = False) -> jax.Array:
    """x: [M, K] (compute dtype); w: [L, K, N] (stored dtype); layer:
    int32 -> x @ w[layer] as [M, N] in x's dtype.  M, K and N must
    divide by their blocks."""
    M, K = x.shape
    N = w.shape[2]
    assert K == w.shape[1], (x.shape, w.shape)
    assert M % block_m == 0 and K % block_k == 0 and N % block_n == 0, (
        M, K, N, block_m, block_k, block_n)
    n_k = K // block_k
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M // block_m, N // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k, l: (i, k)),
            pl.BlockSpec((None, block_k, block_n),
                         lambda i, j, k, l: (l[0], k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, k, l: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_proj_kernel, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), x, w)
