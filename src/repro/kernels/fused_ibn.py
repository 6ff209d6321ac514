"""C3 — fused inverted-bottleneck Pallas kernel (paper §IV on TPU).

Computes  out = act(x @ w1 [* gate]) @ w2  without materializing the
expanded intermediate T = act(x @ w1) in HBM.  The grid tiles T along
(rows x d_ff) — the paper's (X, C) tiling; each (bm, bf) tile of T lives
only in VMEM (the TPU analogue of the accelerator's local buffer), is
immediately contracted into the output accumulator, and is then
discarded.  ``out`` revisits the same block across the d_ff grid axis and
accumulates — the depth-first produce/consume/discard schedule of Fig 4.

Grid: (m_tiles, f_tiles); f is the innermost (fastest) axis so the output
block stays resident while T tiles stream through VMEM.

BlockSpecs (VMEM tiles):
  x   : (bm, D)   at (i, 0)      — row block, full model width
  w1  : (D, bf)   at (0, j)      — expand weights, one f-tile
  wg  : (D, bf)   at (0, j)      — gate weights (gated variants)
  w2  : (bf, D)   at (j, 0)      — project weights, one f-tile
  out : (bm, D)   at (i, 0)      — accumulator (f32 scratch, cast on exit)

Ragged edges: block sizes need not divide the true extents.  The ``ops``
wrapper pads operands to block multiples; ``valid_f`` carries the true
d_ff extent and the kernel zero-masks the padded columns of T before the
contraction (in-kernel edge predication), so the padded final block
contributes nothing regardless of pad contents or activation.  Padded
rows (M axis) are row-independent and simply sliced off by the caller.
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


def _act(name: str, x: jax.Array) -> jax.Array:
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if name == "silu":
        return jax.nn.silu(x)
    if name == "relu2":
        r = jnp.maximum(x, 0.0)
        return r * r
    raise ValueError(name)


def _mask_ragged_f(t: jax.Array, j, bf: int, valid_f: int) -> jax.Array:
    """Zero T columns past the true d_ff extent (static no-op when the
    f blocks tile perfectly)."""
    if valid_f % bf == 0:
        return t
    f_idx = j * bf + jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    return jnp.where(f_idx < valid_f, t, 0.0)


def _ibn_kernel(x_ref, w1_ref, w2_ref, o_ref, acc_ref, *, activation: str,
                n_f: int, bf: int, valid_f: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    # T tile: produced in VMEM, consumed immediately, never written to HBM
    t = jnp.dot(x, w1_ref[...], precision=HIGHEST,
                preferred_element_type=jnp.float32)
    t = _mask_ragged_f(_act(activation, t), j, bf, valid_f)
    acc_ref[...] += jnp.dot(t.astype(x.dtype), w2_ref[...],
                            precision=HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(j == n_f - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _ibn_gated_kernel(x_ref, w1_ref, wg_ref, w2_ref, o_ref, acc_ref, *,
                      activation: str, n_f: int, bf: int, valid_f: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    up = jnp.dot(x, w1_ref[...], precision=HIGHEST,
                 preferred_element_type=jnp.float32)
    gate = jnp.dot(x, wg_ref[...], precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    t = _mask_ragged_f(_act(activation, gate) * up, j, bf, valid_f)
    acc_ref[...] += jnp.dot(t.astype(x.dtype), w2_ref[...],
                            precision=HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(j == n_f - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("activation", "block_m",
                                             "block_f", "interpret",
                                             "valid_f"))
def fused_ibn(x: jax.Array, w1: jax.Array, w2: jax.Array,
              wg: Optional[jax.Array] = None, *, activation: str = "gelu",
              block_m: int = 256, block_f: int = 512,
              interpret: bool = False,
              valid_f: Optional[int] = None) -> jax.Array:
    """x: [M, D]; w1/wg: [D, F]; w2: [F, D] -> [M, D].

    M must divide by block_m and F by block_f — ``ops.fused_ibn`` pads
    ragged operands to block multiples and passes the true d_ff extent
    via ``valid_f`` so the kernel masks the padded columns of T.
    """
    M, D = x.shape
    F = w1.shape[1]
    Do = w2.shape[1]
    bm = min(block_m, M)
    bf = min(block_f, F)
    assert M % bm == 0 and F % bf == 0, (M, F, bm, bf)
    n_m, n_f = M // bm, F // bf
    vf = F if valid_f is None else valid_f
    assert F - bf < vf <= F, (F, bf, vf)

    grid = (n_m, n_f)
    x_spec = pl.BlockSpec((bm, D), lambda i, j: (i, 0))
    w1_spec = pl.BlockSpec((D, bf), lambda i, j: (0, j))
    w2_spec = pl.BlockSpec((bf, Do), lambda i, j: (j, 0))
    o_spec = pl.BlockSpec((bm, Do), lambda i, j: (i, 0))

    if wg is None:
        kernel = functools.partial(_ibn_kernel, activation=activation,
                                   n_f=n_f, bf=bf, valid_f=vf)
        in_specs = [x_spec, w1_spec, w2_spec]
        args = (x, w1, w2)
    else:
        kernel = functools.partial(_ibn_gated_kernel, activation=activation,
                                   n_f=n_f, bf=bf, valid_f=vf)
        in_specs = [x_spec, w1_spec, w1_spec, w2_spec]
        args = (x, w1, wg, w2)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((M, Do), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, Do), jnp.float32)],
        interpret=interpret,
    )(*args)
