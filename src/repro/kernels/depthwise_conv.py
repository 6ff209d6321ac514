"""C1 — C|FX-dataflow depthwise conv Pallas kernel (paper §II on TPU).

On the edge accelerator, depthwise conv collapses the C|K MAC array to a
single column (each group has K=1); the paper's second dataflow C|FX
spreads channels across one array dim and kernel taps across the other.
The TPU analogue: channels ride the 128-wide LANE dimension of the VPU
(perfectly parallel — the C unroll), while the FX/FY taps are an
unrolled temporal accumulation of shifted input slices (no MXU — a
depthwise conv is a rank-1 degenerate contraction that would waste the
systolic array exactly as OX|C wasted the ASIC's array).

Layout: channels-last [B, H, W, C].  Grid: (B, c_tiles); each step loads
one (H+fy-1, W+fx-1, bc) padded input block and produces (H, W, bc).

BlockSpecs:
  x   : (1, H+fy-1, W+fx-1, bc) at (b, 0, 0, c)   — pre-padded input
  w   : (fy, fx, bc)            at (0, 0, c)
  bias: (1, bc)                 at (0, c)   — 2-D so the lane block tiles
  out : (1, H, W, bc)           at (b, 0, 0, c)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dw_kernel(x_ref, w_ref, b_ref, o_ref, *, fy: int, fx: int, H: int,
               W: int):
    x = x_ref[0]                                   # [H+fy-1, W+fx-1, bc]
    acc = jnp.zeros(o_ref.shape[1:], jnp.float32)  # [H, W, bc]
    for dy in range(fy):                           # FX/FY: temporal taps
        for dx in range(fx):
            tap = x[dy:dy + H, dx:dx + W, :].astype(jnp.float32)
            acc += tap * w_ref[dy, dx, :].astype(jnp.float32)
    o_ref[0] = (acc + b_ref[0].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def depthwise_conv2d(x: jax.Array, w: jax.Array, b: jax.Array, *,
                     block_c: int = 128,
                     interpret: bool = False) -> jax.Array:
    """x: [B, H, W, C]; w: [fy, fx, C]; b: [C] -> [B, H, W, C] (SAME)."""
    B, H, W, C = x.shape
    fy, fx, _ = w.shape
    bc = min(block_c, C)
    assert C % bc == 0, (C, bc)
    py0, py1 = (fy - 1) // 2, fy // 2
    px0, px1 = (fx - 1) // 2, fx // 2
    xp = jnp.pad(x, ((0, 0), (py0, py1), (px0, px1), (0, 0)))

    return pl.pallas_call(
        functools.partial(_dw_kernel, fy=fy, fx=fx, H=H, W=W),
        grid=(B, C // bc),
        in_specs=[
            pl.BlockSpec((1, H + fy - 1, W + fx - 1, bc),
                         lambda bi, ci: (bi, 0, 0, ci)),
            pl.BlockSpec((fy, fx, bc), lambda bi, ci: (0, 0, ci)),
            pl.BlockSpec((1, bc), lambda bi, ci: (0, ci)),
        ],
        out_specs=pl.BlockSpec((1, H, W, bc), lambda bi, ci: (bi, 0, 0, ci)),
        out_shape=jax.ShapeDtypeStruct((B, H, W, C), x.dtype),
        interpret=interpret,
    )(xp, w, b.reshape(1, C))
