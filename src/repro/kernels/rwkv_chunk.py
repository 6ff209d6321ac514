"""Beyond-paper — chunked WKV6 state-update Pallas kernel (rwkv6 arch).

The paper's C3 insight (produce a tile into local memory, consume it
immediately, discard it) transfers to the RWKV-6 recurrence: within a
chunk of T tokens the recurrence becomes three MXU matmuls plus a [C, C]
intra-chunk score matrix; the [C, C, K] decay tensor and the [K, V]
running state live only in VMEM and never round-trip HBM per token.

Grid: (B*H, n_chunks), chunks innermost; the [K, V] state scratch carries
across chunk steps (TPU grids execute sequentially).  Decay exponents are
``exp(b_t - b_s)`` with t >= s and b a running cumsum of log-decays
(<= 0), so every exponent is <= 0 — numerically safe.

BlockSpecs:
  r,k,w : (1, C, K) at (bh, c, 0)
  v     : (1, C, V) at (bh, c, 0)
  u     : (1, 1, K) at (bh, 0, 0)  — per-head bonus, caller-expanded; the
                                   unit axis keeps the block's last two
                                   dims equal to the array's
  out   : (1, C, V) at (bh, c, 0)
  state : (1, K, V) at (bh, 0, 0)  — final state output
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# f32 operands contract in full f32: Mosaic's default precision rounds
# them to bf16 (max|d| ~1e-2 against the f32 oracle on a TPU v5e)
HIGHEST = jax.lax.Precision.HIGHEST


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_out_ref,
                state_ref, *, n_chunks: int, C: int, valid_t: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    rc = r_ref[0].astype(jnp.float32)              # [C, K]
    kc = k_ref[0].astype(jnp.float32)
    vc = v_ref[0].astype(jnp.float32)              # [C, V]
    wc = w_ref[0].astype(jnp.float32)              # [C, K] log-decay <= 0
    u = u_ref[0].astype(jnp.float32)               # [1, K]

    if valid_t % C:
        # ragged T: zero the padded tail of the final chunk so it is
        # recurrence-neutral (logw=0 -> decay 1, k=0 -> no state/score
        # contribution, r=0 -> dead output rows).  Static short-circuit:
        # dividing extents compile exactly as before.
        tok = c * C + jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
        live = tok < valid_t
        rc = jnp.where(live, rc, 0.0)
        kc = jnp.where(live, kc, 0.0)
        wc = jnp.where(live, wc, 0.0)

    # running log-decay as a lower-triangular matmul (the TPU lowering
    # has no cumsum)
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    b = jnp.dot(jnp.where(row >= col, 1.0, 0.0), wc,
                precision=HIGHEST,
                preferred_element_type=jnp.float32)       # [C, K]
    b_prev = b - wc
    S = state_ref[...]

    # inter-chunk: r_t decayed to the chunk start, applied to carried state
    inter = jnp.dot(rc * jnp.exp(b_prev), S,
                    precision=HIGHEST,
                    preferred_element_type=jnp.float32)        # [C, V]

    # intra-chunk scores A[t,s] = sum_k r_t k_s exp(b_{t-1} - b_s), s < t
    expo = jnp.exp(jnp.clip(b_prev[:, None, :] - b[None, :, :],
                            max=0.0))              # [C, C, K]
    A = jnp.sum(rc[:, None, :] * kc[None, :, :] * expo, axis=-1)
    A = jnp.where(row > col, A, 0.0)
    diag = jnp.sum(rc * u * kc, axis=-1)           # [C]
    intra = jnp.dot(A, vc, precision=HIGHEST,
                    preferred_element_type=jnp.float32) \
        + diag[:, None] * vc

    o_ref[0] = (inter + intra).astype(o_ref.dtype)

    # state update: S' = diag(exp(b_C)) S + (k_s exp(b_C - b_s))^T v
    b_end = b[-1:, :]                              # [1, K]
    k_dec = kc * jnp.exp(b_end - b)
    state_ref[...] = jnp.exp(b_end[0])[:, None] * S + jnp.dot(
        k_dec.T, vc, precision=HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(c == n_chunks - 1)
    def _done():
        s_out_ref[0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_chunked(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
                u: jax.Array, *, chunk: int = 64,
                interpret: bool = False):
    """r,k,logw: [BH, T, K]; v: [BH, T, V]; u: [BH, K].

    Returns (out [BH, T, V] in r.dtype, final_state [BH, K, V] f32).
    T need not divide by ``chunk``: the operands are padded to the next
    chunk multiple and the kernel masks the padded tail of the final
    chunk in-kernel (true ``valid_t`` extent), so results are identical
    to the sequential reference at any ragged T.
    """
    BH, T, K = r.shape
    V = v.shape[-1]
    C = min(chunk, T)
    n_chunks = -(-T // C)
    Tp = n_chunks * C
    if Tp != T:
        def _pad(x):
            return jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0)))
        r, k, v, logw = _pad(r), _pad(k), _pad(v), _pad(logw)

    out, state = pl.pallas_call(
        functools.partial(_wkv_kernel, n_chunks=n_chunks, C=C,
                          valid_t=T),
        grid=(BH, n_chunks),
        in_specs=[
            pl.BlockSpec((1, C, K), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, C, K), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, C, V), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, C, K), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, 1, K), lambda bh, c: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, C, V), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, K, V), lambda bh, c: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tp, V), r.dtype),
            jax.ShapeDtypeStruct((BH, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u.reshape(BH, 1, K))
    return out[:, :T], state
