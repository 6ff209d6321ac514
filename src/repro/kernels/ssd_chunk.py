"""Beyond-paper — chunked Mamba-2 SSD Pallas kernel (mamba_hybrid arch).

The paper's C3 insight (produce a tile into local memory, consume it
immediately, discard it) applied to Mamba-2's scalar-decay SSD: each
head's [P, N] state stays in VMEM across every chunk of the sequence,
and within a chunk of Q tokens the recurrence is a few MXU matmuls.  No
[H, Q, Q] decay or score tensor ever reaches HBM.

Operands are read as the causal conv wrote them: ``xs``, B and C are
column blocks of ``xbc`` [b, T, H*P + 2N] (a block of ``hb`` heads of
xs is hb*P lanes, a multiple of 128), so no slice, reshape or transpose
precedes the kernel; ``dt`` [b, T, H] (after softplus, f32) and A come
in whole.  y is written in f32 as [b, T, H*P], the layout the gated
norm reads, with the ``D * xs`` skip added.

Grid: (b, T / Q, H / hb), chunks outside head blocks, both sequential.
At a chunk's first head block the running sums of dt*A over the chunk
are taken for every head, exact in f32 (a 0/1 triangle on the MXU, the
values in three bf16 parts), in two forms kept in VMEM scratch: by
column [Q, H] and, per sub-chunk, by row [H, g*c] (repeated for the g
heads of a 128-lane group).  A head's value is spread over its P lanes
on the VPU (a masked lane sum, then a select).
With a_t the running sum at token t (non-increasing), the chunk runs by
sub-chunks of c tokens, the state carried from one to the next as it is
across chunks.  With S the state after token p = jc - 1 (the state
entering the chunk for j = 0, a_p = 0) and e the sub-chunk's last token,

  y_t  = exp(a_t - a_p) C_t S^T  +  sum_{p<s<=t} (C_t . B_s) exp(a_t - a_s) dt_s x_s
         +  D x_t
  S'   = exp(a_e - a_p) S  +  sum_{p<s<=e} exp(a_e - a_s) dt_s x_s (x) B_s.

Every exponent is <= 0, so a factor underflows only where the true
decay does, and no positive number is exponentiated.  The state's
terms serve every head of the block in one matmul each, a head's
factors scaling its lanes.  Only the c x c diagonal block builds a
head's decay elementwise (clipped at 0, masked causal): a group's g
heads side by side as one [c, g*c] score (``C B^T`` repeated), against a
[g*c, g*P] operand that holds each head's rows on its own lanes, so one
matmul serves the group.  ``sub`` = Q is the plain elementwise form.

Precision: decays, running sums, the state and y are f32; MXU operands
are in the inputs' dtype (bf16: one pass, f32 accumulation; f32: full
f32, ``HIGHEST``), as XLA's ``ssd_chunked`` casts its operands to the
compute dtype.

BlockSpecs (Q tokens a step, L = hb*P lanes):
  xs, y            : (-, Q, L)       at (b, t, h)
  B, C             : (-, Q, N)       at (b, t, their column block)
  dt               : (-, Q, H)       at (b, t, 0)
  A                : (1, H);  D (per lane): (1, L) at (0, h)
  state in / out   : (-, H/hb, N, L) at (b, 0, 0, 0), resident
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
_NN = (((1,), (0,)), ((), ()))        # a @ b
_NT = (((1,), (1,)), ((), ()))        # a @ b.T
_TN = (((0,), (0,)), ((), ()))        # a.T @ b


def _mm(a, b, dims, dtype):
    """A contraction with operands in ``dtype`` and f32 accumulation:
    f32 operands in full f32."""
    prec = lax.Precision.HIGHEST if dtype == jnp.float32 else None
    return lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                           precision=prec, preferred_element_type=jnp.float32)


def _exact(ones, vals, dims, exact: bool, vals_first: bool = False):
    """A contraction of f32 ``vals`` with a 0/1 matrix ``ones``, exact
    in f32: in full f32 where ``exact``, else with vals in three bf16
    parts (the 0/1 matrix is exact in bf16)."""
    def mm(v, dtype):
        return _mm(*((v, ones) if vals_first else (ones, v)), dims, dtype)
    if exact:
        return mm(vals, jnp.float32)
    out = 0.0
    for _ in range(3):
        part = vals.astype(jnp.bfloat16)
        out = out + mm(part, jnp.bfloat16)
        vals = vals - part.astype(jnp.float32)
    return out


def _e(v):
    """exp of a log-decay, clipped at 0 against rounding."""
    return jnp.exp(jnp.minimum(v, 0.0))


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, *refs, P: int,
                G: int, sub: int, entering: bool):
    if entering:
        s0_ref, y_ref, s_ref, cum_ref, row_ref = refs
    else:
        y_ref, s_ref, cum_ref, row_ref = refs
    t, blk = pl.program_id(1), pl.program_id(2)
    Q, L = x_ref.shape
    H = dt_ref.shape[1]
    hb, g, c = L // P, G // P, sub
    cdt = x_ref.dtype
    exact = cdt == jnp.float32
    f32 = jnp.float32

    @pl.when(t == 0)
    def _init():
        s_ref[blk] = (s0_ref[blk] if entering
                      else jnp.zeros(s_ref.shape[1:], f32))

    @pl.when(blk == 0)
    def _sums():
        # the running sums of dt*A over the chunk, every head: by column
        # (token, head) and, per sub-chunk, by row (head, token)
        w = dt_ref[...] * a_ref[...]                       # [Q, H] <= 0
        ti = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        si = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        cum_ref[...] = _exact((si <= ti).astype(f32), w, _NN, exact)
        for j in range(Q // c):
            u = (lax.broadcasted_iota(jnp.int32, (Q, g * c), 0)
                 <= lax.broadcasted_iota(jnp.int32, (Q, g * c), 1) % c
                 + j * c)
            row_ref[j] = _exact(u.astype(f32), w, _TN, exact,
                                vals_first=True)

    lane_h = lax.broadcasted_iota(jnp.int32, (1, H), 1)
    sub_h = lax.broadcasted_iota(jnp.int32, (H, 1), 0)
    lane_g = lax.broadcasted_iota(jnp.int32, (1, G), 1) // P   # head in group
    heads = [blk * hb + k for k in range(hb)]

    def column(v, head):
        """[rows, H] -> the head's column [rows, 1]."""
        return jnp.sum(jnp.where(lane_h == head, v, 0.0), axis=1,
                       keepdims=True)

    def spread(cols):
        """Per-head columns [Q, 1] -> [Q, L], each over its P lanes."""
        groups = []
        for grp in range(L // G):
            out = cols[grp * g]
            for k in range(1, g):
                out = jnp.where(lane_g == k, cols[grp * g + k], out)
            groups.append(jnp.broadcast_to(out, (Q, G)))
        return groups[0] if len(groups) == 1 else jnp.concatenate(groups, 1)

    cum = cum_ref[...]
    a_cols = [column(cum, hd) for hd in heads]
    a = spread(a_cols)                                     # [Q, L]
    x = x_ref[...].astype(f32)
    xdt = x * spread([column(dt_ref[...], hd) for hd in heads])
    Bm, Cm = b_ref[...], c_ref[...]
    S = s_ref[blk]                                         # [N, L]
    d = d_ref[...]

    # a group's g heads side by side: lanes (k, s) of a [c, g*c] score
    lane_k = lax.broadcasted_iota(jnp.int32, (1, g * c), 1) // c
    causal = (lax.broadcasted_iota(jnp.int32, (c, g * c), 1) % c
              <= lax.broadcasted_iota(jnp.int32, (c, g * c), 0))
    for j in range(Q // c):
        lo, hi = j * c, (j + 1) * c
        Cj, Bj = Cm[lo:hi], Bm[lo:hi]
        # S: the state after token lo - 1, whose running sum is ref
        ref = a[lo - 1:lo] if j else 0.0
        aj, end = a[lo:hi], a[hi - 1:hi]
        yj = _e(aj - ref) * _mm(Cj, S, _NN, cdt) + d * x[lo:hi]
        S = _e(end - ref) * S + _mm(Bj, _e(end - aj) * xdt[lo:hi], _TN, cdt)
        # the diagonal block, a head's decay elementwise: [t, (k, s)]
        cb = _mm(Cj, jnp.concatenate([Bj] * g, 0) if g > 1 else Bj, _NT,
                 cdt)                                      # [c, g*c]
        rows = row_ref[j]                                  # [H, g*c]
        for grp in range(L // G):
            lanes = slice(grp * G, (grp + 1) * G)
            a_s = jnp.sum(jnp.where(sub_h == heads[grp * g] + lane_k, rows,
                                    0.0), axis=0, keepdims=True)
            a_t = a_cols[grp * g][lo:hi]
            for k in range(1, g):
                a_t = jnp.where(lane_k == k, a_cols[grp * g + k][lo:hi], a_t)
            dec = jnp.where(causal, _e(a_t - a_s), 0.0)
            xg = xdt[lo:hi, lanes]
            xb = jnp.concatenate([jnp.where(lane_g == k, xg, 0.0)
                                  for k in range(g)], 0) if g > 1 else xg
            y_ref[lo:hi, lanes] = yj[:, lanes] + _mm(cb * dec, xb, _NN, cdt)
    s_ref[blk] = S


@functools.partial(jax.jit, static_argnames=(
    "d_state", "chunk", "heads_per_step", "sub", "interpret"))
def ssd(xbc: jax.Array, dt: jax.Array, A: jax.Array, D: jax.Array,
        state, *, d_state: int, chunk: int, heads_per_step: int, sub: int,
        interpret: bool = False):
    """xbc: [b, T, H*P + 2N], xs | B | C (xs's and B's dtype sets the
    MXU's, above); dt: [b, T, H] f32 after softplus; A (< 0), D: [H] f32;
    state: [b, H/hb, N, hb*P] f32 entering (``state_to_blocks``), or None
    for zeros.

    Returns (y [b, T, H*P] f32 with the D skip, final state as
    ``state``).  Q = min(chunk, T rounded up to 16) tokens a chunk; a
    ragged tail is padded with dt = 0 and xs = B = C = 0, which decays
    nothing and adds nothing.  ``heads_per_step`` heads make one grid
    step; ``sub`` is the sub-chunk of the intra-chunk sum (the whole
    chunk where it does not divide it).  Where H*P or N is not a
    multiple of 128 the column blocks cannot be read in place, and xs, B
    and C are sliced apart first."""
    b, T, W = xbc.shape
    H, N, hb = dt.shape[-1], d_state, heads_per_step
    di = W - 2 * N
    P, L = di // H, heads_per_step * di // H
    assert H % hb == 0 and (L % _LANE == 0 or hb == H), (H, P, hb)
    Q = min(chunk, -(-T // 16) * 16)
    Tp = -(-T // Q) * Q
    if Tp != T:
        xbc = jnp.pad(xbc, ((0, 0), (0, Tp - T), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, Tp - T), (0, 0)))
    c = sub if Q % sub == 0 else Q
    g = max(1, min(hb, _LANE // P))
    while hb % g:
        g -= 1

    if L % _LANE == 0 and N % _LANE == 0 and di % N == 0:
        ins, cols = (xbc, xbc, xbc), (di // N, di // N + 1)
    else:
        ins, cols = (xbc[..., :di], xbc[..., di:di + N],
                     xbc[..., di + N:]), (0, 0)
    seq = pl.BlockSpec((None, Q, L), lambda i, t, h: (i, t, h))
    bc = [pl.BlockSpec((None, Q, N), lambda i, t, h, col=col: (i, t, col))
          for col in cols]
    blocks = pl.BlockSpec((None, H // hb, N, L), lambda i, t, h: (i, 0, 0, 0))
    in_specs = [seq, *bc,
                pl.BlockSpec((None, Q, H), lambda i, t, h: (i, t, 0)),
                pl.BlockSpec((1, H), lambda i, t, h: (0, 0)),
                pl.BlockSpec((1, L), lambda i, t, h: (0, h))]
    args = [*ins, dt.astype(jnp.float32), A.reshape(1, H).astype(jnp.float32),
            jnp.repeat(D.astype(jnp.float32), P).reshape(1, H * P)]
    if state is not None:
        in_specs.append(blocks)
        args.append(state.astype(jnp.float32))
    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, P=P, G=g * P, sub=c,
                          entering=state is not None),
        grid=(b, Tp // Q, H // hb),
        in_specs=in_specs,
        out_specs=[seq, blocks],
        out_shape=[jax.ShapeDtypeStruct((b, Tp, H * P), jnp.float32),
                   jax.ShapeDtypeStruct((b, H // hb, N, L), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((Q, H), jnp.float32),
                        pltpu.VMEM((Q // c, H, g * c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="ssd_chunk",
    )(*args)
    return y[:, :T], state


def state_to_blocks(state: jax.Array, hb: int) -> jax.Array:
    """[b, H, P, N] per-head states -> [b, H/hb, N, hb*P]: a block's
    heads side by side on the lanes, transposed (``[n, (head, p)]``)."""
    b, H, P, N = state.shape
    return state.reshape(b, H // hb, hb, P, N).transpose(
        0, 1, 4, 2, 3).reshape(b, H // hb, N, hb * P)


def state_from_blocks(blocks: jax.Array, P: int) -> jax.Array:
    """The inverse of ``state_to_blocks``: [b, H, P, N]."""
    b, U, N, L = blocks.shape
    return blocks.reshape(b, U, N, L // P, P).transpose(
        0, 1, 3, 4, 2).reshape(b, U * (L // P), P, N)
