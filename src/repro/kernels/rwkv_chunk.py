"""Beyond-paper — chunked WKV6 state-update Pallas kernel (rwkv6 arch).

The paper's C3 insight (produce a tile into local memory, consume it
immediately, discard it) transfers to the RWKV-6 recurrence: the [K, V]
state of each head stays in VMEM across every chunk of the sequence,
and within a chunk of C tokens the recurrence is a few MXU matmuls.

Operands are read in the projections' own layout, ``[B, T, H*K]``: a
block is ``hb`` heads wide (a multiple of 128 lanes, or every head),
so no transpose to a per-head layout is needed.  The lanes are worked
in groups of G = g*K (two heads of 64 make 128 lanes); a group's g
states are held as one [G, G] block-diagonal matrix, transposed
(``S[v, k]``), so a head's decay scales lanes and one matmul serves the
group's heads.

Grid: (B, H / hb, T / (C * cps)), the last axis sequential: a step
loops over ``cps`` chunks, and the state, the resident output block
``s_ref``, carries across steps.  Within a chunk, with b the running
cumsum of log-decays (b_{-1} = 0, decreasing), the output is

  r_t exp(b_{t-1}) S  +  sum_{s<t} (sum_k r_tk k_sk exp(b_{t-1,k} - b_{s,k})) v_s
  +  (sum_k r_tk u_k k_tk) v_t.

The intra-chunk scores run on the MXU by sub-chunks of c tokens: for
sub-chunk j with reference p = jc - 1 and every earlier s,
exp(b_{t-1} - b_s) = exp(b_{t-1} - b_p) * exp(b_p - b_s), both
exponents <= 0, so ``(r_t exp(b_{t-1} - b_p)) . (k_s exp(b_p - b_s))``
is the exact score and underflows only where the true value does.  Only
the c x c diagonal blocks keep the elementwise [c, c, G] form, clipped
at 0, summed over each head's K lanes by a matmul with the group's
same-head mask.

Precision: decays, exponentials, the state and every sum are f32.  MXU
operands keep the inputs' precision: f32 inputs contract in full f32
(``HIGHEST``), bf16 inputs in one bf16 pass with f32 accumulation, as
XLA's chunked form contracts its f32 operands at TPU's default
precision.  The running cumsum is exact either way: the 0/1 triangle
is exact in bf16, and the log-decays enter as three bf16 parts.

BlockSpecs (``cps * C`` = Tc tokens a step, L = hb*K lanes):
  r, k, v, logw, out : (-, Tc, L)   at (b, t, h)
  u                  : (-, 1, L)    at (b, 0, h)
  state in / out     : (-, L/G, G, G) at (b, h, 0, 0)
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


def _mm(a, b, dims, exact: bool):
    """A contraction with f32 accumulation: in full f32 where ``exact``,
    else in one bf16 pass."""
    if exact:
        return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    return lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                           dims, preferred_element_type=jnp.float32)


def _cumsum(tri, w, exact: bool):
    """Running sum of ``w`` [C, G] down its rows, exact in f32: the
    lower triangle ``tri`` is 0/1, and w enters as three bf16 parts."""
    if exact:
        return _mm(tri, w, _NN, True)
    out = 0.0
    for _ in range(3):
        part = w.astype(jnp.bfloat16)
        out = out + _mm(tri, part, _NN, False)
        w = w - part.astype(jnp.float32)
    return out


def _chunk(r, k, v, w, u, st, *, K: int, sub: int, exact: bool):
    """One chunk of one lane group.  r, k, v, w: [C, G] f32; u: [1, G];
    st: [G, G] block-diagonal state, ``st[v, k]``.  Returns the chunk's
    output [C, G] and the state after it."""
    C, G = r.shape
    g = G // K
    lane = lax.broadcasted_iota(jnp.int32, (1, G), 1)
    heads = [(lane >= h * K) & (lane < (h + 1) * K) for h in range(g)]
    gi = lax.broadcasted_iota(jnp.int32, (G, G), 0)
    gj = lax.broadcasted_iota(jnp.int32, (G, G), 1)
    same = (gi // K) == (gj // K)                       # [G, G] one head
    same_f = same.astype(jnp.float32)
    ti = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    tj = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    tri = (ti >= tj).astype(jnp.float32)

    b = _cumsum(tri, w, exact)                          # b_t, [C, G]
    bp = b - w                                          # b_{t-1}
    # carried state, and the current token's bonus u
    out = _mm(r * jnp.exp(bp), st, _NT, exact)
    out = out + _mm(r * u * k, same_f, _NN, exact) * v

    c = sub if C % sub == 0 else C
    si = lax.broadcasted_iota(jnp.int32, (c, c, G), 0)
    sj = lax.broadcasted_iota(jnp.int32, (c, c, G), 1)
    earlier = si < sj                                   # [s, t]: s < t
    pieces = []
    for j in range(C // c):
        lo, hi = j * c, (j + 1) * c
        rj, kj, vj = r[lo:hi], k[lo:hi], v[lo:hi]
        bj, bpj = b[lo:hi], bp[lo:hi]
        oj = out[lo:hi]
        if j:
            # earlier sub-chunks, through the reference p = lo - 1
            ref = b[lo - 1:lo]
            q = rj * jnp.exp(bpj - ref)                 # [c, G]
            kk = k[:lo] * jnp.exp(ref - b[:lo])         # [lo, G]
            qs = jnp.concatenate([jnp.where(m, q, 0.0) for m in heads], 0)
            p = _mm(_mm(qs, kk, _NT, exact), v[:lo], _NN, exact)
            for h, m in enumerate(heads):
                oj = oj + jnp.where(m, p[h * c:(h + 1) * c], 0.0)
        # the diagonal block, elementwise: [s, t, G]
        e = jnp.exp(jnp.minimum(bpj[None, :, :] - bj[:, None, :], 0.0))
        prod = jnp.where(earlier, kj[:, None, :] * rj[None, :, :] * e, 0.0)
        seg = _mm(prod.reshape(c * c, G), same_f, _NN, exact)
        oj = oj + jnp.sum(seg.reshape(c, c, G) * vj[:, None, :], axis=0)
        pieces.append(oj)
    out = jnp.concatenate(pieces, 0) if len(pieces) > 1 else pieces[0]

    b_end = b[C - 1:C]                                  # [1, G]
    k_dec = k * jnp.exp(b_end - b)
    st = st * jnp.exp(b_end) + jnp.where(same, _mm(v, k_dec, _TN, exact),
                                         0.0)
    return out, st


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, s_ref,
                *, K: int, C: int, cps: int, sub: int, valid_t: int,
                exact: bool):
    step = pl.program_id(2)
    G = s_ref.shape[-1]

    @pl.when(step == 0)
    def _init():
        s_ref[...] = s0_ref[...]

    def chunk(i):
        row = i * C if cps == 1 else pl.multiple_of(i * C, C)
        rows = pl.ds(row, C)
        live = None
        if valid_t % (C * cps):
            # ragged T: the padded tail is recurrence-neutral (logw 0
            # keeps the state, k 0 adds nothing, r 0 reads nothing)
            tok = (step * cps + i) * C + lax.broadcasted_iota(
                jnp.int32, (C, 1), 0)
            live = tok < valid_t
        for grp in range(s_ref.shape[0]):
            lanes = pl.ds(grp * G, G)
            ins = [ref[rows, lanes].astype(jnp.float32)
                   for ref in (r_ref, k_ref, v_ref, w_ref)]
            if live is not None:
                ins = [jnp.where(live, x, 0.0) for x in ins]
            rc, kc, vc, wc = ins
            out, st = _chunk(rc, kc, vc, wc,
                             u_ref[:, lanes].astype(jnp.float32),
                             s_ref[grp], K=K, sub=sub, exact=exact)
            o_ref[rows, lanes] = out.astype(o_ref.dtype)
            s_ref[grp] = st

    if cps == 1:
        chunk(0)
    else:
        def body(i, carry):
            chunk(i)
            return carry
        lax.fori_loop(0, cps, body, 0)


@functools.partial(jax.jit, static_argnames=(
    "K", "chunk", "heads_per_step", "chunks_per_step", "sub", "interpret"))
def wkv(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
        u: jax.Array, state: jax.Array, *, K: int, chunk: int,
        heads_per_step: int, chunks_per_step: int, sub: int,
        interpret: bool = False):
    """r, k, v: [B, T, H*K] (the inputs' dtype sets the MXU's, above);
    logw: [B, T, H*K] log-decays <= 0; u: [B, 1, H*K]; state: [B, H/g,
    G, G] f32 block-diagonal (``state_to_groups``).

    Returns (out [B, T, H*K] in r's dtype, final state as ``state``).
    T need not divide by the chunk: the operands are padded to whole
    steps and the kernel masks the padded tail in-kernel.  ``chunk`` is
    C, honoured verbatim; ``heads_per_step`` heads and
    ``chunks_per_step`` chunks make one grid step; ``sub`` is the
    sub-chunk of the intra-chunk scores.
    """
    B, T, D = r.shape
    G = state.shape[-1]
    hb = heads_per_step
    L = hb * K
    assert D % L == 0 and L % G == 0 and (L % _LANE == 0 or L == D), (
        D, L, G)
    C = chunk
    n_chunks = -(-T // C)
    cps = min(chunks_per_step, n_chunks)
    Tc = C * cps
    n_steps = -(-n_chunks // cps)
    Tp = n_steps * Tc
    if Tp != T:
        def _pad(x):
            return jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0)))
        r, k, v, logw = _pad(r), _pad(k), _pad(v), _pad(logw)

    seq = pl.BlockSpec((None, Tc, L), lambda b, h, t: (b, t, h))
    groups = pl.BlockSpec((None, L // G, G, G), lambda b, h, t: (b, h, 0, 0))
    out, state = pl.pallas_call(
        functools.partial(_wkv_kernel, K=K, C=C, cps=cps, sub=sub,
                          valid_t=T, exact=r.dtype == jnp.float32),
        grid=(B, D // L, n_steps),
        in_specs=[seq, seq, seq, seq,
                  pl.BlockSpec((None, 1, L), lambda b, h, t: (b, 0, h)),
                  groups],
        out_specs=[seq, groups],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, D), r.dtype),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="wkv_chunk",
    )(r, k, v, logw, u, state)
    return out[:, :T], state


def group_width(H: int, K: int) -> int:
    """G: the lanes of one group, as many whole heads as fit 128 lanes
    (at least one)."""
    g = max(1, min(H, _LANE // K))
    while H % g:
        g -= 1
    return g * K


def state_to_groups(state: jax.Array, G: int) -> jax.Array:
    """[B, H, K, V] per-head states -> [B, H*K/G, G, G]: each group's
    heads on the diagonal, transposed (``[v, k]``), zeros elsewhere."""
    B, H, K, V = state.shape
    g = G // K
    st = state.reshape(B, H // g, g, K, V).swapaxes(-1, -2)
    eye = jnp.eye(g, dtype=state.dtype)[:, None, :, None]   # [i, v, j, k]
    return (st[..., None, :] * eye).reshape(B, H // g, G, G)


def state_from_groups(groups: jax.Array, K: int) -> jax.Array:
    """The inverse of ``state_to_groups``: [B, H, K, V]."""
    B, U, G, _ = groups.shape
    g = G // K
    st = groups.reshape(B, U, g, K, g, K)
    diag = jnp.stack([st[:, :, i, :, i, :] for i in range(g)], axis=2)
    return diag.swapaxes(-1, -2).reshape(B, U * g, K, K)
