"""Granite 4.0-H: Mamba-2 layers beside full GQA attention (HF
``granitemoehybrid``, dense).

    h = E[tokens] * embedding_multiplier
    for each layer i (``block_pattern[i]``: "mamba" or "attention"):
        h = h + r * Mixer_i(RMSNorm(h))
        h = h + r * MLP(RMSNorm(h))                   r = residual_multiplier
    logits = RMSNorm(h) @ E^T / logits_scaling

    MLP(x)    = (silu(g) * u) W_out,  [g | u] = x W_in
    Attn(x)   = causal GQA softmax(q k^T * attention_multiplier) v, W_o;
                no positional encoding, no bias
    Mamba2(x):
      [z | xBC | dt] = x W_in;  xBC = silu(causal_depthwise_conv(xBC) + b)
      [xs | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
      per head h: S_t = exp(dt_t A_h) S_{t-1} + dt_t xs_t (x) B_t
                  y_t = S_t C_t + D_h xs_t          (S: head_dim x d_state)
      y = RMSNorm(y * silu(z)) (over all heads' channels) W_out

One B/C group (``mamba_n_groups`` 1) serves every head, as in the
released model; the module takes no other.

TPU adaptation: the SSD runs in chunked form.  Within a chunk of Q
tokens the decay is a scalar per head, so the intra-chunk term is
``(C B^T) * exp(segsum(dt A))`` — one [Q, Q] product shared by all heads
and a per-head [Q, Q] decay — applied to ``dt * xs`` on the MXU; the
[H, head_dim, d_state] state is carried across chunks.  On one device
this is the Pallas kernel ``kernels.ssd_chunk`` (``ssd``): it reads xs,
B and C in place from the conv's output, keeps each head's state in
VMEM across the chunks, builds a decay only for each sub-chunk's
diagonal block and only in VMEM, and writes y with the ``D * xs`` skip
in the layout the gated norm reads.  Under an ``actshard`` mesh it is
XLA's ``ssd_chunked`` (a [H, Q, Q] decay, the state carried by a scan).
Every exponent is a difference of running sums of ``dt A <= 0`` taken
later-minus-earlier and clipped at 0, so no positive number is
exponentiated.  Decays, states and sums are f32; matmul operands are in
the compute dtype.  Decode (one token with a state) is ``ssd_step``.

Layout: the weights are stacked per kind, ``mamba`` [n_mamba, ...],
``attn`` [n_attn, ...] and ``mlp`` [num_layers, ...], and the layers run
as a ``lax.scan`` over the periods of ``block_pattern`` (Granite: four
of [5 Mamba-2, attention, 4 Mamba-2]) with the period unrolled in the
body.  On one device every projection reads its layer's tiles straight
from the whole stack (``kernels.ops.stacked_proj``, as ``rwkv6``);
under an ``actshard`` mesh XLA slices the layer out, since the SPMD
partitioner cannot split a Pallas call.

Scopes, spelled as the scheduler's layers
(``repro.core.workload.granite_workload``) without their block index:
``ln1``, ``mamba`` / ``in_proj`` ``conv`` ``ssd`` ``gnorm`` ``out_proj``,
``attn`` / ``qkv`` ``qk`` ``sm`` ``av`` ``proj`` (the blockwise
attention opens the middle three), ``res1``, ``ln2``, ``mlp`` / ``up``
``act`` ``down``, ``res2``; outside the scan ``embed``, ``head.ln`` and
``head.logits``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import actshard
from repro.models import attention as attn_lib
from repro.models import layers as L
from repro.models.params import ParamDef
from repro.models.recurrentgemma import causal_conv1d
from repro.models.rwkv6 import chain_scope  # noqa: F401  (layer_scopes)

Params = Dict[str, Any]

OUTSIDE_CHAIN_SCOPES = ("embed", "head.logits")


class HybridCache(NamedTuple):
    conv: jax.Array    # [n_mamba, B, conv1d_width - 1, d_inner + 2 d_state]
    ssm: jax.Array     # [n_mamba, B, H, head_dim, d_state] f32
    k: jax.Array       # [n_attn, B, kv_heads, S, head_dim]
    v: jax.Array
    step: jax.Array


def _sizes(cfg: ModelConfig):
    """(heads, head_dim, d_state, d_inner, conv channels)."""
    assert cfg.mamba_n_groups == 1, "one B/C group only"
    H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    return H, P, N, H * P, H * P + 2 * N


def _period(cfg: ModelConfig) -> Tuple[str, ...]:
    """The shortest prefix of ``block_pattern`` that repeats to fill it."""
    pat = tuple(cfg.block_pattern)
    for p in range(1, len(pat) + 1):
        if len(pat) % p == 0 and pat[:p] * (len(pat) // p) == pat:
            return pat[:p]
    raise ValueError(pat)


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


def param_defs(cfg: ModelConfig) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    H, _, N, di, conv_c = _sizes(cfg)
    h, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    nm = cfg.block_pattern.count("mamba")
    na = cfg.block_pattern.count("attention")
    nl = cfg.num_layers

    def stack(n, *shape_axes, init="normal"):
        shape = tuple(s for s, _ in shape_axes)
        axes = tuple(a for _, a in shape_axes)
        return ParamDef((n,) + shape, ("layers",) + axes, init)

    def norm(n, width=d, axis="embed"):
        return {"scale": stack(n, (width, axis), init="ones")}

    mamba = {
        "ln": norm(nm),
        "w_zx": stack(nm, (d, "embed"), (2 * di, "ff")),
        "w_bc": stack(nm, (d, "embed"), (2 * N, None)),
        "w_dt": stack(nm, (d, "embed"), (H, None)),
        "conv_w": stack(nm, (cfg.conv1d_width, None), (conv_c, None)),
        "conv_b": stack(nm, (conv_c, None), init="zeros"),
        "dt_bias": stack(nm, (H, None), init="zeros"),
        "A_log": stack(nm, (H, None), init="zeros"),
        "D": stack(nm, (H, None), init="ones"),
        "gnorm": norm(nm, di, "ff"),
        "w_out": stack(nm, (di, "ff"), (d, "embed")),
    }
    attn = {
        "ln": norm(na),
        "wq": stack(na, (d, "embed"), (h * hd, "heads")),
        "wk": stack(na, (d, "embed"), (hk * hd, "kv_heads")),
        "wv": stack(na, (d, "embed"), (hk * hd, "kv_heads")),
        "wo": stack(na, (h * hd, "heads"), (d, "embed")),
    }
    mlp = {
        "ln": norm(nl),
        "w_in": stack(nl, (d, "embed"), (2 * f, "ff")),
        "w_out": stack(nl, (f, "ff"), (d, "embed")),
    }
    return {"embed": L.embedding_defs(cfg), "mamba": mamba, "attn": attn,
            "mlp": mlp, "ln_f": L.norm_defs(cfg)}


# ---------------------------------------------------------------------------
# SSD: chunked (forward / prefill) and one step (decode)
# ---------------------------------------------------------------------------


def ssd_chunked(x, dt, A, B, C, chunk: int,
                state: Optional[jax.Array] = None):
    """x: [b, T, H, P]; dt: [b, T, H] (after softplus); A: [H] (< 0);
    B, C: [b, T, N]; state: [b, H, P, N] entering, or None (zeros).

    Returns (y [b, T, H, P] f32 without the D skip, final state f32).
    A ragged final chunk is padded with dt = 0 and x = B = C = 0: its
    padded steps decay nothing and add nothing."""
    b, T, H, P = x.shape
    N = B.shape[-1]
    dtype = x.dtype
    f32 = jnp.float32
    Q = min(chunk, T)
    n = -(-T // Q)
    pad = n * Q - T
    if pad:
        x, dt, B, C = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] *
                               (a.ndim - 2)) for a in (x, dt, B, C))
    dt = dt.astype(f32).reshape(b, n, Q, H)
    Bq = B.reshape(b, n, Q, N).astype(dtype)
    Cq = C.reshape(b, n, Q, N).astype(dtype)
    xdt = x.reshape(b, n, Q, H, P).astype(f32) * dt[..., None]
    acum = jnp.cumsum((dt * A.astype(f32)).transpose(0, 1, 3, 2), axis=-1)

    # within a chunk: (C B^T) * exp(acum_t - acum_s) for s <= t
    seg = jnp.minimum(acum[..., :, None] - acum[..., None, :], 0.0)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.where(causal, jnp.exp(seg), 0.0)             # [b,n,H,Q,Q]
    cb = jnp.einsum("bnti,bnsi->bnts", Cq, Bq, preferred_element_type=f32)
    scores = (cb[:, :, None] * decay).astype(dtype)
    y = jnp.einsum("bnhts,bnshp->bnthp", scores, xdt.astype(dtype),
                   preferred_element_type=f32)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(jnp.minimum(acum[..., -1:] - acum, 0.0))  # [b,n,H,Q]
    xend = (xdt * to_end.transpose(0, 1, 3, 2)[..., None]).astype(dtype)
    states = jnp.einsum("bnshp,bnsi->bnhpi", xend, Bq,
                        preferred_element_type=f32)         # [b,n,H,P,N]

    # carried across chunks: the state entering each chunk
    def carry(S, inp):
        st, dec = inp
        return dec[..., None, None] * S + st, S

    S0 = jnp.zeros((b, H, P, N), f32) if state is None else state.astype(f32)
    S, S_in = lax.scan(carry, S0, (states.swapaxes(0, 1),
                                   jnp.exp(acum[..., -1]).swapaxes(0, 1)))
    y_in = jnp.einsum("bnti,bnhpi->bnthp", Cq,
                      S_in.swapaxes(0, 1).astype(dtype),
                      preferred_element_type=f32)
    y = y + y_in * jnp.exp(acum).transpose(0, 1, 3, 2)[..., None]
    return y.reshape(b, n * Q, H, P)[:, :T], S


def ssd_step(x, dt, A, B, C, state):
    """One token.  x: [b, H, P]; dt: [b, H]; B, C: [b, N]; state:
    [b, H, P, N] f32 -> (y [b, H, P] f32, new state)."""
    f32 = jnp.float32
    dt = dt.astype(f32)
    S = jnp.exp(dt * A.astype(f32))[..., None, None] * state + \
        (dt[..., None] * x.astype(f32))[..., None] * \
        B.astype(f32)[:, None, None, :]
    return jnp.einsum("bhpi,bi->bhp", S, C.astype(f32)), S


def ssd(xbc, dt, A, D, state, sizes: Tuple[int, int, int, int]):
    """The chunked SSD with the ``D * xs`` skip, on the conv's output
    ``xbc`` [b, T, H*P + 2N] (xs | B | C); dt [b, T, H] after softplus;
    A, D: [H]; ``sizes`` (H, P, N, chunk).  Returns (y [b, T, H*P] f32,
    final state [b, H, P, N] f32).  On one device the Pallas kernel
    ``kernels.ssd_chunk``, which reads xs, B and C in place and keeps each
    head's state in VMEM across the chunks; its gradient is that of
    ``ssd_xla``.  Under an ``actshard`` mesh it is ``ssd_xla``: the
    partitioner cannot split a Pallas call."""
    if actshard.current_mesh() is not None:
        return ssd_xla(xbc, dt, A, D, state, sizes)
    return _ssd_kernel(xbc, dt, A, D, state, sizes)


def ssd_xla(xbc, dt, A, D, state, sizes: Tuple[int, int, int, int]):
    """``ssd`` as XLA's ``ssd_chunked``."""
    H, P, N, chunk = sizes
    b, T, _ = xbc.shape
    di = H * P
    xs = xbc[..., :di].reshape(b, T, H, P)
    y, S = ssd_chunked(xs, dt, A, xbc[..., di:di + N], xbc[..., di + N:],
                       chunk, state)
    y = y + D[:, None] * xs.astype(jnp.float32)
    return y.reshape(b, T, di), S


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_kernel(xbc, dt, A, D, state, sizes):
    # imported where the SSD is traced, as in ``_proj``
    from repro.kernels import ops
    _, _, N, chunk = sizes
    return ops.ssd(xbc, dt, A, D, state, d_state=N, chunk=chunk)


def _ssd_kernel_fwd(xbc, dt, A, D, state, sizes):
    return (_ssd_kernel(xbc, dt, A, D, state, sizes),
            (xbc, dt, A, D, state))


def _ssd_kernel_bwd(sizes, res, g):
    """The XLA form's gradient, its forward recomputed."""
    _, vjp = jax.vjp(lambda *a: ssd_xla(*a, sizes), *res)
    return vjp(g)


_ssd_kernel.defvjp(_ssd_kernel_fwd, _ssd_kernel_bwd)


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def _at(stack: jax.Array, i) -> jax.Array:
    """Layer ``i`` of a stacked leaf."""
    return lax.dynamic_index_in_dim(stack, i, keepdims=False)


def _proj(h: jax.Array, w: jax.Array, i) -> jax.Array:
    """``h @ w[i]`` in h's dtype for a weight stack ``w`` (module
    docstring)."""
    if actshard.current_mesh() is not None:
        return h @ _at(w, i).astype(h.dtype)
    # imported where a projection is traced: a process that runs another
    # model never loads Pallas
    from repro.kernels import ops
    return ops.stacked_proj(h, w, i)


def _rms(cfg: ModelConfig, scale: jax.Array, x: jax.Array) -> jax.Array:
    return L.norm_apply(cfg, {"scale": scale}, x)


def mamba_mixer(cfg: ModelConfig, p: Params, i, x: jax.Array, state=None):
    """Mamba-2 on normed ``x`` [b, T, d] with layer ``i`` of the stacks
    ``p``; ``state`` (conv inputs, SSM state) to continue from, or None.
    Returns (out [b, T, d], (conv state, SSM state))."""
    b, T, _ = x.shape
    H, P, N, di, _ = _sizes(cfg)
    f32 = jnp.float32
    with jax.named_scope("in_proj"):
        zx = _proj(x, p["w_zx"], i)
        z, xbc = zx[..., :di], jnp.concatenate(
            [zx[..., di:], _proj(x, p["w_bc"], i)], axis=-1)
        dt = _proj(x, p["w_dt"], i)
    with jax.named_scope("conv"):
        conv = {"conv_w": _at(p["conv_w"], i), "conv_b": _at(p["conv_b"], i)}
        xbc, conv_state = causal_conv1d(conv, xbc,
                                        None if state is None else state[0])
        xbc = jax.nn.silu(xbc)
    with jax.named_scope("ssd"):
        dt = jax.nn.softplus(dt.astype(f32) + _at(p["dt_bias"], i).astype(f32))
        A = -jnp.exp(_at(p["A_log"], i).astype(f32))
        D = _at(p["D"], i).astype(f32)
        if T == 1 and state is not None:
            xs = xbc[:, 0, :di].reshape(b, H, P)
            y, ssm = ssd_step(xs, dt[:, 0], A, xbc[:, 0, di:di + N],
                              xbc[:, 0, di + N:], state[1])
            y = y + D[:, None] * xs.astype(f32)
        else:
            y, ssm = ssd(xbc, dt, A, D, None if state is None else state[1],
                         (H, P, N, cfg.mamba_chunk_size))
    with jax.named_scope("gnorm"):
        y = y.reshape(b, T, di) * jax.nn.silu(z.astype(f32))
        y = _rms(cfg, _at(p["gnorm"]["scale"], i), y).astype(x.dtype)
    with jax.named_scope("out_proj"):
        return _proj(y, p["w_out"], i), (conv_state, ssm)


def attention_mixer(cfg: ModelConfig, p: Params, i, x: jax.Array,
                    state=None, pos=None):
    """Causal GQA on normed ``x`` [b, T, d], no positional encoding.
    Full sequence: returns (out, (k, v)); decode (``state`` the layer's
    KV cache [b, kv_heads, S, head_dim] and ``pos`` the step): writes
    position ``pos`` and attends to the first ``pos + 1``."""
    b, T, _ = x.shape
    h, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    scale = cfg.attention_multiplier

    def heads(a, n):
        return a.reshape(b, T, n, hd).transpose(0, 2, 1, 3)

    with jax.named_scope("qkv"):
        q = heads(_proj(x, p["wq"], i), h)
        k = heads(_proj(x, p["wk"], i), hk)
        v = heads(_proj(x, p["wv"], i), hk)
    if state is None:
        o = attn_lib.flash_attention(q, jnp.repeat(k, h // hk, axis=1),
                                     jnp.repeat(v, h // hk, axis=1), True,
                                     None, scale)
    else:
        k = lax.dynamic_update_slice_in_dim(state[0], k.astype(
            state[0].dtype), pos, axis=2)
        v = lax.dynamic_update_slice_in_dim(state[1], v.astype(
            state[1].dtype), pos, axis=2)
        o = attn_lib.decode_attention(q, k, v, pos + 1, scale=scale)
    o = actshard.attn_out_sharded(o)
    with jax.named_scope("proj"):
        o = o.transpose(0, 2, 1, 3).reshape(b, T, h * hd)
        return _proj(o, p["wo"], i), (k, v)


def mlp(cfg: ModelConfig, p: Params, i, x: jax.Array) -> jax.Array:
    f = cfg.d_ff
    with jax.named_scope("up"):
        gu = _proj(x, p["w_in"], i)
    with jax.named_scope("act"):
        g = jax.nn.silu(gu[..., :f]) * gu[..., f:]
    with jax.named_scope("down"):
        return _proj(g, p["w_out"], i)


def _layer(cfg: ModelConfig, params: Params, kind: str, i, li, x, state,
           pos=None):
    """Layer ``li`` of the stack, the ``i``-th of its kind."""
    r = cfg.residual_multiplier
    x = actshard.batch_sharded(x)
    p = params["mamba" if kind == "mamba" else "attn"]
    with jax.named_scope("ln1"):
        h = _rms(cfg, _at(p["ln"]["scale"], i), x)
    if kind == "mamba":
        with jax.named_scope("mamba"):
            h, state = mamba_mixer(cfg, p, i, h, state)
    else:
        with jax.named_scope("attn"):
            h, state = attention_mixer(cfg, p, i, h, state, pos)
    with jax.named_scope("res1"):
        x = x + r * h
    with jax.named_scope("ln2"):
        h = _rms(cfg, _at(params["mlp"]["ln"]["scale"], li), x)
    with jax.named_scope("mlp"):
        h = mlp(cfg, params["mlp"], li, h)
    with jax.named_scope("res2"):
        return x + r * h, state


def _stack(cfg: ModelConfig, x, layer_fn, states=None,
           remat: bool = False, unroll: int = 1):
    """``lax.scan`` over the periods of ``block_pattern``, the period
    unrolled in the body: ``layer_fn(x, kind, i, li, state)`` runs layer
    ``li``, the ``i``-th of its kind.  ``states``: (mamba states, attention
    states), pytrees led by [n_mamba] and [n_attn], or None.  Returns (x,
    the states ``layer_fn`` returned, stacked the same way)."""
    period = _period(cfg)
    n = cfg.num_layers // len(period)
    per = {k: period.count(k) for k in ("mamba", "attention")}

    def split(tree, k):
        return jax.tree.map(lambda a: a.reshape((n, k) + a.shape[1:]), tree)

    def stacked(outs):
        return jax.tree.map(lambda *a: jnp.stack(a), *outs) if outs else None

    def body(x, xs):
        pi, st = xs
        outs = {"mamba": [], "attention": []}
        for j, kind in enumerate(period):
            k = len(outs[kind])
            s = None if st is None else jax.tree.map(
                lambda a: a[k], st[kind != "mamba"])
            x, s = layer_fn(x, kind, pi * per[kind] + k,
                            pi * len(period) + j, s)
            outs[kind].append(s)
        return x, (stacked(outs["mamba"]), stacked(outs["attention"]))

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    xs = None if states is None else (split(states[0], per["mamba"]),
                                      split(states[1], per["attention"]))
    x, out = lax.scan(body, x, (jnp.arange(n, dtype=jnp.int32), xs),
                      unroll=unroll)
    return x, jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), out)


# ---------------------------------------------------------------------------
# Model entry points
# ---------------------------------------------------------------------------


def _embed(cfg: ModelConfig, params: Params, tokens) -> jax.Array:
    x = L.embed_tokens(params["embed"], tokens, cfg.compute_dtype)
    return actshard.batch_sharded(x * cfg.embedding_multiplier)


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, Any], *,
            remat: bool = True, scan_unroll: int = 1,
            **_) -> Tuple[jax.Array, jax.Array]:
    with jax.named_scope("embed"):
        x = _embed(cfg, params, batch["tokens"])

    def layer_fn(x, kind, i, li, _):
        return _layer(cfg, params, kind, i, li, x, None)[0], None

    x, _ = _stack(cfg, x, layer_fn, remat=remat, unroll=scan_unroll)
    with jax.named_scope("head.ln"):
        x = L.norm_apply(cfg, params["ln_f"], x)
    return x, jnp.zeros((), jnp.float32)


def logits_fn(cfg: ModelConfig, params: Params, hidden: jax.Array):
    """Normed hidden [B, S, d] -> [B, S, vocab] over the tied embedding,
    compute-dtype operands and f32 sums."""
    with jax.named_scope("head.logits"):
        dt = cfg.compute_dtype
        logits = jnp.einsum("bsd,vd->bsv", hidden.astype(dt),
                            params["embed"]["embedding"].astype(dt),
                            preferred_element_type=jnp.float32)
        return actshard.logits_sharded(logits / cfg.logits_scaling)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> HybridCache:
    H, P, N, _, conv_c = _sizes(cfg)
    nm = cfg.block_pattern.count("mamba")
    na = cfg.block_pattern.count("attention")
    kv = (na, batch, cfg.num_kv_heads, seq_len, cfg.head_dim)
    return HybridCache(
        conv=jnp.zeros((nm, batch, cfg.conv1d_width - 1, conv_c),
                       cfg.compute_dtype),
        ssm=jnp.zeros((nm, batch, H, P, N), jnp.float32),
        k=jnp.zeros(kv, cfg.compute_dtype),
        v=jnp.zeros(kv, cfg.compute_dtype),
        step=jnp.zeros((), jnp.int32))


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, Any], *,
            decode_len: Optional[int] = None, scan_unroll: int = 1,
            **_) -> Tuple[jax.Array, HybridCache]:
    """The prompt's last normed hidden [B, d] and the cache after it; the
    KV cache holds ``decode_len`` positions (default: the prompt's)."""
    x = _embed(cfg, params, batch["tokens"])
    T = x.shape[1]
    S = max(T, decode_len or T)

    def layer_fn(x, kind, i, li, _):
        x, st = _layer(cfg, params, kind, i, li, x, None)
        if kind != "mamba":
            st = tuple(jnp.pad(a.astype(cfg.compute_dtype),
                               ((0, 0), (0, 0), (0, S - T), (0, 0)))
                       for a in st)
        return x, st

    x, ((conv, ssm), (k, v)) = _stack(cfg, x, layer_fn, unroll=scan_unroll)
    x = L.norm_apply(cfg, params["ln_f"], x)
    return x[:, -1, :], HybridCache(conv=conv.astype(cfg.compute_dtype),
                                    ssm=ssm, k=k, v=v,
                                    step=jnp.array(T, jnp.int32))


def decode_step(cfg: ModelConfig, params: Params, cache: HybridCache,
                batch: Dict[str, Any], *, scan_unroll: int = 1,
                **_) -> Tuple[jax.Array, HybridCache]:
    x = _embed(cfg, params, batch["tokens"])

    def layer_fn(x, kind, i, li, st):
        return _layer(cfg, params, kind, i, li, x, st, pos=cache.step)

    x, ((conv, ssm), (k, v)) = _stack(
        cfg, x, layer_fn,
        ((cache.conv, cache.ssm), (cache.k, cache.v)), unroll=scan_unroll)
    x = L.norm_apply(cfg, params["ln_f"], x)
    logits = logits_fn(cfg, params, x)[:, 0, :]
    return logits, HybridCache(conv=conv.astype(cfg.compute_dtype), ssm=ssm,
                               k=k, v=v, step=cache.step + 1)
