"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode).

Property style: every kernel is swept over shapes x dtypes x block sizes
(hypothesis is unavailable offline, so properties are exercised as seeded
parametric sweeps — same coverage intent).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=3e-5, atol=3e-5)


def _assert_close(out, want, dtype):
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# fused inverted bottleneck (C3)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("m,d,f", [(64, 32, 128), (100, 48, 96),
                                   (17, 64, 256), (256, 128, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("gated", [False, True])
def test_fused_ibn_sweep(m, d, f, dtype, gated):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (m, d), jnp.float32).astype(dtype)
    w1 = (jax.random.normal(ks[1], (d, f), jnp.float32) * 0.1).astype(dtype)
    w2 = (jax.random.normal(ks[2], (f, d), jnp.float32) * 0.1).astype(dtype)
    wg = (jax.random.normal(ks[3], (d, f), jnp.float32) * 0.1).astype(dtype) \
        if gated else None
    act = "silu" if gated else "gelu"
    out = ops.fused_ibn(x, w1, w2, wg, activation=act, block_m=32,
                        block_f=64)
    want = ref.fused_ibn_ref(x, w1, w2, wg, activation=act)
    _assert_close(out, want, dtype)


def test_fused_ibn_block_invariance():
    """The depth-first tiling must not change the math: any (bm, bf)."""
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (64, 32))
    w1 = jax.random.normal(ks[1], (32, 128)) * 0.1
    w2 = jax.random.normal(ks[2], (128, 32)) * 0.1
    want = ref.fused_ibn_ref(x, w1, w2)
    for bm in (16, 32, 64):
        for bf in (32, 64, 128):
            out = ops.fused_ibn(x, w1, w2, block_m=bm, block_f=bf)
            np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                       rtol=2e-5, atol=2e-5)


def test_fused_ibn_ragged_edges():
    """Imperfect blocks on EdgeNeXt-style odd extents: 197 pixels x
    d_ff=160 with 64-blocks leaves ragged final blocks on both grid
    axes; the padded blocks must be masked out in-kernel."""
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (197, 48))
    w1 = jax.random.normal(ks[1], (48, 160)) * 0.1
    w2 = jax.random.normal(ks[2], (160, 48)) * 0.1
    out = ops.fused_ibn(x, w1, w2, block_m=64, block_f=64)
    want = ref.fused_ibn_ref(x, w1, w2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.slow
@pytest.mark.parametrize("m,d,f,bm,bf", [
    (197, 48, 160, 64, 64),      # ragged m (197 = 3*64 + 5) and f
    (304, 160, 304, 128, 128),   # ragged both, stage-4 dims
    (48, 48, 192, 32, 128),      # ragged m only
    (160, 64, 304, 32, 256),     # ragged f only (304 = 256 + 48)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("gated", [False, True])
def test_fused_ibn_ragged_sweep(m, d, f, bm, bf, dtype, gated):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (m, d), jnp.float32).astype(dtype)
    w1 = (jax.random.normal(ks[1], (d, f)) * 0.1).astype(dtype)
    w2 = (jax.random.normal(ks[2], (f, d)) * 0.1).astype(dtype)
    wg = (jax.random.normal(ks[3], (d, f)) * 0.1).astype(dtype) \
        if gated else None
    act = "silu" if gated else "gelu"
    out = ops.fused_ibn(x, w1, w2, wg, activation=act, block_m=bm,
                        block_f=bf)
    want = ref.fused_ibn_ref(x, w1, w2, wg, activation=act)
    _assert_close(out, want, dtype)


# ---------------------------------------------------------------------------
# matmul + LayerNorm epilogue (C2)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("m,k,n", [(64, 32, 48), (100, 64, 32),
                                   (32, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_ln_sweep(m, k, n, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (m, k), jnp.float32).astype(dtype)
    w = (jax.random.normal(ks[1], (k, n)) * 0.1).astype(dtype)
    b = (jax.random.normal(ks[2], (n,)) * 0.1).astype(dtype)
    g = jnp.ones((n,), dtype) + 0.1 * jax.random.normal(
        ks[3], (n,)).astype(dtype)
    be = (jax.random.normal(ks[4], (n,)) * 0.1).astype(dtype)
    out = ops.matmul_ln(x, w, b, g, be, block_m=32, block_k=32)
    want = ref.matmul_ln_ref(x, w, b, g, be)
    _assert_close(out, want, dtype)


def test_matmul_ln_rows_normalized():
    """Post-LN rows (gamma=1, beta=0) have zero mean / unit variance."""
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (64, 32))
    w = jax.random.normal(ks[1], (32, 64))
    out = ops.matmul_ln(x, w, jnp.zeros(64), jnp.ones(64), jnp.zeros(64),
                        block_m=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out.mean(-1)), 0.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out.var(-1)), 1.0, atol=1e-3)


def test_matmul_ln_ragged_edges():
    """block_k no longer needs to divide K: the ragged reduction block
    is zero-masked in-kernel so the LN statistics stay exact."""
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (197, 48))
    w = jax.random.normal(ks[1], (48, 160)) * 0.1
    b = jax.random.normal(ks[2], (160,)) * 0.1
    g = jnp.ones((160,)) + 0.1 * jax.random.normal(ks[3], (160,))
    be = jax.random.normal(ks[4], (160,)) * 0.1
    out = ops.matmul_ln(x, w, b, g, be, block_m=64, block_k=32)
    want = ref.matmul_ln_ref(x, w, b, g, be)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.slow
@pytest.mark.parametrize("m,k,n,bm,bk", [
    (197, 48, 160, 64, 32),      # ragged m and k
    (160, 304, 48, 64, 128),     # ragged k (304 = 2*128 + 48)
    (304, 160, 304, 128, 64),    # ragged m and k, stage-4 dims
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_ln_ragged_sweep(m, k, n, bm, bk, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (m, k), jnp.float32).astype(dtype)
    w = (jax.random.normal(ks[1], (k, n)) * 0.1).astype(dtype)
    b = (jax.random.normal(ks[2], (n,)) * 0.1).astype(dtype)
    g = jnp.ones((n,), dtype) + 0.1 * jax.random.normal(
        ks[3], (n,)).astype(dtype)
    be = (jax.random.normal(ks[4], (n,)) * 0.1).astype(dtype)
    out = ops.matmul_ln(x, w, b, g, be, block_m=bm, block_k=bk)
    want = ref.matmul_ln_ref(x, w, b, g, be)
    _assert_close(out, want, dtype)


# ---------------------------------------------------------------------------
# flash attention (C2)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sq,sk,bq,bk", [(64, 64, 16, 16), (64, 64, 64, 16),
                                         (128, 64, 32, 32),
                                         (64, 128, 16, 64)])
@pytest.mark.slow
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 24)])
def test_flash_attention_sweep(sq, sk, bq, bk, causal, window):
    if causal and sq > sk:
        pytest.skip("causal with sq>sk undefined here")
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 2, sq, 16))
    k = jax.random.normal(ks[1], (2, 2, sk, 16))
    v = jax.random.normal(ks[2], (2, 2, sk, 16))
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=bq, block_k=bk)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 32)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 2, 64, 32)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 2, 64, 32)).astype(dtype)
    out = ops.flash_attention(q, k, v, block_q=16, block_k=16)
    want = ref.attention_ref(q, k, v)
    _assert_close(out, want, dtype)


def test_flash_attention_ragged_edges():
    """ViT-style ragged sequence (197 = 196 patches + CLS): padded keys
    must fall out of the online softmax via the in-kernel kv_len mask."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 197, 16))
    k = jax.random.normal(ks[1], (1, 2, 197, 16))
    v = jax.random.normal(ks[2], (1, 2, 197, 16))
    out = ops.flash_attention(q, k, v, causal=False, block_q=64,
                              block_k=64)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
@pytest.mark.parametrize("sq,sk,bq,bk", [
    (197, 197, 64, 64),          # ragged both sequence axes
    (160, 304, 64, 128),         # ragged kv only (304 = 2*128 + 48)
    (304, 304, 128, 128),        # stage-4 XCA token extent
])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 48)])
def test_flash_attention_ragged_sweep(sq, sk, bq, bk, causal, window):
    if causal and sq > sk:
        pytest.skip("causal with sq>sk undefined here")
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, sq, 16))
    k = jax.random.normal(ks[1], (1, 2, sk, 16))
    v = jax.random.normal(ks[2], (1, 2, sk, 16))
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=bq, block_k=bk)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# depthwise conv (C1 — C|FX dataflow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("h,w,c,kk", [(12, 12, 24, 3), (16, 16, 48, 5),
                                      (8, 8, 16, 7), (10, 14, 32, 9)])
def test_depthwise_conv_sweep(h, w, c, kk):
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (2, h, w, c))
    wt = jax.random.normal(ks[1], (kk, kk, c)) * 0.2
    b = jax.random.normal(ks[2], (c,)) * 0.1
    out = ops.depthwise_conv2d(x, wt, b, block_c=16)
    want = ref.depthwise_conv2d_ref(x, wt, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_depthwise_channel_independence():
    """Depthwise property: channel c of the output depends only on
    channel c of the input (the C|FX dataflow has no cross-channel MACs)."""
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (1, 8, 8, 16))
    wt = jax.random.normal(ks[1], (3, 3, 16))
    b = jnp.zeros((16,))
    base = np.asarray(ops.depthwise_conv2d(x, wt, b, block_c=8))
    x2 = x.at[..., 3].set(jax.random.normal(ks[2], (1, 8, 8)))
    pert = np.asarray(ops.depthwise_conv2d(x2, wt, b, block_c=8))
    changed = np.abs(pert - base).max(axis=(0, 1, 2))
    assert changed[3] > 0
    np.testing.assert_allclose(np.delete(changed, 3), 0.0, atol=1e-6)


# ---------------------------------------------------------------------------
# chunked WKV6 (beyond-paper)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("t,chunk", [(32, 8), (32, 16), (64, 64), (48, 16),
                                     (50, 16), (33, 8), (100, 64)])
def test_wkv_chunk_sweep(t, chunk):
    ks = jax.random.split(KEY, 5)
    BH, K = 4, 8
    r = jax.random.normal(ks[0], (BH, t, K)) * 0.5
    k = jax.random.normal(ks[1], (BH, t, K)) * 0.5
    v = jax.random.normal(ks[2], (BH, t, K)) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], (BH, t, K)) * 0.5)
    u = jax.random.normal(ks[4], (BH, K)) * 0.5
    out, st = ops.wkv_chunked(r, k, v, logw, u, chunk=chunk)
    want, st_want = ref.wkv_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_want),
                               rtol=2e-4, atol=2e-4)


def test_wkv_chunk_ragged_t():
    """T % chunk != 0: the wrapper pads T to a chunk multiple and the
    kernel masks the padded tail to the true ``valid_t`` extent, so a
    ragged launch matches the sequential reference — the searched chunk
    is honored verbatim instead of being shrunk to a divisor."""
    ks = jax.random.split(KEY, 5)
    BH, T, K = 2, 50, 8
    r = jax.random.normal(ks[0], (BH, T, K)) * 0.5
    k = jax.random.normal(ks[1], (BH, T, K)) * 0.5
    v = jax.random.normal(ks[2], (BH, T, K)) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], (BH, T, K)) * 0.5)
    u = jax.random.normal(ks[4], (BH, K)) * 0.5
    out, st = ops.wkv_chunked(r, k, v, logw, u, chunk=16)
    want, st_want = ref.wkv_ref(r, k, v, logw, u)
    assert out.shape == (BH, T, K)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_want),
                               rtol=2e-4, atol=2e-4)


def test_wkv_chunk_invariance():
    """Chunk size must not change the recurrence (associativity)."""
    ks = jax.random.split(KEY, 5)
    BH, T, K = 2, 64, 8
    r = jax.random.normal(ks[0], (BH, T, K)) * 0.5
    k = jax.random.normal(ks[1], (BH, T, K)) * 0.5
    v = jax.random.normal(ks[2], (BH, T, K)) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], (BH, T, K)) * 0.5)
    u = jax.random.normal(ks[4], (BH, K)) * 0.5
    out8, st8 = ops.wkv_chunked(r, k, v, logw, u, chunk=8)
    out32, st32 = ops.wkv_chunked(r, k, v, logw, u, chunk=32)
    np.testing.assert_allclose(np.asarray(out8), np.asarray(out32),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st8), np.asarray(st32),
                               rtol=2e-4, atol=2e-4)


def _wkv_model_inputs(B, T, H, K, dtype, decay_scale):
    """r, k, v (dtype), logw (f32), u, a non-zero initial state, in the
    model's layout [B, T, H, K]."""
    ks = jax.random.split(KEY, 6)
    r, k, v = (jax.random.normal(kk, (B, T, H, K)).astype(dtype) * 0.5
               for kk in ks[:3])
    logw = -jnp.exp(jax.random.normal(ks[3], (B, T, H, K)) * decay_scale)
    u = jax.random.normal(ks[4], (H, K)) * 0.5
    state = jax.random.normal(ks[5], (B, H, K, K)) * 0.3
    return r, k, v, logw, u, state


@pytest.mark.parametrize("B,T,H,K,chunk,dtype,decay_scale,tol,steps", [
    # four heads of 64 a block, two lane groups; T = 50 ragged at chunk 16
    (2, 50, 4, 64, 16, jnp.float32, 0.5, 2e-4, None),
    # the same two heads a block and two chunks a step: the chunk loop
    # inside a grid step, and a ragged step
    (2, 50, 4, 64, 16, jnp.float32, 0.5, 2e-4, (2, 2)),
    # chunk 64 in sub-chunks; log-decays down to -20 a token, so the
    # sub-chunk factors underflow where the true scores do
    (1, 128, 2, 64, 64, jnp.float32, 1.2, 2e-4, None),
    # bf16 r/k/v and f32 logw, as the model feeds them: one bf16 MXU
    # pass, as XLA's chunked form takes on a TPU
    (1, 128, 4, 64, 64, jnp.bfloat16, 0.5, 1e-2, None),
], ids=["ragged-t-state", "two-chunks-a-step", "strong-decay", "bf16"])
def test_wkv_kernel_in_model_layout(B, T, H, K, chunk, dtype, decay_scale,
                                    tol, steps, monkeypatch):
    """ops.wkv (the kernel on [B, T, H*K] operands, several heads a
    block, from a non-zero state) == ref.wkv_ref per head == the model's
    XLA chunked form.  ``steps``: (heads, chunks) a grid step in place of
    the tuned ones."""
    from repro.models import rwkv6
    if steps:
        monkeypatch.setattr(ops, "_WKV_HEADS", steps[0])
        monkeypatch.setattr(ops, "_WKV_CHUNKS", steps[1])
    r, k, v, logw, u, state = _wkv_model_inputs(B, T, H, K, dtype,
                                                decay_scale)
    if decay_scale > 1:
        assert float(logw.min()) < -20
    out, st = ops.wkv(r, k, v, logw, u, state, chunk=chunk)
    assert out.dtype == dtype and out.shape == (B, T, H, K)

    def per_head(x):                     # [B,T,H,K] -> [BH,T,K]
        return x.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
            B * H, T, K)
    want, st_want = ref.wkv_ref(per_head(r), per_head(k), per_head(v),
                                per_head(logw), jnp.tile(u, (B, 1)),
                                state.reshape(B * H, K, K))
    xla, st_xla = rwkv6.wkv_chunked_xla(r, k, v, logw, u, state, chunk)
    scale = float(jnp.abs(want).max())
    for got_o, got_s in ((out, st), (xla, st_xla)):
        np.testing.assert_allclose(
            np.asarray(per_head(got_o)), np.asarray(want),
            rtol=tol, atol=tol * scale)
        np.testing.assert_allclose(
            np.asarray(got_s.reshape(B * H, K, K)), np.asarray(st_want),
            rtol=tol, atol=tol * float(jnp.abs(st_want).max()))


def test_wkv_kernel_gradient_is_the_xla_forms():
    """The model's kernel path differentiates as XLA's chunked form: its
    custom VJP recomputes that form's gradient."""
    from repro.models import rwkv6
    r, k, v, logw, u, state = _wkv_model_inputs(1, 32, 2, 64, jnp.float32,
                                                0.5)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    w_out = jax.random.normal(ks[0], r.shape)
    w_st = jax.random.normal(ks[1], state.shape)

    def loss(f):
        def run(*args):
            out, st = f(*args, 16)
            return (out * w_out).sum() + (st * w_st).sum()
        return jax.grad(run, argnums=tuple(range(6)))(r, k, v, logw, u,
                                                      state)

    for got, want in zip(loss(rwkv6._wkv_kernel),
                         loss(rwkv6.wkv_chunked_xla)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Mamba-2's chunked SSD (mamba_hybrid), the conv's output read in place
# ---------------------------------------------------------------------------


def _ssd_inputs(b, T, H, P, N, dtype, decay, key=7):
    """xbc (xs | B | C, dtype), dt (f32, after softplus), A, D, and a
    non-zero entering state [b, H, P, N]; ``decay`` sets dt*A's mean."""
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    xbc = jax.random.normal(ks[0], (b, T, H * P + 2 * N)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, T, H)))
    A = -decay * jnp.exp(0.2 * jax.random.normal(ks[2], (H,))) \
        / jnp.mean(dt)
    D = jax.random.uniform(ks[3], (H,), minval=0.5, maxval=1.5)
    state = jax.random.normal(ks[4], (b, H, P, N)) * 0.3
    return xbc, dt, A, D, state


def _ssd_recurrence(xbc, dt, A, D, state, H, P, N):
    """The token-by-token recurrence (``mamba_hybrid.ssd_step``) with
    the skip, in f32: (y [b, T, H*P], final state)."""
    from repro.models import mamba_hybrid
    b, T, _ = xbc.shape
    di = H * P
    xbc = xbc.astype(jnp.float32)
    xs = xbc[..., :di].reshape(b, T, H, P)

    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        y, S = mamba_hybrid.ssd_step(x_t, dt_t, A, B_t, C_t, S)
        return S, y + D[:, None] * x_t

    S, y = jax.lax.scan(step, state, tuple(a.swapaxes(0, 1) for a in (
        xs, dt, xbc[..., di:di + N], xbc[..., di + N:])))
    return y.swapaxes(0, 1).reshape(b, T, di), S


@pytest.mark.parametrize(
    "b,T,H,P,N,chunk,heads,sub,dtype,decay,entering,tol", [
        # the reduced configuration's widths (4 heads of 32, d_state 16:
        # xs, B and C sliced apart), T ragged at chunk 8, elementwise
        (2, 37, 4, 32, 16, 8, 8, 64, jnp.float32, 0.5, True, 2e-4),
        # T shorter than one chunk
        (2, 5, 4, 32, 16, 8, 8, 64, jnp.float32, 1.0, False, 2e-4),
        # several chunks of 16 in sub-chunks of 4, two heads of 64 a
        # step (128 lanes, read in place from xbc with N = 128)
        (1, 64, 4, 64, 128, 16, 2, 4, jnp.float32, 0.5, True, 2e-4),
        # weak decay: the carried state matters across every chunk
        (1, 64, 4, 64, 128, 32, 4, 8, jnp.float32, 0.05, True, 2e-4),
        # strong decay: dt*A down past -100 within a chunk, so the
        # sub-chunk factors underflow where the true decays do
        (1, 64, 4, 64, 128, 32, 2, 8, jnp.float32, 8.0, True, 2e-4),
        # bf16 xbc, as the model feeds it: one bf16 MXU pass, f32 sums
        (1, 96, 4, 64, 128, 32, 2, 16, jnp.bfloat16, 0.5, True, 2e-2),
        (2, 40, 4, 32, 16, 16, 8, 8, jnp.bfloat16, 1.0, False, 2e-2),
    ], ids=["reduced-ragged", "shorter-than-a-chunk", "sub-chunks",
            "weak-decay", "strong-decay", "bf16", "bf16-reduced"])
def test_ssd_kernel_in_model_layout(b, T, H, P, N, chunk, heads, sub, dtype,
                                    decay, entering, tol, monkeypatch):
    """ops.ssd (the kernel on the conv's [b, T, H*P + 2N] output, from a
    non-zero state or zeros) == the token-by-token recurrence == the
    model's XLA chunked form, y with the D skip and the final state.
    ``heads``, ``sub``: the grid step in place of the tuned one."""
    from repro.models import mamba_hybrid
    monkeypatch.setattr(ops, "_SSD_HEADS", heads)
    monkeypatch.setattr(ops, "_SSD_SUB", sub)
    xbc, dt, A, D, state = _ssd_inputs(b, T, H, P, N, dtype, decay)
    if decay > 1:
        assert float(jnp.cumsum(dt * A, axis=1).min()) < -100
    state = state if entering else jnp.zeros_like(state)
    y, st = ops.ssd(xbc, dt, A, D, state if entering else None,
                    d_state=N, chunk=chunk)
    assert y.dtype == st.dtype == jnp.float32
    assert y.shape == (b, T, H * P) and st.shape == (b, H, P, N)
    want, st_want = _ssd_recurrence(xbc, dt, A, D, state, H, P, N)
    with jax.default_matmul_precision("highest"):
        xla, st_xla = mamba_hybrid.ssd_xla(xbc.astype(jnp.float32), dt, A,
                                           D, state, (H, P, N, chunk))
    for got_y, got_s in ((y, st), (xla, st_xla)):
        np.testing.assert_allclose(
            np.asarray(got_y), np.asarray(want), rtol=tol,
            atol=tol * float(jnp.abs(want).max()))
        np.testing.assert_allclose(
            np.asarray(got_s), np.asarray(st_want), rtol=tol,
            atol=tol * float(jnp.abs(st_want).max()))


def test_ssd_kernel_gradient_is_the_xla_forms():
    """The model's kernel path differentiates as XLA's chunked form: its
    custom VJP recomputes that form's gradient, the entering state's
    too."""
    from repro.models import mamba_hybrid
    H, P, N, chunk = 4, 32, 16, 8
    xbc, dt, A, D, state = _ssd_inputs(1, 20, H, P, N, jnp.float32, 0.5)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    w_y = jax.random.normal(ks[0], (1, 20, H * P))
    w_st = jax.random.normal(ks[1], state.shape)

    def loss(f):
        def run(*args):
            y, st = f(*args, (H, P, N, chunk))
            return (y * w_y).sum() + (st * w_st).sum()
        return jax.grad(run, argnums=tuple(range(5)))(xbc, dt, A, D, state)

    for got, want in zip(loss(mamba_hybrid._ssd_kernel),
                         loss(mamba_hybrid.ssd_xla)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# projection by one layer of a stacked weight, cast in VMEM
# ---------------------------------------------------------------------------


def _stacked_proj_ref(x, w, layer):
    return jnp.dot(x, w[layer].astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)


@pytest.mark.parametrize("m,k,n", [
    (1, 64, 128),                   # decode: one row, whole extents
    (8, 128, 256),
    (64, 200, 300),                 # ragged K and N: whole extents
    (8, 1536, 2304),                # K in 3 blocks of 512, N in 2 of 1152
    (600, 128, 128),                # rows past one block: x padded
], ids=["m1", "m8", "m64-ragged", "m8-tiled", "m600"])
@pytest.mark.parametrize("wdtype", [jnp.float32, jnp.bfloat16])
def test_stacked_proj_equals_the_cast_layer(m, k, n, wdtype):
    """ops.stacked_proj(x, w, l) == x @ w[l].astype(bf16), f32
    accumulation, for every layer l of the stack."""
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (m, k), jnp.float32).astype(jnp.bfloat16)
    w = (jax.random.normal(ks[1], (3, k, n), jnp.float32) * 0.1
         ).astype(wdtype)
    for layer in range(w.shape[0]):
        out = ops.stacked_proj(x, w, jnp.int32(layer))
        assert out.shape == (m, n) and out.dtype == x.dtype
        _assert_close(out, _stacked_proj_ref(x, w, layer), jnp.bfloat16)


def test_stacked_proj_refuses_a_stack_it_would_have_to_pad():
    """N = 2100 is over the 2048 block and no multiple of 128 divides
    it: padding would copy the whole stack on every call."""
    x = jnp.zeros((8, 128), jnp.bfloat16)
    w = jnp.zeros((2, 128, 2100), jnp.float32)
    with pytest.raises(ValueError, match="divides 2100"):
        ops.stacked_proj(x, w, 0)


def test_stacked_proj_gradient_is_the_plain_projections():
    """The VJP: dx through the cast layer, dw into that layer alone."""
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (2, 8, 128), jnp.float32)
    w = jax.random.normal(ks[1], (3, 128, 256), jnp.float32) * 0.1
    g = jax.random.normal(ks[2], (2, 8, 256), jnp.float32)

    def loss(proj):
        return lambda x, w: jnp.sum(proj(x, w) * g)

    got = jax.grad(loss(lambda x, w: ops.stacked_proj(x, w, 1)),
                   (0, 1))(x, w)
    want = jax.grad(loss(lambda x, w: x @ w[1]), (0, 1))(x, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-5, atol=3e-5)
    assert not np.asarray(got[1])[[0, 2]].any()


# ---------------------------------------------------------------------------
# every wrapper at its default blocks, at model-sized shapes
# ---------------------------------------------------------------------------


def _default_fused_ibn(ks):
    # transformer-FFN-shaped: M x D x F = 512 x 256 x 1024
    x = jax.random.normal(ks[0], (512, 256), jnp.float32)
    w1 = jax.random.normal(ks[1], (256, 1024)) * 0.05
    w2 = jax.random.normal(ks[2], (1024, 256)) * 0.05
    return (ops.fused_ibn(x, w1, w2), ref.fused_ibn_ref(x, w1, w2),
            _tol(jnp.float32)["atol"])


def _default_matmul_ln(ks):
    x = jax.random.normal(ks[0], (512, 256), jnp.float32)
    w = jax.random.normal(ks[1], (256, 256)) * 0.05
    b, g, be = jnp.zeros((256,)), jnp.ones((256,)), jnp.zeros((256,))
    return (ops.matmul_ln(x, w, b, g, be), ref.matmul_ln_ref(x, w, b, g, be),
            _tol(jnp.float32)["atol"])


def _default_flash_attention(ks):
    q, k, v = (jax.random.normal(kk, (1, 4, 256, 64)) for kk in ks[:3])
    return ops.flash_attention(q, k, v), ref.attention_ref(q, k, v), 2e-4


def _default_depthwise_conv2d(ks):
    # EdgeNeXt stage-3-shaped: 16 x 16 x 160, kernel 7
    x = jax.random.normal(ks[0], (1, 16, 16, 160))
    w = jax.random.normal(ks[1], (7, 7, 160)) * 0.1
    b = jnp.zeros((160,))
    return (ops.depthwise_conv2d(x, w, b),
            ref.depthwise_conv2d_ref(x, w, b), 1e-4)


def _default_wkv_chunked(ks):
    # BH x T x K = 8 x 128 x 64: two chunks of the default 64
    r, k, v = (jax.random.normal(kk, (8, 128, 64)) * 0.5 for kk in ks[:3])
    logw = -jnp.exp(jax.random.normal(ks[3], (8, 128, 64)))
    u = jax.random.normal(ks[4], (8, 64)) * 0.5
    return (ops.wkv_chunked(r, k, v, logw, u)[0],
            ref.wkv_ref(r, k, v, logw, u)[0], 2e-4)


@pytest.mark.parametrize("case", [
    _default_fused_ibn, _default_matmul_ln, _default_flash_attention,
    _default_depthwise_conv2d, _default_wkv_chunked,
], ids=["fused_ibn", "matmul_ln", "flash_attention", "depthwise_conv2d",
        "wkv_chunked"])
def test_kernel_at_its_default_blocks(case):
    """Each ops wrapper called with no blocks, so its defaults set the
    grid; tolerances are those of the kernel's sweep above."""
    out, want, tol = case(jax.random.split(KEY, 5))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=tol, atol=tol)
