"""Model-internal invariants: chunked==recurrent recurrences, MoE
properties, RoPE properties, IBN chunking equivalence, EdgeNeXt."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.edgenext_s import CONFIG as EDGE_FULL, reduced_edgenext
from repro.models import edgenext, layers as L, params as P, recurrentgemma
from repro.models import rwkv6

KEY = jax.random.PRNGKey(3)


# ---------------------------------------------------------------------------
# RWKV: chunked form == naive recurrence (the paper-technique transfer)
# ---------------------------------------------------------------------------


def test_wkv_chunked_equals_recurrent():
    """The chunked WKV, as the Pallas kernel (``wkv_chunked`` off a
    mesh) and as XLA's chunked form, == the per-token recurrence."""
    B, T, H, K = 2, 32, 2, 8
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, T, H, K)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, K)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, K)) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], (B, T, H, K)))
    u = jax.random.normal(ks[4], (H, K)) * 0.5
    state0 = jnp.zeros((B, H, K, K), jnp.float32)

    state = state0
    outs = []
    for t in range(T):
        o, state = rwkv6.wkv_recurrent_step(
            r[:, t], k[:, t], v[:, t], logw[:, t], u, state)
        outs.append(o)
    out_r = jnp.stack(outs, axis=1)
    for chunked in (rwkv6.wkv_chunked, rwkv6.wkv_chunked_xla):
        out_c, state_c = chunked(r, k, v, logw, u, state0, chunk=8)
        np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_r),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(state_c), np.asarray(state),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("on_mesh", [False, True], ids=["one-device",
                                                      "mesh"])
def test_rwkv6_projections_are_kernels_off_a_mesh_only(on_mesh):
    """Off a mesh each layer's eight projections are ``stacked_proj``
    Pallas calls, in the train step's forward and in decode, and the
    train step's WKV is the ``wkv_chunk`` kernel; under an ``actshard``
    mesh none is, since the partitioner cannot split one.  Decode's one
    token takes the recurrent step, never the WKV kernel."""
    from repro.models import actshard
    from repro.optim import adamw_init, warmup_cosine
    from repro.runtime import build_train_step
    cfg = reduced(get_config("rwkv6-1.6b"))
    params = P.init_params(KEY, rwkv6.param_defs(cfg))
    tokens = jnp.zeros((2, 16), jnp.int32)
    step = build_train_step(cfg, lr_schedule=warmup_cosine(1e-3, 2, 10))
    cache = rwkv6.init_cache(cfg, 2, 16)
    actshard.set_mesh(jax.make_mesh(
        (1, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2) if on_mesh else None)
    try:
        train = jax.make_jaxpr(step)(params, adamw_init(params),
                                     {"tokens": tokens, "labels": tokens})
        decode = jax.make_jaxpr(
            lambda p, c: rwkv6.decode_step(cfg, p, c,
                                           {"tokens": tokens[:, :1]}))(
            params, cache)
    finally:
        actshard.set_mesh(None)
    for jaxpr in (train, decode):
        assert ("pallas_call" in str(jaxpr)) is not on_mesh
    assert ("name=wkv_chunk" in str(train)) is not on_mesh
    assert "name=wkv_chunk" not in str(decode)


def test_rg_lru_scan_equals_stepwise():
    cfg = reduced(get_config("recurrentgemma-2b"))
    rec = P.init_params(KEY, recurrentgemma._recurrent_defs(cfg))
    u = jax.random.normal(jax.random.PRNGKey(9), (2, 16, cfg.lru_width))
    y, h_last = recurrentgemma.rg_lru(rec, u)
    h = jnp.zeros((2, cfg.lru_width), jnp.float32)
    for t in range(16):
        yt, h = recurrentgemma.rg_lru_step(rec, u[:, t], h)
        np.testing.assert_allclose(np.asarray(y[:, t]), np.asarray(yt),
                                   rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(h),
                               rtol=2e-4, atol=2e-4)


def test_causal_conv1d_state_continuity():
    """conv(x) == conv(x[:8]) ++ conv(x[8:], carried state)."""
    cfg = reduced(get_config("recurrentgemma-2b"))
    rec = P.init_params(KEY, recurrentgemma._recurrent_defs(cfg))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.lru_width))
    y_full, _ = recurrentgemma.causal_conv1d(rec, x)
    y1, st = recurrentgemma.causal_conv1d(rec, x[:, :8])
    y2, _ = recurrentgemma.causal_conv1d(rec, x[:, 8:], st)
    np.testing.assert_allclose(np.asarray(y_full),
                               np.asarray(jnp.concatenate([y1, y2], 1)),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# MoE properties
# ---------------------------------------------------------------------------


def _moe_setup(top_k=2, e=4, pad=0):
    cfg = reduced(get_config("qwen3-moe-30b-a3b"))
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, top_k=top_k, num_experts=e,
                                     num_experts_padded=e + pad))
    params = P.init_params(KEY, L.moe_defs(cfg))
    return cfg, params


def test_moe_output_finite_and_shaped():
    cfg, params = _moe_setup()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model))
    out, aux = L.moe_apply(cfg, params, x)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    assert 0.0 <= float(aux) < cfg.moe.num_experts


def test_moe_padded_experts_unused():
    """Tokens must never route to padding experts (masked logits)."""
    cfg, params = _moe_setup(pad=4)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg.d_model))
    m = cfg.moe
    xt = x.reshape(-1, cfg.d_model)
    logits = xt @ params["router"]
    pad_mask = jnp.arange(m.num_experts_padded) >= m.num_experts
    probs = jax.nn.softmax(
        jnp.where(pad_mask[None], -1e30, logits), axis=-1)
    _, idx = jax.lax.top_k(probs, m.top_k)
    assert (np.asarray(idx) < m.num_experts).all()
    out, _ = L.moe_apply(cfg, params, x)
    assert np.isfinite(np.asarray(out)).all()


def test_moe_capacity_drops_tokens():
    """With a tiny capacity factor, outputs shrink (dropped tokens produce
    zero contribution) but stay finite."""
    cfg, params = _moe_setup()
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 64, cfg.d_model))
    out_hi, _ = L.moe_apply(cfg, params, x, capacity_factor=4.0)
    out_lo, _ = L.moe_apply(cfg, params, x, capacity_factor=0.1)
    assert np.isfinite(np.asarray(out_lo)).all()
    assert float(jnp.abs(out_lo).mean()) < float(jnp.abs(out_hi).mean())


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def test_rope_preserves_norm():
    x = jax.random.normal(KEY, (2, 4, 16, 32))
    pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
    y = L.apply_rope(x, pos, 10_000.0)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(y, axis=-1)),
                               np.asarray(jnp.linalg.norm(x, axis=-1)),
                               rtol=1e-4, atol=1e-4)


def test_rope_relative_property():
    """<rope(q,m), rope(k,n)> depends only on m-n."""
    q = jax.random.normal(KEY, (1, 1, 1, 32))
    k = jax.random.normal(jax.random.PRNGKey(8), (1, 1, 1, 32))

    def dot_at(m, n):
        pos_q = jnp.full((1, 1), m)
        pos_k = jnp.full((1, 1), n)
        qr = L.apply_rope(q, pos_q, 10_000.0)
        kr = L.apply_rope(k, pos_k, 10_000.0)
        return float(jnp.sum(qr * kr))

    assert dot_at(5, 3) == pytest.approx(dot_at(12, 10), rel=1e-4)
    assert dot_at(0, 0) == pytest.approx(dot_at(9, 9), rel=1e-4)


def test_mrope_equals_rope_when_positions_equal():
    """With all three position streams equal, M-RoPE == RoPE."""
    cfg = get_config("qwen2-vl-2b")
    x = jax.random.normal(KEY, (2, 4, 8, cfg.head_dim))
    pos = jnp.broadcast_to(jnp.arange(8)[None], (2, 8))
    pos3 = jnp.broadcast_to(pos[None], (3, 2, 8))
    y_rope = L.apply_rope(x, pos, cfg.rope_theta)
    y_mrope = L.apply_mrope(x, pos3, cfg.rope_theta, cfg.mrope_sections)
    np.testing.assert_allclose(np.asarray(y_rope), np.asarray(y_mrope),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# IBN chunking equivalence (C3 at the XLA level)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mlp", ["gelu", "swiglu"])
def test_mlp_ibn_chunks_equivalent(mlp):
    cfg = dataclasses.replace(reduced(get_config("olmo-1b")), mlp=mlp)
    params = P.init_params(KEY, L.mlp_defs(cfg))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.d_model))
    base = L.mlp_apply(cfg, params, x, ibn_chunks=0)
    for n in (2, 4, 8):
        out = L.mlp_apply(cfg, params, x, ibn_chunks=n)
        np.testing.assert_allclose(np.asarray(base), np.asarray(out),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# EdgeNeXt
# ---------------------------------------------------------------------------


def test_edgenext_param_count_matches_published():
    n = P.count_params(edgenext.param_defs(EDGE_FULL))
    assert abs(n / 1e6 - 5.6) < 0.2, n          # paper: ~5.6M


@pytest.mark.parametrize("ibn_chunks", [0, 4])
def test_edgenext_full_config_traces(ibn_chunks):
    """The published EdgeNeXt-S widths trace end to end: stage 3's SDTA
    splits 160 channels unevenly (54 + 54 + 52), which the reduced
    config never exercises."""
    defs = edgenext.param_defs(EDGE_FULL)
    img = jax.ShapeDtypeStruct((1, EDGE_FULL.img_size, EDGE_FULL.img_size,
                                EDGE_FULL.in_channels), jnp.float32)
    out = jax.eval_shape(
        lambda p, x: edgenext.forward(EDGE_FULL, p, x,
                                      ibn_chunks=ibn_chunks),
        P.abstract_params(defs), img)
    assert out.shape == (1, EDGE_FULL.num_classes)


@pytest.mark.slow
def test_edgenext_forward_and_chunked_ibn():
    cfg = reduced_edgenext()
    params = P.init_params(KEY, edgenext.param_defs(cfg))
    img = jax.random.normal(jax.random.PRNGKey(1),
                            (2, cfg.img_size, cfg.img_size, 3))
    logits = edgenext.forward(cfg, params, img)
    assert logits.shape == (2, cfg.num_classes)
    assert np.isfinite(np.asarray(logits)).all()
    chunked = edgenext.forward(cfg, params, img, ibn_chunks=4)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(chunked),
                               rtol=2e-4, atol=2e-4)


def test_edgenext_matches_pallas_ibn_kernel():
    """The model's IBN block == the fused Pallas kernel (C3 both levels)."""
    from repro.kernels import ops
    cfg = reduced_edgenext()
    params = P.init_params(KEY, edgenext.param_defs(cfg))
    bp = params["stages"][0]["conv_blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(6), (64, cfg.dims[0]))
    want = edgenext._ibn_mlp(bp, x)
    # kernel omits the inner bias; fold it in as an extra input row
    got_full = ops.fused_ibn(
        jnp.concatenate([x, jnp.ones((64, 1), x.dtype)], -1),
        jnp.concatenate([bp["pw1_w"], bp["pw1_b"][None]], 0),
        bp["pw2_w"], activation="gelu", block_m=32, block_f=32) \
        + bp["pw2_b"]
    np.testing.assert_allclose(np.asarray(got_full), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
