"""Granite 4.0-H (``repro.models.mamba_hybrid``) against the benchmark's
plain float32 reference (``bench/configs/granite-4.0-h-micro.reference
.py``, which imports nothing of the program), on seeded weights at a
small size on the CPU: d_model 64, 4 SSD heads of 32, d_state 16, chunk
8, one period of 10 layers with one attention layer."""
import functools
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.granite_4_0_h_micro import CONFIG
from repro.models import mamba_hybrid
from repro.models.params import ParamDef
from repro.obs.layers import op_layers
from repro.search import layer_scopes

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.append(str(BENCH))          # the config's own imports
import registry  # noqa: E402
import weights as W  # noqa: E402

SIZES = json.loads((BENCH / "configs" / "granite-4.0-h-micro.json")
                   .read_text())
BENCH_CFG = registry.config_module("granite-4.0-h-micro")
REF = registry.reference_module("granite-4.0-h-micro")

PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def small_sizes(dtype="float32", layers=PERIOD):
    s = dict(SIZES)
    s.update(num_layers=len(layers), num_hidden_layers=len(layers),
             layer_types=list(layers), d_model=64, hidden_size=64,
             num_heads=4, num_attention_heads=4, num_kv_heads=2,
             num_key_value_heads=2, head_dim=16, d_ff=128,
             intermediate_size=128, vocab_size=512, mamba_n_heads=4,
             mamba_d_head=32, mamba_d_state=16, mamba_chunk_size=8,
             dtype=dtype, param_dtype="float32")
    return s


def model(sizes, seed=3):
    cfg = BENCH_CFG.program_config(sizes)
    return cfg, W.make(BENCH_CFG.weight_spec(sizes), seed, "float32")


def rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want,
                                                                axis=-1)


@pytest.fixture(scope="module")
def f32_model():
    """The small model in f32, 20 prompt tokens, and its forward's
    normed hidden states at every position (one compile for the tests
    below)."""
    sizes = small_sizes()
    cfg, w = model(sizes)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 20), 0,
                                sizes["vocab_size"])
    hidden, _ = jax.jit(functools.partial(mamba_hybrid.forward, cfg))(
        w, {"tokens": tokens})
    return sizes, cfg, w, tokens, hidden


def test_forward_logits_match_the_reference(f32_model):
    """f32 compute: the program and the reference differ only in the
    order of their f32 sums (chunked against sequential SSD, blockwise
    against whole softmax), ~1e-7 relative here."""
    sizes, cfg, w, tokens, hidden = f32_model
    got = mamba_hybrid.logits_fn(cfg, w, hidden[:, -1:])[:, 0]
    want = REF.logits(sizes, w, np.asarray(tokens))
    assert got.shape == want.shape == (2, sizes["vocab_size"])
    assert rel_err(got, want).max() < 1e-5


def test_bf16_forward_is_within_the_cell_s_rounding():
    """bf16 compute, as the chip cell runs: every matmul operand and the
    residual stream rounded to 8 bits (2^-9 relative each), about 1e-2
    through ten layers (0.011 here); the served call itself."""
    sizes = small_sizes("bfloat16")
    cfg, w = model(sizes)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (2, 21),
                                           0, sizes["vocab_size"]))
    got = jax.jit(functools.partial(BENCH_CFG.served, cfg))(w, tokens)
    assert rel_err(got, REF.logits(sizes, w, tokens)).max() < 5e-2


def _ssd_inputs(T, decay, key=0, b=2, H=3, P=4, N=5):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    x = jax.random.normal(ks[0], (b, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, T, H)))
    A = -decay * jnp.exp(0.2 * jax.random.normal(ks[2], (H,))) \
        / jnp.mean(dt)
    B = jax.random.normal(ks[3], (b, T, N))
    C = jax.random.normal(ks[4], (b, T, N))
    return x, dt, A, B, C


@jax.jit
def _sequential(x, dt, A, B, C, state):
    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        y, S = mamba_hybrid.ssd_step(x_t, dt_t, A, B_t, C_t, S)
        return S, y
    S, y = jax.lax.scan(step, state, tuple(
        a.swapaxes(0, 1) for a in (x, dt, B, C)))
    return y.swapaxes(0, 1), S


@pytest.mark.parametrize("T,chunk,decay", [
    (37, 8, 5.0),          # ragged final chunk, strong decay (dt A ~ -5)
    (32, 8, 0.05),         # whole chunks, weak decay: the carry matters
    (5, 8, 1.0)])          # shorter than one chunk
def test_chunked_ssd_matches_the_recurrence(T, chunk, decay):
    chunked = jax.jit(mamba_hybrid.ssd_chunked, static_argnums=5)
    x, dt, A, B, C = _ssd_inputs(T, decay)
    y, S = chunked(x, dt, A, B, C, chunk)
    want = jax.jit(REF.ssd)(x, dt, A, B, C)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)
    # from a state entering midway, as prefill hands to decode
    h = T // 2
    _, S_h = chunked(x[:, :h], dt[:, :h], A, B[:, :h], C[:, :h], chunk)
    y2, S2 = chunked(x[:, h:], dt[:, h:], A, B[:, h:], C[:, h:], chunk, S_h)
    y3, S3 = _sequential(x[:, h:], dt[:, h:], A, B[:, h:], C[:, h:], S_h)
    for y_ in (y2, y3):
        np.testing.assert_allclose(y_, want[:, h:], rtol=1e-4, atol=1e-4)
    for s in (S2, S3):
        np.testing.assert_allclose(s, S, rtol=1e-4, atol=1e-4)


def test_prefill_then_decode_matches_the_forward(f32_model):
    _, cfg, w, tokens, hidden = f32_model
    T, pre = tokens.shape[1], 12
    full = mamba_hybrid.logits_fn(cfg, w, hidden)
    prefill = functools.partial(mamba_hybrid.prefill, cfg, decode_len=T)
    last, cache = jax.jit(prefill)(w, {"tokens": tokens[:, :pre]})
    got = [mamba_hybrid.logits_fn(cfg, w, last[:, None])[:, 0]]
    step = jax.jit(functools.partial(mamba_hybrid.decode_step, cfg))
    for t in range(pre, T):
        logits, cache = step(w, cache, {"tokens": tokens[:, t:t + 1]})
        got.append(logits)
    assert int(cache.step) == T
    # the same f32 sums in another order (one token at a time)
    np.testing.assert_allclose(np.stack(got, 1), full[:, pre - 1:],
                               rtol=1e-4, atol=1e-4)


def test_bench_config_is_the_program_s():
    """The benchmark's sizes are the registered configuration at its
    published widths, and its weight tree is the program's parameter
    layout; shapes only, nothing allocated."""
    BENCH_CFG.check_sizes(SIZES)
    assert BENCH_CFG.program_config(SIZES) == CONFIG
    want = jax.tree.map(lambda d: d.shape, mamba_hybrid.param_defs(CONFIG),
                        is_leaf=lambda d: isinstance(d, ParamDef))
    assert W.shapes(BENCH_CFG.weight_spec(SIZES)) == want
    n = sum(math.prod(s) for s in jax.tree.leaves(
        want, is_leaf=lambda s: isinstance(s, tuple)))
    assert 3.18e9 < n < 3.20e9                  # 3.19 B parameters
    # 6.25 GFLOP a token at 8192 tokens (36 Mamba-2 and 4 attention
    # layers), 0.41 GFLOP for the logits
    assert 6.2e9 < BENCH_CFG.flops_prompt(SIZES, 8192) / 8192 < 6.3e9
    for key, value in (("mamba_d_state", 64), ("hidden_size", 1024),
                       ("num_layers", 36)):
        with pytest.raises(SystemExit):
            BENCH_CFG.check_sizes({**SIZES, key: value})


def test_layer_scopes_attribute_the_compiled_program():
    sizes = small_sizes(layers=PERIOD[:6])
    cfg = BENCH_CFG.program_config(sizes)
    params = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, jnp.float32),
        BENCH_CFG.weight_spec(sizes), is_leaf=lambda x: isinstance(x, W.Leaf))
    tokens = jax.ShapeDtypeStruct((1, 24), jnp.int32)
    hlo = jax.jit(functools.partial(BENCH_CFG.served, cfg)).lower(
        params, tokens).compile().as_text()
    classes = layer_scopes("granite-h-micro")
    found = set(op_layers(hlo, classes).values())
    assert {"mamba.ssd", "mamba.conv", "attn.qk", "mamba.in_proj",
            "mlp.up", "embed", "head.logits"} <= found
    assert not any(n.startswith("blk") for n in found)
    assert classes["mamba.ssd"] == ("scan", None)
    assert classes["mamba.conv"] == ("dwconv", None)
    assert classes["mlp.down"] == ("pwconv", "project")
