"""Per-assigned-architecture smoke tests (reduced same-family configs).

For each of the 10 archs: one train step on CPU asserting output shapes +
finite loss, and a prefill -> decode round trip.  The FULL configs are
exercised only as ShapeDtypeStruct specs (no allocation).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES_BY_NAME, get_config, reduced
from repro.launch.specs import input_specs
from repro.models import get_module, params as P
from repro.optim import adamw_init, warmup_cosine
from repro.runtime import (build_decode_step, build_prefill_step,
                           build_train_step)

ARCH_IDS = sorted(ARCHS)
# the costliest reduced configs (recurrent scans / MoE dispatch / long
# encoder-decoder compiles) run only in the slow lane; the cheap archs
# keep per-family train coverage in the default run
_HEAVY = {"recurrentgemma-2b", "seamless-m4t-large-v2", "rwkv6-1.6b",
          "granite-4.0-h-micro",
          "h2o-danube-1.8b", "qwen2-moe-a2.7b", "qwen3-moe-30b-a3b"}
TRAIN_ARCH_IDS = [
    pytest.param(a, marks=pytest.mark.slow) if a in _HEAVY else a
    for a in ARCH_IDS]


def _random_batch(cfg, struct, key, seq):
    batch = {}
    for k, s in struct.items():
        if s.dtype == jnp.int32:
            batch[k] = jax.random.randint(key, s.shape, 0, cfg.vocab_size)
        else:
            batch[k] = jax.random.normal(key, s.shape).astype(jnp.float32)
    if "positions" in batch:
        batch["positions"] = jnp.abs(batch["positions"]) % seq
    return batch


@pytest.mark.parametrize("arch", TRAIN_ARCH_IDS)
def test_train_step_smoke(arch):
    cfg = reduced(get_config(arch))
    mod = get_module(cfg)
    shape = dataclasses.replace(SHAPES_BY_NAME["train_4k"], seq_len=32,
                                global_batch=2)
    params = P.init_params(jax.random.PRNGKey(0), mod.param_defs(cfg))
    batch = _random_batch(cfg, input_specs(cfg, shape),
                          jax.random.PRNGKey(7), 32)
    step = build_train_step(cfg, lr_schedule=warmup_cosine(3e-4, 5, 20))
    opt = adamw_init(params)
    p2, opt2, metrics = jax.jit(step)(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed (clip+warmup make deltas small but nonzero)
    l0 = jax.tree.leaves(params)[0]
    l1 = jax.tree.leaves(p2)[0]
    assert not np.array_equal(np.asarray(l0), np.asarray(l1))
    assert int(opt2.count) == 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_smoke(arch):
    cfg = reduced(get_config(arch))
    shape = dataclasses.replace(SHAPES_BY_NAME["prefill_32k"], seq_len=32,
                                global_batch=2)
    mod = get_module(cfg)
    params = P.init_params(jax.random.PRNGKey(0), mod.param_defs(cfg))
    batch = _random_batch(cfg, input_specs(cfg, shape),
                          jax.random.PRNGKey(3), 32)
    prefill = build_prefill_step(cfg, decode_len=40)
    decode = build_decode_step(cfg)
    last, cache = jax.jit(prefill)(params, batch)
    assert last.shape == (2, cfg.d_model)
    tok = jnp.zeros((2, 1), jnp.int32)
    for _ in range(3):
        tok1, logits, cache = jax.jit(decode)(params, cache,
                                              {"tokens": tok})
        tok = tok1[:, None]
    assert logits.shape == (2, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits[:, :cfg.vocab_size])).all()
    assert (np.asarray(tok1) < cfg.vocab_size).all()


@pytest.mark.parametrize(
    "arch", [a for a in ARCH_IDS if ARCHS[a].block_pattern])
def test_reduced_pattern_keeps_every_layer_kind(arch):
    """The smoke variant builds a layer of every kind the pattern has
    (Granite's first attention layer is its sixth), and RecurrentGemma's
    stays its first period."""
    cfg = get_config(arch)
    small = reduced(cfg)
    assert set(small.block_pattern) == set(cfg.block_pattern)
    assert small.block_pattern == cfg.block_pattern[:small.num_layers]
    if arch == "recurrentgemma-2b":
        assert small.block_pattern == ("recurrent", "recurrent",
                                       "attention")


def test_full_configs_validate():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        cfg.validate()
        # published dims survive the registry round trip
        assert cfg.name == arch


@pytest.mark.slow
def test_decode_matches_forward_dense():
    """Stepwise decode logits == teacher-forced forward logits (olmo)."""
    cfg = reduced(get_config("olmo-1b"))
    mod = get_module(cfg)
    params = P.init_params(jax.random.PRNGKey(0), mod.param_defs(cfg))
    T = 16
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, T), 0,
                                cfg.vocab_size)
    hidden, _ = mod.forward(cfg, params, {"tokens": tokens}, remat=False,
                            use_flash=False)
    full_logits = mod.logits_fn(cfg, params, hidden)        # [1,T,V]

    prefix = T // 2
    last, cache = mod.prefill(cfg, params, {"tokens": tokens[:, :prefix]},
                              use_flash=False)
    # grow the cache to T
    pad = T - cache.k.shape[3]
    cache = cache._replace(
        k=jnp.pad(cache.k, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))),
        v=jnp.pad(cache.v, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))))
    for t in range(prefix, T):
        logits, cache = mod.decode_step(cfg, params,
                                        cache, {"tokens": tokens[:, t:t+1]})
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(full_logits[0, t]),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_decode_matches_forward_rwkv():
    """RWKV: chunked train path == recurrent decode path."""
    cfg = reduced(get_config("rwkv6-1.6b"))
    mod = get_module(cfg)
    params = P.init_params(jax.random.PRNGKey(0), mod.param_defs(cfg))
    T = 12
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, T), 0,
                                cfg.vocab_size)
    hidden, _ = mod.forward(cfg, params, {"tokens": tokens}, remat=False)
    full_logits = mod.logits_fn(cfg, params, hidden)

    prefix = 6
    _, cache = mod.prefill(cfg, params, {"tokens": tokens[:, :prefix]})
    for t in range(prefix, T):
        logits, cache = mod.decode_step(cfg, params, cache,
                                        {"tokens": tokens[:, t:t+1]})
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(full_logits[0, t]),
                                   rtol=2e-3, atol=2e-3)
