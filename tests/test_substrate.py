"""Substrate tests: checkpoint store, data pipeline, optimizer,
compression, watchdog, sharding rules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (AsyncCheckpointer, latest_step,
                              load_checkpoint, save_checkpoint)
from repro.configs import ARCHS, get_config
from repro.data.synthetic import SyntheticLMDataset
from repro.models import get_module, params as param_lib
from repro.optim import (adamw_init, adamw_update, clip_by_global_norm,
                         global_norm, warmup_cosine)
from repro.optim.compression import (dequantize_int8, quantize_int8,
                                     quantize_with_feedback)
from repro.runtime.watchdog import StragglerWatchdog


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------


def _tree(key):
    ks = jax.random.split(key, 3)
    return {"a": jax.random.normal(ks[0], (4, 8)),
            "nested": {"b": jax.random.normal(ks[1], (3,)),
                       "c": [jnp.ones((2, 2)), jnp.zeros((5,))]},
            "count": jnp.asarray(7, jnp.int32)}


def test_checkpoint_roundtrip(tmp_path, key):
    tree = _tree(key)
    save_checkpoint(tmp_path, 3, tree)
    step, restored = load_checkpoint(tmp_path, like=tree)
    assert step == 3
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_latest_and_gc(tmp_path, key):
    tree = _tree(key)
    ck = AsyncCheckpointer(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, tree)
    ck.wait()
    assert latest_step(tmp_path) == 4
    kept = sorted(p.name for p in tmp_path.iterdir())
    assert kept == ["step_00000003", "step_00000004"]


def test_checkpoint_atomicity(tmp_path, key):
    """A leftover .tmp dir must never shadow a committed checkpoint."""
    tree = _tree(key)
    save_checkpoint(tmp_path, 1, tree)
    (tmp_path / "step_00000002.tmp").mkdir()      # simulated crash
    assert latest_step(tmp_path) == 1
    step, _ = load_checkpoint(tmp_path, like=tree)
    assert step == 1


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def test_data_deterministic_resume():
    ds = SyntheticLMDataset(vocab_size=128, seq_len=16, global_batch=4)
    b1 = ds.batch(10)
    ds2 = SyntheticLMDataset(vocab_size=128, seq_len=16, global_batch=4)
    b2 = ds2.batch(10)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])


def test_data_labels_are_next_tokens():
    ds = SyntheticLMDataset(vocab_size=128, seq_len=16, global_batch=4,
                            noise=0.0)
    b = ds.batch(0)
    # bigram language: label = perm[token] everywhere when noise=0
    np.testing.assert_array_equal(b["labels"], ds.perm[b["tokens"]])


def test_data_process_sharding_differs():
    kw = dict(vocab_size=128, seq_len=16, global_batch=8, process_count=2)
    d0 = SyntheticLMDataset(process_index=0, **kw)
    d1 = SyntheticLMDataset(process_index=1, **kw)
    assert d0.local_batch == 4
    assert not np.array_equal(d0.batch(0)["tokens"], d1.batch(0)["tokens"])


def test_data_steps_differ():
    ds = SyntheticLMDataset(vocab_size=128, seq_len=16, global_batch=4)
    assert not np.array_equal(ds.batch(0)["tokens"], ds.batch(1)["tokens"])


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_descends_quadratic(key):
    target = jax.random.normal(key, (8,))
    params = {"w": jnp.zeros((8,))}
    state = adamw_init(params)

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2)

    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state = adamw_update(g, state, params, lr=0.05,
                                     weight_decay=0.0)
    assert float(loss(params)) < 1e-2


def test_clip_by_global_norm(key):
    g = {"a": jax.random.normal(key, (32,)) * 100}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) > 1.0
    assert float(global_norm(clipped)) <= 1.0 + 1e-5


def test_warmup_cosine_shape():
    fn = warmup_cosine(1e-3, 10, 100)
    lrs = [float(fn(s)) for s in range(100)]
    assert lrs[0] > 0                       # no wasted step-0
    assert abs(max(lrs) - 1e-3) < 1e-9
    assert np.argmax(lrs) == 9              # peak at end of warmup
    assert lrs[-1] < 0.2 * 1e-3             # decayed


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------


def test_quantize_roundtrip_error_bound(key):
    x = jax.random.normal(key, (1024,)) * 3
    q, scale = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, scale) - x)
    assert float(err.max()) <= float(scale) * 0.5 + 1e-6


def test_error_feedback_unbiased(key):
    """With feedback, the accumulated dequantized sum tracks the true sum
    (compression error does not accumulate)."""
    xs = jax.random.normal(key, (50, 256))
    residual = jnp.zeros((256,))
    acc = jnp.zeros((256,))
    for i in range(50):
        q, scale, residual = quantize_with_feedback(xs[i], residual)
        acc = acc + dequantize_int8(q, scale)
    true = xs.sum(0)
    # residual bounds the total error
    np.testing.assert_allclose(np.asarray(acc + residual), np.asarray(true),
                               rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(acc - true).max()) < 0.2


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------


def test_watchdog_flags_outliers():
    events = []
    wd = StragglerWatchdog(threshold=2.0, patience=2, warmup_steps=0,
                           on_escalate=events.append)
    wd.record(0, 1.0)
    for s in range(1, 5):
        assert not wd.record(s, 1.0)
    assert wd.record(5, 5.0)
    assert wd.record(6, 5.0)
    assert events                       # escalated after patience=2


def test_watchdog_ignores_warmup():
    wd = StragglerWatchdog(warmup_steps=2, threshold=2.0)
    assert not wd.record(0, 100.0)      # compile step
    assert not wd.record(1, 100.0)


# ---------------------------------------------------------------------------
# sharding rules: every assigned arch divides the production mesh
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_param_pspecs_divisible_on_production_mesh(arch):
    cfg = get_config(arch)
    defs = get_module(cfg).param_defs(cfg)
    sizes = {"data": 16, "model": 16}
    rules = param_lib.resolve_rules(sizes, kv_heads=cfg.num_kv_heads,
                                    num_heads=cfg.num_heads)

    def check(d: param_lib.ParamDef):
        spec = param_lib._leaf_pspec(d, rules)
        for dim, ax in zip(d.shape, spec):
            if ax is not None and dim % sizes[ax] != 0:
                rules[[a for a in d.axes][list(spec).index(ax)]] = None

    # demote-then-validate mirrors runtime.model_param_pspecs
    param_lib.tree_map_defs(check, defs)
    param_lib.validate_pspecs(defs, rules, sizes)
