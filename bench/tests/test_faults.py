"""Whole runs on the CPU at small sizes, with the chip check skipped:
sound runs come out correct, and each fault planted in the timed path
makes ``correct`` false."""
import json
import shutil
from pathlib import Path

import jax.numpy as jnp
import pytest

import run

BENCH = Path(__file__).resolve().parents[1]
SEED = "3000000019"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the checkout's benchmark, so that the serving store's
    cache goes to a temporary directory."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__",
                                                  "testdata"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", root)
    return root


def sizes(name: str, **small) -> dict:
    s = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    s.update(small)
    return s


# the published widths are cut for the CPU; the limits are the cells'
EDGE = dict(img_size=64, num_classes=100, depths=[1, 1, 2, 1],
            dims=[48, 96, 160, 304], kernel_sizes=[3, 5, 7, 9])
RWKV = dict(num_layers=2, d_model=256, num_heads=4, num_kv_heads=4,
            d_ff=896, vocab_size=1000)


def go(root, cell, small, patch=None, seconds=0.5):
    return run.main(["--workload", cell, "--seed", SEED, "--seconds",
                     str(seconds)], require_tpu=False, sizes=small,
                    root=root, patch=patch)


def altered(model):
    """Every answer altered where it is produced: classes shifted by one."""
    launch = model.launch
    model.launch = lambda xd: jnp.roll(launch(xd), 1, axis=-1)


def half_batch(model):
    """Half of the batch left out: its answers copy the other half's."""
    launch = model.launch

    def half(xd):
        y = launch(xd[: len(xd) // 2])
        return jnp.concatenate([y, y])
    model.launch = half


def lost(model):
    """Every seventh request never comes back."""
    launch, n = model.launch, [0]

    def flaky(xd):
        n[0] += 1
        if n[0] % 7 == 0:
            raise RuntimeError("lost request")
        return launch(xd)
    model.launch = flaky


@pytest.mark.parametrize("cell", ["edgenext-s.b64", "edgenext-s.b1-poisson"])
def test_edgenext_sound_run_is_correct(root, cell):
    res = go(root, cell, sizes("edgenext-s", **EDGE))
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("cell,fault", [
    ("edgenext-s.b64", altered), ("edgenext-s.b64", half_batch),
    ("edgenext-s.b1-poisson", altered), ("edgenext-s.b1-poisson", lost)])
def test_edgenext_fault_is_not_correct(root, cell, fault):
    res = go(root, cell, sizes("edgenext-s", **EDGE), patch=fault)
    assert res["correct"] is False


def test_rwkv_sound_run_is_correct(root):
    res = go(root, "rwkv6.prefill512", sizes("rwkv6-1.6b", **RWKV))
    assert res["correct"] is True, res["checks"]


def test_rwkv_answer_altered_is_not_correct(root):
    res = go(root, "rwkv6.prefill512", sizes("rwkv6-1.6b", **RWKV),
             patch=altered)
    assert res["correct"] is False


def test_rwkv_state_left_unchanged_is_not_correct(root, monkeypatch):
    """The WKV step returns its state unchanged: every chunk starts from
    the initial state, so no token sees the ones before its chunk."""
    from repro.models import rwkv6
    chunked = rwkv6.wkv_chunked

    def frozen(r, k, v, logw, u, state, chunk):
        outs = [chunked(r[:, s:s + chunk], k[:, s:s + chunk],
                        v[:, s:s + chunk], logw[:, s:s + chunk], u, state,
                        chunk)[0] for s in range(0, r.shape[1], chunk)]
        return jnp.concatenate(outs, axis=1), state
    monkeypatch.setattr(rwkv6, "wkv_chunked", frozen)
    res = go(root, "rwkv6.prefill512", sizes("rwkv6-1.6b", **RWKV))
    assert res["correct"] is False
