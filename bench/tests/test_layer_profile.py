"""The per-layer reduction (``layer_profile.py``): layer self times plus
the unattributed time add up to the device's busy time, a layer class
that ran no op reads None, the reduction holds on a small scoped trace
recorded on a TPU v5e chip (``bench/testdata/record_scoped.py``), and a
traced CPU run reads the program's ``serve.request`` span."""
import functools
import json
import shutil
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

import layer_profile as LP
import run
import trace as T
from repro.obs.layers import op_layers

BENCH = Path(__file__).resolve().parents[1]
TESTDATA = BENCH / "testdata"
PHASES = ("dispatch", "wait", "idle-for-arrival")
DEV = "/device:TPU:0"


def _op(name, s, e):
    return (f"%{name} = f32[8]{{0}} {name.split('.')[0]}(f32[8] %x)", s, e)


# a window of 100 ns: a loop (its body's two ops nested inside it), a
# layout copy in no layer, and an op half outside the window
SYNTHETIC = T.Trace(
    {DEV: [_op("while.1", 0, 40), _op("fusion.2", 5, 20),
           _op("convolution.3", 20, 35), _op("copy.4", 50, 60),
           _op("fusion.5", 90, 110)]},
    [("window", 0, 100)])
TABLE = {"while.1": "tmix.wkv", "fusion.2": "tmix.wkv",
         "convolution.3": "tmix.rkvg", "fusion.5": "head.ln"}


def test_layer_times_and_unattributed_add_up_to_busy():
    got = LP.layer_times(SYNTHETIC, TABLE)
    assert got["layer_s"] == pytest.approx(
        {"tmix.wkv": 25e-9, "tmix.rkvg": 15e-9, "head.ln": 10e-9})
    assert got["unattributed_s"] == pytest.approx(10e-9)
    assert got["unattributed_ops"] == [["copy f32[8]", pytest.approx(1e-8)]]
    busy = T.reduce(SYNTHETIC)["busy_s"]
    assert sum(got["layer_s"].values()) + got["unattributed_s"] == \
        pytest.approx(busy)


def _run_with(layer_s, classes, requests=5):
    r = SimpleNamespace()
    r.layer_profile = {"requests": requests, "span_ns": [], "classes": classes,
                       "layer_s": layer_s}
    return r


def test_a_class_that_ran_no_op_reads_none():
    classes = {"s0.conv0.pw1": ("pwconv", "expand"),
               "s0.conv0.dw": ("dwconv", None)}
    r = _run_with({"s0.conv0.pw1": 0.01}, classes)
    assert LP.class_ms(r, lambda n, op, role: role is not None) == \
        pytest.approx(2.0)
    assert LP.class_ms(r, lambda n, op, role: op == "dwconv") is None
    assert LP.class_ms(_run_with(None, {}), lambda *a: True) is None
    assert LP.span_median_us(r) is None


@pytest.fixture(scope="module")
def scoped():
    hlo = (TESTDATA / "scoped.hlo.txt").read_text()
    return (T.load(TESTDATA / "scoped.xplane.pb", PHASES),
            op_layers(hlo, ("pw1", "act", "ln", "head.fc")))


def test_scoped_chip_trace_splits_busy_time_by_layer(scoped):
    trace, table = scoped
    got = LP.layer_times(trace, table)
    busy = T.reduce(trace)["busy_s"]
    total = sum(got["layer_s"].values()) + got["unattributed_s"]
    assert total == pytest.approx(busy, rel=0.01)
    # the matmuls dominate; the weight cast XLA hoists out of the scan
    # counts to pw1, the layer that uses the weight
    assert {"pw1", "ln", "head.fc"} <= set(got["layer_s"])
    assert max(got["layer_s"], key=got["layer_s"].get) == "pw1"
    ops = {LP.instruction(n) for n, _, _ in trace.ops[DEV]}
    assert "convert" in ops and table["convert"] == "pw1"
    assert got["unattributed_s"] < 0.2 * busy


@pytest.fixture
def cache_everything():
    """The compile cache keeps even the smallest program, as a run's
    set-up has it keep the served one."""
    keys = ("jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = [getattr(jax.config, k) for k in keys]
    for k in keys:
        jax.config.update(k, 0)
    yield
    for k, v in zip(keys, was):
        jax.config.update(k, v)


def test_hlo_text_names_scopes_the_cached_executable_lost(cache_everything):
    """The compile cache keys a program without its metadata: a scoped
    program compiled after its unscoped twin runs the twin's executable,
    whose text names no layer.  ``hlo_text`` still gives the scopes."""
    def plain(w, x):
        return jnp.tanh(x @ w) * 3.0

    def scoped(w, x):
        with jax.named_scope("pw1"):
            return jnp.tanh(x @ w) * 3.0

    w = jnp.full((128, 128), 0.5)
    x = jnp.ones((8, 128))
    jax.jit(functools.partial(plain))(w, x).block_until_ready()
    model = SimpleNamespace(fwd=jax.jit(functools.partial(scoped)),
                            weights=w)
    model.fwd(w, x).block_until_ready()
    assert "/pw1/" not in model.fwd.lower(w, x).compile().as_text()
    assert "/pw1/" in LP.hlo_text(model, x)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__",
                                                  "testdata"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", root)
    return root


def _traced_b1(root, monkeypatch):
    """A traced b1 run on the CPU at a small size: the CPU trace has no
    device plane, so the existing reduction is given a stand-in."""
    fake = {"busy_s": 0.1, "window_s": 0.2, "idle_share": 0.5, "ops": 3,
            "top_ops": [["op", 0.1]], "idle_gaps": [["wait", 0.1]]}
    monkeypatch.setattr(run.trace_lib, "reduce", lambda t: fake)
    monkeypatch.setattr(run, "TRACE_S", 0.3)
    sizes = json.loads((BENCH / "configs" / "edgenext-s.json").read_text())
    sizes.update(img_size=64, num_classes=100, depths=[1, 1, 2, 1])
    return run.main(["--workload", "edgenext-s.b1-poisson", "--seed",
                     "3000000019", "--seconds", "0.3", "--trace", "1"],
                    require_tpu=False, sizes=sizes, root=root)


def test_traced_run_reads_the_programs_request_span(root, monkeypatch,
                                                    capsys):
    res = _traced_b1(root, monkeypatch)
    out = capsys.readouterr().out
    assert res["correct"] is True
    assert res["metrics"]["store_us.edge"]["value"] > 0
    assert "layer profile: " in out and "serve.request spans" in out
    assert json.loads(out.strip().splitlines()[-1]) == res


def test_program_without_the_spans_leaves_the_new_metrics_out(
        root, monkeypatch):
    """Over a program that lacks what the readers read (an earlier
    commit's), the run goes on and the metrics are left out."""
    monkeypatch.setitem(__import__("sys").modules, "repro.obs.layers", None)
    res = _traced_b1(root, monkeypatch)
    assert res["correct"] is True
    assert "store_us.edge" not in res["metrics"]
    assert "idle_share.edge" in res["metrics"]
