"""A new configuration, traffic mix and metric family are new files and
new ``BENCHMARK.json`` entries; the harness finds them by name."""
import json
import shutil
import textwrap
from pathlib import Path

import pytest

import registry
import run

BENCH = Path(__file__).resolve().parents[1]

DUMMY_CONFIG = '''
import jax, jax.numpy as jnp, numpy as np
import registry, weights as W
from traffic import rng


def check_sizes(sizes):
    pass


class Model:
    ITEM = "images"

    def __init__(self, sizes, seed, root):
        self.sizes, self.root = sizes, root
        self.weights = W.make({"w": W.fan_in((sizes["width"],
                                              sizes["classes"]))}, seed)
        self.fwd = jax.jit(lambda w, x: x.astype(jnp.float32) @ w["w"])
        self.looked_up = 0

    def inputs(self, pool, batch, length, seed):
        shape = (pool, batch, self.sizes["width"])
        return rng(seed, 5).integers(0, 256, shape, dtype=np.uint8)

    def lookup(self, batch):
        self.looked_up += 1

    def put(self, x):
        return jax.device_put(x)

    def launch(self, xd):
        return self.fwd(self.weights, xd)

    def fetch(self, yd):
        return np.asarray(yd)

    def items(self, req):
        return req.batch

    def flops(self, req):
        return 2.0 * req.batch * self.sizes["width"] * self.sizes["classes"]

    def free(self):
        del self.fwd

    def reference(self, x, mode="exact"):
        ref = registry.reference_module(self.sizes["name"], self.root)
        return ref.logits(np.asarray(self.weights["w"]), x)
'''

DUMMY_REFERENCE = '''
import numpy as np


def logits(w, x):
    return x.astype(np.float64) @ w
'''


@pytest.fixture
def root(tmp_path):
    """A checkout's benchmark with one more config, mix and metric."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    b = tmp_path / "bench"
    (b / "configs" / "dummy-net.json").write_text(json.dumps({
        "name": "dummy-net", "width": 32, "classes": 10,
        "correct": {"number": "logit_rel_err", "limit": 1e-5,
                    "sample_answers": 8}}))
    (b / "configs" / "dummy-net.py").write_text(DUMMY_CONFIG)
    (b / "configs" / "dummy-net.reference.py").write_text(DUMMY_REFERENCE)
    (b / "traffic" / "trickle.json").write_text(json.dumps(
        {"kind": "poisson", "batch": 2, "pool": 4, "rate_per_s": 50.0}))
    # the two mixes queued next for EdgeNeXt-S, as data alone
    (b / "traffic" / "bursts.json").write_text(json.dumps(
        {"kind": "poisson", "batch": 1, "pool": 4, "rate_per_s": 40.0,
         "profile": [[0.1, 1.0], [0.2, 0.0]]}))
    (b / "traffic" / "mixed-batch.json").write_text(json.dumps(
        {"kind": "closed", "pool": 4,
         "batch": {"values": [1, 4, 16], "weights": [4, 2, 1]}}))
    (b / "metrics" / "answers_per_s.py").write_text(textwrap.dedent('''
        def read(run):
            return sum(r.req.batch for r in run.done) / run.seconds
    '''))
    (b / "metrics" / "lookups.py").write_text(textwrap.dedent('''
        def read(run):
            return run.model.looked_up
    '''))
    bench["configs"].append({"name": "dummy-net", "source": "none",
                             "file": "bench/configs/dummy-net.json",
                             "reduced": [], "why": "a test"})
    cells = []
    for mix in ("trickle", "bursts", "mixed-batch"):
        cells.append(f"dummy-net.{mix}")
        bench["workloads"].append({"name": cells[-1], "config": "dummy-net",
                                   "traffic": mix, "chips": 1,
                                   "why": "a test"})
    bench["end_to_end"].append({"name": "answers_per_s", "unit": "1/s",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock", "workloads": cells})
    bench["per_layer"].append({"name": "lookups.trickle", "unit": "1",
                               "better": "lower", "source": "host_clock",
                               "layer": "serve store",
                               "moves": "answers_per_s",
                               "workloads": ["dummy-net.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_new_files_and_entries_make_a_new_cell(root, capsys):
    res = run.main(["--workload", "dummy-net.trickle", "--seed",
                    "4000000001", "--seconds", "0.4"],
                   require_tpu=False, root=root)
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == res
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 20                  # 50/s for 0.4 s
    assert set(res["metrics"]) == {"answers_per_s", "setup_s"}
    assert res["metrics"]["answers_per_s"]["unit"] == "1/s"
    assert 0 < res["metrics"]["answers_per_s"]["value"] <= 2 * 50.0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("mix", ["bursts", "mixed-batch"])
def test_new_mix_files_make_new_cells(root, mix, capsys):
    """A bursty open loop and a closed loop of mixed batch sizes need
    only their data files: the generator reads them, and the run warms
    and serves every shape they send."""
    res = run.main(["--workload", f"dummy-net.{mix}", "--seed",
                    "4000000003", "--seconds", "0.6"],
                   require_tpu=False, root=root)
    out = capsys.readouterr().out
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"answers_per_s", "setup_s"}
    if mix == "bursts":
        assert res["attempted"] == 24              # 40/s for 0.6 s
    else:
        for b in (1, 4, 16):
            assert f"warm request 0 of batch {b}," in out
        assert "compilations in the window(s): 0" in out


def test_per_layer_entries_are_found_by_family(root):
    bench = registry.benchmark(root)
    names = [m["name"] for m in registry.metrics(bench, "dummy-net.trickle",
                                                 traced=True)]
    assert names == ["lookups.trickle"]
    reader = registry.reader("lookups.trickle", root)

    class FakeRun:
        model = type("M", (), {"looked_up": 7})
    assert reader.read(FakeRun) == 7


def test_metrics_without_a_workloads_key_follow_what_they_move():
    bench = {"end_to_end": [{"name": "setup_s"},
                            {"name": "qps", "workloads": ["a"]}],
             "per_layer": [{"name": "x.a", "moves": "qps"},
                           {"name": "y.b", "moves": "qps",
                            "workloads": ["b"]}]}
    assert [m["name"] for m in registry.metrics(bench, "a", False)] == [
        "setup_s", "qps"]
    assert [m["name"] for m in registry.metrics(bench, "a", True)] == ["x.a"]
    assert [m["name"] for m in registry.metrics(bench, "b", True)] == ["y.b"]


def test_traced_run_reads_host_metrics_from_the_untraced_window(
        root, monkeypatch, capsys):
    """With --trace 1 a traced window follows the measured one: metrics
    read from the device trace come from it, all others from the
    untraced window, which the profiler never slows."""
    bench = registry.benchmark(root)
    for name, source in (("window_s.host", "host_clock"),
                         ("window_s.device", "device_trace")):
        bench["per_layer"].append({
            "name": name, "unit": "s", "better": "lower", "source": source,
            "layer": "test", "moves": "answers_per_s",
            "workloads": ["dummy-net.trickle"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "metrics" / "window_s.py").write_text(
        "def read(run):\n    return run.seconds\n")
    fake = {"busy_s": 0.1, "window_s": 0.2, "idle_share": 0.5, "ops": 3,
            "top_ops": [["op", 0.1]], "idle_gaps": [["wait", 0.1]]}
    monkeypatch.setattr(run.trace_lib, "find_xplane", lambda d: d)
    monkeypatch.setattr(run.trace_lib, "load", lambda *a: None)
    monkeypatch.setattr(run.trace_lib, "reduce", lambda t: fake)
    monkeypatch.setattr(run, "TRACE_S", 0.2)
    res = run.main(["--workload", "dummy-net.trickle", "--seed", "5",
                    "--seconds", "0.4", "--trace", "1"],
                   require_tpu=False, root=root)
    out = capsys.readouterr().out
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["window_s.host"] == 0.4 and m["window_s.device"] == 0.2
    assert res["attempted"] == 20 + 10            # both windows at 50/s
    assert res["device"]["busy_s"] == 0.1
    assert res["breakdown"]["idle_gaps"] == [["wait", 0.1]]
    assert "traced/untraced requests per second:" in out
    assert res["correct"] is True
