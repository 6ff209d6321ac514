"""Find the benchmark's pieces by the names in ``BENCHMARK.json``.

Paths are under the checkout's root:

    configuration  bench/configs/<config>.json   sizes, as run
                   bench/configs/<config>.py     weights, served call,
                                                 FLOPs, inputs
                   bench/configs/<config>.reference.py   plain reference
    traffic mix    bench/traffic/<traffic>.json  (read by traffic.py)
    metric         bench/metrics/<family>.py     ``read(run)``; the family
                   is the metric's name up to its first ``.``

Adding a configuration, a mix or a metric family adds files and
entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def load_file(path):
    """Import a Python file by its path (names may hold '-' and '.')."""
    path = Path(path).resolve()
    name = "bench_" + "".join(c if c.isalnum() else "_"
                              for c in f"{path.parent.name}_{path.stem}")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"bench: no config {name!r} in BENCHMARK.json")


def metrics(bench: dict, cell_name: str, traced: bool) -> list:
    """The cell's end-to-end metrics, or with ``traced`` its per-layer
    ones: those that list the cell under ``workloads``, or that have no
    such key and move one of the cell's end-to-end metrics."""
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", ())
            or "workloads" not in m and m["moves"] in moved]


def config_module(name: str, root: Path = ROOT):
    return load_file(root / "bench" / "configs" / f"{name}.py")


def reference_module(name: str, root: Path = ROOT):
    return load_file(root / "bench" / "configs" / f"{name}.reference.py")


def traffic_file(name: str, root: Path = ROOT) -> Path:
    return root / "bench" / "traffic" / f"{name}.json"


def reader(metric_name: str, root: Path = ROOT):
    family = metric_name.split(".")[0]
    return load_file(root / "bench" / "metrics" / f"{family}.py")
