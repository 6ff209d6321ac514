"""Benchmark aggregator: one section per paper table/figure + the
auto-scheduler's DSE, spatial, scan, perf, obs, serve and check rows.
These are cost-model and host-side search numbers, never device
metrics: device numbers come from ``bench/run.py`` on the chip.

Prints ``name,value,derived`` CSV to stdout and mirrors the same rows
into a machine-readable ``BENCH_<sha>.json`` under ``--out-dir``
(default: the repo root) so the perf trajectory is tracked across PRs —
point ``--out-dir`` at the directory holding the redirected CSV to keep
the two together.  Usage:
    PYTHONPATH=src python -m benchmarks.run [--out-dir DIR] [--no-json]
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

ROOT = Path(__file__).resolve().parents[1]


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=ROOT,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "nogit"
    except (OSError, subprocess.SubprocessError):
        return "nogit"


def collect_rows() -> list:
    """All benchmark rows as (name, value, note) tuples."""
    from benchmarks.paper_figs import ALL
    from benchmarks.dse import (bench_obs, bench_scan, bench_search,
                                bench_search_perf, bench_spatial)
    from benchmarks.serve import bench_serve
    from benchmarks.check import bench_check

    rows = []
    sections = dict(ALL)
    sections["search(DSE)"] = bench_search
    sections["search(spatial)"] = bench_spatial
    sections["search(scan)"] = bench_scan
    sections["search(perf)"] = bench_search_perf
    sections["search(obs)"] = bench_obs
    sections["search(serve)"] = bench_serve
    sections["search(check)"] = bench_check
    for section, fn in sections.items():
        t0 = time.perf_counter()
        for name, value, note in fn():
            rows.append((name, value, note))
        dt = (time.perf_counter() - t0) * 1e6
        rows.append((f"_section.{section}.us_per_call", dt, ""))

    return rows


def main(argv=None) -> None:
    from repro.runtime.compile_cache import setup_compile_cache
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", type=Path, default=ROOT,
                    help="where BENCH_<sha>.json is written")
    ap.add_argument("--no-json", action="store_true",
                    help="print the CSV only")
    args = ap.parse_args(argv)

    rows = collect_rows()
    print("name,value,derived")
    for name, value, note in rows:
        print(f"{name},{value:.6g},{note}")

    if not args.no_json:
        sha = _git_sha()
        args.out_dir.mkdir(parents=True, exist_ok=True)
        out = args.out_dir / f"BENCH_{sha}.json"
        out.write_text(json.dumps({
            "sha": sha,
            "unix_time": int(time.time()),
            "rows": [{"name": n, "value": v, "note": note}
                     for n, v, note in rows],
        }, indent=1))
        print(f"_bench.json,0,{out}", file=sys.stderr)


if __name__ == "__main__":
    main()
