"""The command line refuses to measure without a TPU, and without the
program beside the benchmark, printing no result either way."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "edgenext-s.b64", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def bench(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(p) -> bool:
    return '"correct"' not in p.stdout


def test_cpu_run_exits_nonzero_and_names_the_platform():
    p = bench(ROOT)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert no_result(p)


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = bench(tmp_path)
    assert p.returncode != 0
    assert no_result(p)
