#!/usr/bin/env python3
"""Benchmark cells on two checkouts of the repo, alternated on one chip.

    python3 tools/ab_cells.py --parent <dir> --change <dir> \
        --cells rwkv6.prefill512,edgenext-s.b64 --seeds 11,12 \
        --seconds 51 --out bench-out/ab

Each checkout is a directory holding ``bench/`` and ``src/``; the parent
is typically ``git archive HEAD~1`` unpacked into a gitignored
directory.  For every cell, first one short run of each side warms the
compile cache (its set-up is printed as cold, not compared); then, per
pair of seeds, the order parent, change, change, parent, so neither side
always runs first.  Every run is its own process, ``bench/run.py`` as a
benchmark check runs it, with its log under ``--out``.  The summary
gives each end-to-end metric, ``setup_s`` and the set-up's parts as the
harness prints them, per side, with the medians.
"""
from __future__ import annotations

import argparse
import json
import re
import statistics
import subprocess
import sys
from pathlib import Path

_PART = re.compile(r"set-up: to the device ([\d.]+) s, weights and program "
                   r"([\d.]+) s")
_WARM = re.compile(r"set-up: warm request 0 .*: ([\d.]+) s")


def run(tree: Path, cell: str, seed: int, seconds: float, trace: int,
        log: Path) -> dict:
    """One benchmark run in ``tree``: its result line and set-up parts."""
    with log.open("w") as fh:
        rc = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", cell, "--seed",
             str(seed), "--seconds", str(seconds), "--trace", str(trace)],
            cwd=tree, stdout=fh, stderr=subprocess.STDOUT).returncode
    text = log.read_text()
    row = {"rc": rc, "seed": seed}
    lines = [ln for ln in text.splitlines() if ln.startswith("{")]
    if lines:
        res = json.loads(lines[-1])
        row["correct"] = res.get("correct")
        row.update({k: v["value"] for k, v in res["metrics"].items()})
    if m := _PART.search(text):
        row["to_device_s"], row["weights_s"] = map(float, m.groups())
    if m := _WARM.search(text):
        row["warm0_s"] = float(m.group(1))
    return row


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--parent", type=Path, required=True)
    ap.add_argument("--change", type=Path, required=True)
    ap.add_argument("--cells", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated; an even count gives ABBA pairs")
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", type=Path, default=Path("bench-out/ab"))
    args = ap.parse_args()
    args.out.mkdir(parents=True, exist_ok=True)
    sides = {"parent": args.parent.resolve(), "change": args.change.resolve()}
    seeds = [int(s) for s in args.seeds.split(",")]
    for cell in args.cells.split(","):
        for side, tree in sides.items():
            row = run(tree, cell, seeds[0] - 1, 1, 0,
                      args.out / f"{cell}.{side}.cold.log")
            print(f"{cell} {side} cold: {row}", flush=True)
        rows = {side: [] for side in sides}
        for i, seed in enumerate(seeds):
            order = ("parent", "change") if i % 2 == 0 else ("change",
                                                             "parent")
            for side in order:
                row = run(sides[side], cell, seed, args.seconds, args.trace,
                          args.out / f"{cell}.{side}.{seed}.log")
                rows[side].append(row)
                print(f"{cell} {side}: {row}", flush=True)
        for side, got in rows.items():
            keys = sorted({k for r in got for k in r} - {"rc", "seed"})
            med = {k: statistics.median(r[k] for r in got if k in r)
                   for k in keys if k != "correct"}
            print(f"SUMMARY {cell} {side} median {json.dumps(med)}",
                  flush=True)


if __name__ == "__main__":
    main()
