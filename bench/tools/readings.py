#!/usr/bin/env python3
"""Readings for a cell's correctness limit, on many seeds in one process.

    python3 bench/tools/readings.py --workload <cell> --seeds 1,2,3 \
        --seconds 3 --controls high

For each seed it makes one run of the cell (``run.main``, a short window
at the cell's own load) and adds the readings of each control: the
reference at a lower precision, on the same sampled inputs.  The lower
reading of a limit is the largest program reading over the seeds, the
upper one the smallest control reading.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--controls", default="")
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args()
    controls = tuple(c for c in args.controls.split(",") if c)
    rows = []
    for seed in args.seeds.split(","):
        res = run.main(["--workload", args.workload, "--seed", seed,
                        "--seconds", str(args.seconds),
                        "--trace", str(args.trace)], controls=controls)
        rows.append({"seed": int(seed), "correct": res["correct"],
                     **{k: v["value"] for k, v in res["checks"].items()},
                     **{k: v["value"] for k, v in res["metrics"].items()}})
        gc.collect()
    for r in rows:
        print("READING " + json.dumps(r))
    for key in rows[0]:
        if key not in ("seed", "correct"):
            vals = [r[key] for r in rows]
            print(f"SUMMARY {key}: min {min(vals)} max {max(vals)}")


if __name__ == "__main__":
    main()
