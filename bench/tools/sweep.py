#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest arrival rate served
without a growing backlog.

    python3 bench/tools/sweep.py --workload edgenext-s.b1-poisson \
        --rates 400,600,800 --seconds 5 --seed 1 --repeats 2

It builds and warms the cell once, measures the closed-loop service rate
(requests back to back), then runs the cell's open loop at each rate,
``--repeats`` times on successive seeds, and prints the latency
quartiles, the 95th percentile, the slowest service time, and how far
the last tenth of requests waited beyond the first tenth (a backlog that
grows).  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import loop  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--repeats", type=int, default=1)
    args = ap.parse_args()
    st = run.prepare(args.workload, args.seed)
    gc.collect()
    gc.disable()                  # as in a run's window
    closed = dict(st.spec, kind="closed")
    w = loop.drive(st.model, traffic.requests(closed, args.seed,
                                              args.seconds),
                   st.inputs, args.seconds)
    done = [r for r in w.records if r.ok and r.end <= w.end]
    print("SWEEP " + json.dumps({"closed_req_per_s": len(done) / w.seconds,
                                 "service_ms_median": 1e3 * statistics.median(
                                     r.end - r.start for r in done)}))
    for rate in (float(r) for r in args.rates.split(",")):
        for k in range(args.repeats):
            spec = dict(st.spec, kind="poisson", rate_per_s=rate)
            w = loop.drive(st.model, traffic.requests(spec, args.seed + k,
                                                      args.seconds),
                           st.inputs, args.seconds)
            lat = [r.latency for r in w.records]
            q = statistics.quantiles(lat, n=100)
            tenth = max(1, len(lat) // 10)
            print("SWEEP " + json.dumps({
                "rate": rate, "seed": args.seed + k, "requests": len(lat),
                "p50_ms": 1e3 * q[49], "p95_ms": 1e3 * q[94],
                "p99_ms": 1e3 * q[98],
                "service_max_ms": 1e3 * max(r.end - r.start
                                            for r in w.records if r.ok),
                "service_over_50ms": sum(r.end - r.start > 0.05
                                         for r in w.records if r.ok),
                "backlog_growth_ms": 1e3 * (
                    statistics.median(lat[-tenth:])
                    - statistics.median(lat[:tenth])),
                "late_sends_max_ms": 1e3 * max(w.lateness)
                if w.lateness else None}))


if __name__ == "__main__":
    main()
