#!/usr/bin/env python3
"""What the program's ``serve.request`` span costs a request, on the host.

    python3 bench/tools/span_cost.py --workload edgenext-s --batch 1 \
        --requests 20000 --rounds 5

It warms a serving store in a temporary directory, then times
``ServeStore.request`` back to back, ``--requests`` calls at a time, in
four modes, interleaved over ``--rounds`` rounds so that a drift of the
machine falls on every mode alike:

    off              no tracer (the benchmark's measured windows)
    tracer           an ``obs.tracing()`` tracer without the profiler flag
    profiler         a running ``jax.profiler`` trace with the
                     benchmark's options (``trace.options``), no tracer
    profiler+spans   a running trace and ``Tracer(profiler=True)`` (the
                     profiled window of ``bench/layer_profile.py``)

and prints each mode's median us per request over the rounds, with the
spread of the rounds, and the two differences that price the spans:
``tracer - off`` and ``profiler+spans - profiler``.  A 3 s window of the
benchmark serves 80-300 requests, too few to resolve a cost of a few us.
Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import trace as trace_lib  # noqa: E402

MODES = ("off", "tracer", "profiler", "profiler+spans")


def _timed(store, workload: str, batch: int, n: int) -> float:
    """Mean us per ``store.request`` over ``n`` calls."""
    t = time.perf_counter()
    for _ in range(n):
        store.request(workload, batch)
    return (time.perf_counter() - t) / n * 1e6


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="edgenext-s")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--requests", type=int, default=20000)
    ap.add_argument("--rounds", type=int, default=5)
    args = ap.parse_args()

    import jax
    from repro import obs
    from repro.serve.store import ServeStore

    with tempfile.TemporaryDirectory() as tmp:
        root = Path(tmp)
        ServeStore(root / "store").warm([args.workload],
                                        batches=(args.batch,))
        store = ServeStore(root / "store")
        _timed(store, args.workload, args.batch, 1000)     # warm the rung
        us = {m: [] for m in MODES}
        for r in range(args.rounds):
            for mode in MODES if r % 2 == 0 else MODES[::-1]:
                trace_dir = root / "trace"
                profiling = mode.startswith("profiler")
                if profiling:
                    jax.profiler.start_trace(
                        str(trace_dir), profiler_options=trace_lib.options())
                tracer = obs.Tracer(profiler=mode == "profiler+spans")
                ctx = obs.tracing(tracer) if mode in (
                    "tracer", "profiler+spans") else contextlib.nullcontext()
                try:
                    with ctx:
                        us[mode].append(_timed(store, args.workload,
                                               args.batch, args.requests))
                finally:
                    if profiling:
                        jax.profiler.stop_trace()
                        shutil.rmtree(trace_dir, ignore_errors=True)
    med = {m: statistics.median(v) for m, v in us.items()}
    out = {"requests_per_round": args.requests, "rounds": args.rounds,
           "us_per_request": med,
           "spread_us": {m: max(v) - min(v) for m, v in us.items()},
           "tracer_minus_off_us": med["tracer"] - med["off"],
           "spans_minus_profiler_us": med["profiler+spans"]
           - med["profiler"],
           "rounds_us": us}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
