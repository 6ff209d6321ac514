#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds ``BENCHMARK.json``.  A run:

1. places JAX's compile cache (``repro.runtime.compile_cache``) and
   requires a TPU with as many chips as the cell asks for;
2. builds the cell's configuration (weights from the seed, on the
   device), makes its traffic's input pool, and warms every request
   shape its traffic sends: that is the set-up, ``setup_s``;
3. measures for ``--seconds``: a closed loop or an open loop of requests
   through the served path (``loop.py``).  With ``--trace 1`` a second,
   traced window of at most ``TRACE_S`` follows; the per-layer metrics
   read from the device trace come from it, all others from the first
   window, so that the profiler's cost on the host reaches none of them,
   and the run prints the two windows' ratio of requests per second;
4. reads the device's peak memory, frees the program's state, and runs
   the plain reference over a sample of the finished requests drawn from
   the seed: ``correct`` holds if every request came back and the widest
   relative error of a sampled answer is within the configuration's
   limit;
5. prints, on earlier lines, how late the sender ran, how many programs
   were compiled inside the window and the peak memory; on the last
   lines of standard error each compared number beside its limit; and as
   the last line of standard output one JSON object.  With ``--trace 0``
   its metrics are the cell's end-to-end metrics, with ``--trace 1`` its
   per-layer ones.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]
# the TPU runtime would otherwise log to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import numpy as np  # noqa: E402

import loop  # noqa: E402
import peaks as peak_table  # noqa: E402
import registry  # noqa: E402
import trace as trace_lib  # noqa: E402
import traffic as traffic_lib  # noqa: E402

TRACE_DIR = ".bench-trace"     # inside the checkout, removed after reading
TRACE_S = 10.0                 # the traced window's length at most


def process_age_s() -> float:
    """Seconds since this process started (0 where /proc is missing)."""
    try:
        ticks = int(Path("/proc/self/stat").read_text()
                    .rsplit(")", 1)[1].split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return max(0.0, uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


START_AGE_S = process_age_s() - (time.perf_counter() - T0)


class Run:
    """What the metric readers read (``bench/metrics/<family>.py``)."""

    def __init__(self, model, window, setup_s, trace, peaks):
        self.model, self.window = model, window
        self.setup_s, self.trace, self.peaks = setup_s, trace, peaks
        self.seconds = window.seconds
        # requests whose answers reached the host inside the window
        self.done = [r for r in window.records
                     if r.ok and r.end <= window.end]

    def items_per_s(self) -> float:
        return sum(self.model.items(r.req) for r in self.done) \
            / self.seconds

    def flops_per_s(self) -> float:
        return sum(self.model.flops(r.req) for r in self.done) \
            / self.seconds

    def phase_median(self, *names) -> float | None:
        vals = [sum(r.phases[n] for n in names) for r in self.done]
        return statistics.median(vals) if vals else None


class CompileCounter:
    """Counts JAX traces and compiles while ``on``."""

    def __init__(self):
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *_args, **_kw):
        if self.on and ("compile" in name or "trace_duration" in name):
            self.n += 1


def sample(records, seed: int, answers: int):
    """Finished requests drawn from the seed until they hold
    ``answers`` answers (an image or a prompt each)."""
    done = [r for r in records if r.ok]
    picked, n = [], 0
    for i in traffic_lib.rng(seed, 4).permutation(len(done)):
        if n >= answers:
            break
        picked.append(done[i])
        n += done[i].req.batch
    return picked


def service(window) -> None:
    """Print the spread of the window's service times and its slowest
    requests, where a stall of the host shows."""
    done = [r for r in window.records if r.ok]
    if not done:
        return
    t = sorted(r.end - r.start for r in done)
    q = statistics.quantiles(t, n=100) if len(t) > 1 else t * 99
    stalls = sum(x > 10 * q[49] for x in t)
    print(f"service s: median {q[49]} p99 {q[98]} max {t[-1]}; "
          f"{stalls} over 10x the median")
    slow = sorted(done, key=lambda r: r.start - r.end)[:3]
    print("slowest requests (s after the window opened, s, slowest "
          "phase): " + ", ".join(
              f"{r.start - window.start:.3f} {r.end - r.start:.4f} "
              f"{max(r.phases, key=r.phases.get)}" for r in slow))


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Widest ||got - want|| / ||want|| over the rows (one row an
    answer: an image's or a prompt's logits)."""
    got = got.reshape(len(got), -1).astype(np.float64)
    want = want.reshape(len(want), -1).astype(np.float64)
    err = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
    return float(np.max(err)) if np.all(np.isfinite(got)) else float("inf")


def prepare(cell_name: str, seed: int, *, root: Path = ROOT,
            sizes: dict | None = None, require_tpu: bool = True,
            patch=None) -> SimpleNamespace:
    """Place the compile cache, check the chip, build the cell's
    configuration and input pool, and warm every request shape its
    traffic sends: what a run or a tool drives."""
    st = SimpleNamespace()
    st.bench = registry.benchmark(root)
    st.cell = cell = registry.cell(st.bench, cell_name)
    cfg_entry = registry.config(st.bench, cell["config"])
    st.spec = spec = traffic_lib.load(
        registry.traffic_file(cell["traffic"], root))
    mod = registry.config_module(cfg_entry["name"], root)
    if sizes is None:
        sizes = json.loads((root / cfg_entry["file"]).read_text())
        mod.check_sizes(sizes)
    st.sizes = sizes

    from repro.runtime.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    st.devices = devices = jax.devices()
    st.peaks = peak_table.require_chip(devices, cell["chips"]) \
        if require_tpu else None
    print(f"cell {cell['name']}: config {cfg_entry['name']}, traffic "
          f"{cell['traffic']} {spec}, seed {seed}, device "
          f"{devices[0].device_kind} x{len(devices)}, compile cache "
          f"{cache_dir}")
    t = time.perf_counter()
    st.model = model = mod.Model(sizes, seed, root)
    jax.block_until_ready(model.weights)
    if patch is not None:
        patch(model)
    t_model = time.perf_counter()
    batch, length = traffic_lib.largest(spec)
    st.inputs = inputs = model.inputs(spec["pool"], batch, length, seed)
    t_inputs = time.perf_counter()
    for b, n in traffic_lib.shapes(spec):
        warm = traffic_lib.Request(-1, None, b, n, 0)
        for i in range(2):
            now = time.perf_counter()
            rec = loop.Record(warm, now, now)
            loop.serve(model, warm, loop.request_input(inputs, warm), rec)
            if not rec.ok:
                raise SystemExit("bench: the warm-up request failed")
            print(f"set-up: warm request {i} of batch {b}, length {n}: "
                  f"{rec.end - rec.start:.3f} s")
    print(f"set-up: to the device {t - T0:.3f} s, weights and program "
          f"{t_model - t:.3f} s, input pool {t_inputs - t_model:.3f} s")
    return st


def main(argv=None, *, require_tpu: bool = True, sizes: dict | None = None,
         root: Path = ROOT, patch=None, controls=()) -> dict:
    """One run; returns the result line's object.  Tests pass
    ``require_tpu=False``, a smaller ``sizes``, another ``root`` and a
    ``patch(model)`` that breaks the timed path; ``controls`` names
    reference modes at a lower precision whose readings are added."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        raise SystemExit("bench: --seed must be >= 0 and --seconds > 0")

    setup = prepare(args.workload, args.seed, root=root, sizes=sizes,
                    require_tpu=require_tpu, patch=patch)
    cell, sizes, model = setup.cell, setup.sizes, setup.model
    spec, inputs, dev = setup.spec, setup.inputs, setup.devices[0]
    metrics = registry.metrics(setup.bench, cell["name"], bool(args.trace))
    compiles = CompileCounter()
    gc.collect()
    setup_s = START_AGE_S + time.perf_counter() - T0

    # -- the window, and with --trace 1 a traced one after it -----------
    gc.disable()
    compiles.on = True
    window = loop.drive(model, traffic_lib.requests(spec, args.seed,
                                                    args.seconds),
                        inputs, args.seconds)
    traced, reduced = None, None
    if args.trace:
        trace_dir = root / TRACE_DIR / cell["name"]
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=trace_lib.options())
        seconds = min(args.seconds, TRACE_S)
        traced = loop.drive(model, traffic_lib.requests(spec, args.seed,
                                                        seconds),
                            inputs, seconds)
        jax.profiler.stop_trace()
    compiles.on = False
    gc.enable()
    if args.trace:
        t = time.perf_counter()
        reduced = trace_lib.reduce(trace_lib.load(
            trace_lib.find_xplane(trace_dir), loop.PHASES))
        shutil.rmtree(root / TRACE_DIR, ignore_errors=True)
        print(f"trace: {reduced['ops']} device ops, read in "
              f"{time.perf_counter() - t:.3f} s")
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    late = window.lateness
    print(f"sender lateness: {len(late)} sends after an idle wait, "
          f"median {statistics.median(late) if late else 0.0} s, "
          f"max {max(late) if late else 0.0} s")
    print(f"compilations in the window(s): {compiles.n}")
    print(f"peak_bytes_in_use: {peak_bytes}")
    service(window)

    run = Run(model, window, setup_s, None, setup.peaks)
    runs = {"host": run}
    if traced is not None:
        runs["device_trace"] = Run(model, traced, setup_s, reduced,
                                   setup.peaks)
        ratio = (len(runs["device_trace"].done) / traced.seconds) \
            / (len(run.done) / window.seconds) if run.done else None
        print(f"traced/untraced requests per second: {ratio}")
    values = {}
    for m in metrics:
        r = runs.get(m["source"], run)
        v = registry.reader(m["name"], root).read(r)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}

    # -- correctness: the reference over a sample, program state freed --
    records = window.records + (traced.records if traced else [])
    failed = sum(not r.ok for r in records)
    check = sizes["correct"]
    picked = sample(records, args.seed, check["sample_answers"])
    model.free()
    limit, err, n_answers = check["limit"], float("inf"), 0
    checks = {}
    # one reference call per input shape: prompts of one length together
    groups = {}
    for r in picked:
        x = loop.request_input(inputs, r.req)
        xs, ys = groups.setdefault(x.shape[1:], ([], []))
        xs.append(x)
        ys.append(r.output)
    for xs, ys in groups.values():
        x = np.concatenate(xs)
        want = model.reference(x)
        err = max(err if n_answers else 0.0,
                  rel_err(np.concatenate(ys), want))
        n_answers += len(x)
        for mode in controls:
            key = f"{mode}_{check['number']}"
            got = rel_err(model.reference(x, mode=mode), want)
            checks[key] = {"value": max(got, checks.get(key, {}).get(
                "value", 0.0)), "limit": limit}
    # a non-finite error (nothing compared, or NaN answers) prints null
    checks = {check["number"]: {"value": err if np.isfinite(err) else None,
                                "limit": limit}, **checks}
    correct = failed == 0 and limit is not None and err <= limit

    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": values,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(setup.devices),
                         "memory_peak_bytes": peak_bytes}}
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    print(f"compared: {len(picked)} requests, {n_answers} answers; "
          f"failed requests {failed}")
    sys.stdout.flush()
    for name, c in checks.items():
        print(f"check {name} = {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
