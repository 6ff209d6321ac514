"""Per-layer device time and the program's own spans, from a short
profiled window that a ``--trace 1`` run's metric readers drive.

The program names its work (``repro.obs.layers``): each layer of the
model forwards runs in a ``jax.named_scope`` spelled as the scheduler's
layer, and ``ServeStore.request`` is one ``serve.request`` span, which a
tracer made with ``Tracer(profiler=True)`` writes onto a running
``jax.profiler`` trace.  ``profile(run)`` reads both, once per run:

1. after the traced window, it serves the traced window's first
   ``PROFILE_S`` seconds of requests again (the same arrivals, sizes
   and order, inputs drawn anew) through ``loop.drive``, with the
   profiler on (``trace.options``) and such a tracer active;
2. it reads the device ops, the host phases and the ``serve.request``
   spans from that trace (the spans apart from the phases, which
   ``trace.attribute`` splits);
3. it takes the served program's optimized HLO text (``hlo_text``),
   maps each instruction to its layer (``op_layers``) and each layer to
   its scheduler class (``repro.search.layer_scopes``), and sums the
   device ops' self time per layer over the window (``layer_times``).

Nothing of it reaches ``setup_s`` or either measured window.  A program
without layer scopes or the profiler flag (an earlier commit's) gives
``None``, and so every reader's metric is left out.  The run prints
what it read: the layers' times and their share of device busy time,
the unattributed ops, and the requests per second and median service
time against the traced window's, which is the cost of
the program's spans on top of the profiler's.
"""
from __future__ import annotations

import dataclasses
import functools
import shutil
import statistics
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, Optional

import jax

import loop
import trace as trace_lib

PROFILE_S = 3.0           # the profiled window's length at most
SPAN = "serve.request"    # the serving store's span
TRACE_DIR = ".bench-trace/layers"
TOP = 5


def instruction(op_name: str) -> str:
    """The HLO instruction's name of a device op's event name
    ('%fusion.7 = f32[8] fusion(...)' gives 'fusion.7')."""
    return op_name.partition(" = ")[0].strip().lstrip("%")


def layer_times(trace: trace_lib.Trace, table: Dict[str, str],
                window=None) -> dict:
    """Device self time per layer over the window (the ``window``
    annotation unless given), averaged over the devices: ``layer_s``
    ``{layer: s}``, ``unattributed_s`` for the ops ``table`` maps to no
    layer, and ``unattributed_ops``, their largest labels.  Self time is
    ``trace.reduce``'s: the two add up to its ``busy_s``."""
    lo, hi = window if window is not None else trace_lib.window_of(trace)
    layer_ns: Dict[str, float] = defaultdict(float)
    other_ns: Dict[str, float] = defaultdict(float)
    for ops in trace.ops.values():
        for name, s, e, own in trace_lib.self_times(ops):
            if s < hi and e > lo and e > s:
                ns = own * (min(e, hi) - max(s, lo)) / (e - s)
                layer = table.get(instruction(name))
                if layer is None:
                    other_ns[trace_lib.op_label(name)] += ns
                else:
                    layer_ns[layer] += ns
    n = max(len(trace.ops), 1)
    return {"layer_s": {k: v / n * 1e-9 for k, v in layer_ns.items()},
            "unattributed_s": sum(other_ns.values()) / n * 1e-9,
            "unattributed_ops": [
                [k, v / n * 1e-9] for k, v in sorted(
                    other_ns.items(), key=lambda kv: -kv[1])[:TOP]]}


def hlo_text(model, xd) -> str:
    """The served program's optimized HLO text, its scopes included.

    JAX's compile cache keys a program without its metadata, so the
    executable a run loaded may have been compiled from the same program
    without scopes (an earlier commit's): its text names no layer.  A
    fresh ``jit`` of the served function is compiled under a key that
    holds the metadata; instruction names do not depend on metadata, so
    they are those of the executable that ran.  The first traced run of a
    checkout compiles; later ones find the entry in the cache."""
    fresh = jax.jit(functools.partial(model.fwd.__wrapped__))
    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        return fresh.lower(model.weights, xd).compile().as_text()
    finally:
        jax.config.update(key, was)


def profile(run) -> Optional[dict]:
    """The profiled window's readings, made once per run (kept on the
    run); None where the run has no traced window or the program has no
    layer scopes."""
    if "layer_profile" not in vars(run):
        run.layer_profile = _profile(run)
    return run.layer_profile


def _profile(run) -> Optional[dict]:
    if run.trace is None or not run.done:
        return None
    try:
        from repro.obs import Tracer, tracing
        from repro.obs.layers import op_layers
        from repro.search import layer_scopes
    except ImportError:          # a program without layer scopes
        return None
    model = run.model
    first = run.done[0].req
    # one program: the requests of the first one's shape, inputs anew
    reqs = [dataclasses.replace(r.req, input=0) for r in run.window.records
            if (r.req.batch, r.req.length) == (first.batch, first.length)
            and (r.req.due is None or r.req.due < PROFILE_S)]
    if not reqs:
        return None
    inputs = model.inputs(1, first.batch, first.length, 0)
    trace_dir = Path(model.root) / TRACE_DIR
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir),
                             profiler_options=trace_lib.options())
    try:
        with tracing(Tracer(profiler=True)):
            window = loop.drive(model, reqs, inputs,
                                min(PROFILE_S, run.window.seconds))
    finally:
        jax.profiler.stop_trace()
    try:
        loaded = trace_lib.load(trace_lib.find_xplane(trace_dir),
                                loop.PHASES + (SPAN,))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    spans = [e - s for n, s, e in loaded.host if n == SPAN]
    trace = trace_lib.Trace(loaded.ops,
                            [h for h in loaded.host if h[0] != SPAN],
                            loaded.offset_ns)
    done = [r for r in window.records if r.ok and r.end <= window.end]
    out = {"requests": len(done), "span_ns": spans, "layer_s": None,
           "classes": {}}
    if trace.ops and done:
        workload = model.lookup(first.batch).workload
        t = time.perf_counter()
        hlo = hlo_text(model, model.put(loop.request_input(inputs,
                                                           reqs[0])))
        print(f"layer profile: the program's HLO in "
              f"{time.perf_counter() - t:.3f} s")
        out["classes"] = layer_scopes(workload)
        out.update(layer_times(trace, op_layers(hlo, out["classes"])))
        out["busy_s"] = trace_lib.reduce(trace)["busy_s"]
    _report(run, window, reqs, done, out)
    return out


def _report(run, window, reqs, done, out) -> None:
    """What the profiled window read.  Its requests per second are
    compared with the traced window's in a closed loop, and with those
    due in it in an open loop (1: the server kept up)."""
    rate = len(done) / window.seconds
    base = len(run.done) / run.window.seconds \
        if reqs[0].due is None else len(reqs) / window.seconds
    service = statistics.median(r.end - r.start for r in done) \
        if done else None
    base_service = statistics.median(r.end - r.start for r in run.done)
    spans = out["span_ns"]
    print(f"layer profile: {len(done)} requests in {window.seconds} s with "
          f"the program's spans on; requests per second {rate} against "
          f"{base} (ratio {rate / base}); median "
          f"service s {service} against {base_service}; {len(spans)} "
          f"{SPAN} spans, median us "
          f"{1e-3 * statistics.median(spans) if spans else None}")
    if out["layer_s"] is None:
        return
    attributed = sum(out["layer_s"].values())
    by_op: Dict[str, float] = defaultdict(float)
    for layer, s in out["layer_s"].items():
        op, role = out["classes"].get(layer, (None, None))
        by_op[str(op)] += s
        by_op["ibn_role"] += s if role else 0.0
    print(f"layer profile: busy s {out['busy_s']}, in layers {attributed} "
          f"({100 * attributed / out['busy_s']}%), unattributed "
          f"{out['unattributed_s']}: {out['unattributed_ops']}")
    print(f"layer profile: s by scheduler op {dict(by_op)}")
    print("layer profile: s by layer " + str(dict(sorted(
        out["layer_s"].items(), key=lambda kv: -kv[1]))))


def class_ms(run, keep) -> Optional[float]:
    """Device ms per request in the layers ``keep(name, op, ibn_role)``
    selects; None where none of them ran an op (a renamed scope)."""
    p = profile(run)
    if p is None or p["layer_s"] is None or not p["requests"]:
        return None
    hit = [s for name, s in p["layer_s"].items()
           if keep(name, *p["classes"].get(name, (None, None)))]
    return 1e3 * sum(hit) / p["requests"] if hit else None


def span_median_us(run) -> Optional[float]:
    """Median duration, in us, of the program's ``serve.request`` span."""
    p = profile(run)
    if p is None or not p["span_ns"]:
        return None
    return 1e-3 * statistics.median(p["span_ns"])
