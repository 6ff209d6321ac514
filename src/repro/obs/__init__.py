"""repro.obs — observability for the scheduler stack.

Four pieces:

  ``tracer``    — hierarchical span ``Tracer`` (nested wall-time spans
                  with attributes, thread/process-safe), typed
                  counters/gauges, and the ambient active-tracer hooks
                  (``span``/``count``/``gauge``/``event``) every
                  instrumentation site in ``repro.search`` calls; all
                  no-ops when no tracer is active.  The serving stack
                  reports through the same hooks: ``cache.*`` (incl.
                  ``cache.lock_takeover``), ``serve.retry.*`` (the
                  cold-search retry/deadline envelope),
                  ``serve.degrade.*`` (which degradation-ladder rung
                  answered), ``serve.chaos.*`` (injected faults), and
                  ``serve.loop.*`` (the simulated request loop), and
                  ``check.pass`` / ``check.fail`` (the ``repro.check``
                  static verifier on replayed artifacts) — all flow
                  into BENCH rows via ``bench_rows`` generically.
  ``exporters`` — Chrome-trace/Perfetto JSON (``--trace out.json``,
                  load in ``chrome://tracing``) and ``search.obs.*``
                  BENCH rows.
  ``explain``   — the markdown "schedule explain" report behind the
                  CLI's ``--explain`` (per-layer mapping decisions,
                  per-level traffic/energy breakdown, fusion groups).
  ``layers``    — the op -> layer table of a compiled program
                  (``op_layers``): each HLO instruction's scheduler layer,
                  read from the ``jax.named_scope`` the model forwards
                  open per layer, and each scope's scheduler class
                  (``layer_classes``, over a chain and the spelling its
                  model module declares).  A tracer made with
                  ``Tracer(profiler=True)`` also writes its spans onto a
                  running ``jax.profiler`` trace (``serve.request``), on
                  the clock of the device's ops.

Typical capture::

    from repro import obs
    with obs.tracing() as tracer:
        sched = auto_schedule(layers, hw, workload="edgenext-s")
    obs.write_chrome_trace(tracer, "trace.json")
    print(obs.explain_schedule(layers, sched, hw))
"""
from repro.obs.tracer import (Span, Tracer, activate, count, current,
                              event, gauge, span, tracing)
from repro.obs.exporters import bench_rows, chrome_trace, write_chrome_trace
from repro.obs.explain import explain_schedule
from repro.obs.layers import layer_classes, op_layers

__all__ = [
    "Span", "Tracer", "activate", "count", "current", "event", "gauge",
    "span", "tracing",
    "bench_rows", "chrome_trace", "write_chrome_trace",
    "explain_schedule", "layer_classes", "op_layers",
]
