"""Reduce a profiler trace to device busy time, top ops and idle gaps.

A traced run writes the JAX profiler's ``.xplane.pb``.  Device operations
are the events of the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane;
the benchmark's host phases are ``jax.profiler.TraceAnnotation`` events
on the host plane.  One annotation named ``window`` spans the measured
window.  The device's clock runs behind the host's by a millisecond or
two in these traces, so device times are shifted onto the host's.  Each
request runs one program, so the k-th program on the device
(``XLA Modules``) is the one the k-th ``wait`` annotation waits for; the
shift is the least gap between a program's end and its wait's end, which
puts every program's end at or before the host learns of it.  (Where the
counts differ, no shift is made.)

    busy       union of the op intervals of a device, clipped to the window
    idle       the window less busy
    top ops    device self time (less the ops nested inside, such as a
               loop's body) per op label, summed over the window; the
               label is the HLO op's name without its number, and its
               result type without layouts
    idle gaps  each idle interval split by the host phase that covers it
               (``host-other`` where no phase does), summed per phase

Times are averaged over the devices that ran an op.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WAIT = "wait"
HOST_PLANE_PREFIX = "/host:"
WINDOW = "window"
OTHER = "host-other"
TOP = 10
TRUNCATED_NS = 1e9

Interval = Tuple[float, float]


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Tuple[str, float, float]]]   # device -> (name, s, e)
    host: List[Tuple[str, float, float]]             # (phase, s, e)
    offset_ns: Dict[str, float] = dataclasses.field(default_factory=dict)


def options():
    """Profiler options for a traced run: device ops and the benchmark's
    annotations.  The Python tracer and the runtime's level-2 host events
    stay off: on a v5e host the first slowed a request's host path many
    times over, the second its input transfer about tenfold."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def find_xplane(logdir) -> Path:
    found = sorted(Path(logdir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def load(path, phases: Iterable[str]) -> Trace:
    """Read device ops and the host annotations named in ``phases``
    (plus ``window``) from one ``.xplane.pb``; times in ns on the host's
    clock."""
    from jax.profiler import ProfileData
    wanted = set(phases) | {WINDOW}
    data = ProfileData.from_file(str(path))
    ops: Dict[str, List[Tuple[str, float, float]]] = {}
    ends: Dict[str, List[float]] = {}           # device -> program ends
    host: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
                elif line.name == MODULES_LINE:
                    ends[plane.name] = sorted(
                        e.start_ns + e.duration_ns for e in line.events)
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    waits = sorted(e for n, _, e in host if n == WAIT)
    offsets = {}
    for dev in ops:
        done = ends.get(dev, [])
        offsets[dev] = min(w - e for w, e in zip(waits, done)) \
            if done and len(done) == len(waits) else 0.0
    return Trace({dev: [(n, s + offsets[dev], e + offsets[dev])
                        for n, s, e in evs]
                  for dev, evs in ops.items() if evs}, host, offsets)


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def complement(busy: List[Interval], lo: float, hi: float
               ) -> List[Interval]:
    """The gaps of sorted disjoint ``busy`` within [lo, hi]."""
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def attribute(gaps: List[Interval], phases: List[Tuple[str, float, float]]
              ) -> Dict[str, float]:
    """Split each gap by the phases that cover it; ns per phase name.
    ``phases`` are sorted by start and do not overlap one another."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for s, e in gaps:
        covered = 0.0
        while j < len(phases) and phases[j][2] <= s:
            j += 1
        k = j
        while k < len(phases) and phases[k][1] < e:
            name, ps, pe = phases[k]
            ov = min(e, pe) - max(s, ps)
            if ov > 0:
                out[name] += ov
                covered += ov
            k += 1
        if e - s - covered > 0:
            out[OTHER] += e - s - covered
    return dict(out)


def op_label(name: str) -> str:
    """An op's label: '%fusion.7 = f32[64,48]{1,0:T(8,128)} fusion(...)'
    gives 'fusion f32[64,48]'."""
    head, _, rest = name.partition(" = ")
    base = re.sub(r"\.\d+$", "", head.strip().lstrip("%"))
    rest = re.sub(r"/\*.*?\*/|\{[^{}]*\}", "", rest)
    m = re.match(r"\([^)]*\)|\S+", rest)
    label = f"{base} {m.group(0)}" if m else base
    return label if len(label) <= 120 else label[:117] + "..."


def self_times(ops: List[Tuple[str, float, float]]
               ) -> List[Tuple[str, float, float, float]]:
    """(name, start, end, self ns): each op's duration less that of the
    ops nested directly inside it."""
    out: List[List] = []
    stack: List[int] = []
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= out[stack[-1]][2]:
            out[stack[-1]][3] -= e - s
        out.append([name, s, e, e - s])
        stack.append(len(out) - 1)
    return [tuple(o) for o in out]


def window_of(trace: Trace) -> Interval:
    spans = [(s, e) for n, s, e in trace.host if n == WINDOW]
    if len(spans) != 1:
        raise ValueError(f"expected one '{WINDOW}' annotation, "
                         f"found {len(spans)}")
    return spans[0]


def reduce(trace: Trace, window: Optional[Interval] = None) -> dict:
    """Busy and idle seconds, top ops and idle gaps by host phase over
    the window (the ``window`` annotation unless given), averaged over
    the devices that ran an op.  The profiler keeps a bounded number of
    device events (about 3.5 million on a v5e); where the device's ops
    stop more than ``TRUNCATED_NS`` before the window's end, the traced
    window ends with them."""
    lo, hi = window if window is not None else window_of(trace)
    last = max(e for ops in trace.ops.values() for _, _, e in ops) \
        if trace.ops else hi
    truncated = last < hi - TRUNCATED_NS
    if truncated:
        hi = last
    phases = sorted((p for p in trace.host if p[0] != WINDOW),
                    key=lambda p: p[1])
    devices = sorted(trace.ops)
    if not devices:
        raise ValueError("no device ops in the trace")
    busy_ns = 0.0
    op_ns: Dict[str, float] = defaultdict(float)
    gap_ns: Dict[str, float] = defaultdict(float)
    n_ops = 0
    for dev in devices:
        spans = clip(((s, e) for _, s, e in trace.ops[dev]), lo, hi)
        busy = merge(spans)
        busy_ns += sum(e - s for s, e in busy)
        for name, s, e, own in self_times(trace.ops[dev]):
            if s < hi and e > lo:
                op_ns[op_label(name)] += own * (min(e, hi) - max(s, lo)) \
                    / (e - s) if e > s else 0.0
                n_ops += 1
        for name, ns in attribute(complement(busy, lo, hi), phases).items():
            gap_ns[name] += ns
    n = len(devices)
    window_s = (hi - lo) * 1e-9
    busy_s = busy_ns / n * 1e-9

    def top(d):
        return [[k, v / n * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s, "devices": n,
            "truncated": truncated,
            "clock_offset_s": [trace.offset_ns.get(d, 0.0) * 1e-9
                               for d in devices],
            "ops": n_ops, "top_ops": top(op_ns), "idle_gaps": top(gap_ns)}
