"""The measured window: one client (closed loop) or one open-loop
sender, in the benchmark's one process and thread (``drive``).

Each request walks the served path in host phases, each written as a
``jax.profiler.TraceAnnotation`` so that a traced run can attribute the
device's idle gaps, and timed on the host clock:

    lookup    the serving store's schedule lookup
    h2d       the request's inputs put on the device
    dispatch  the jitted call enqueued
    wait      until the device has finished
    d2h       the outputs copied to the host
    idle-for-arrival   (open loop) nothing due yet
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterable, List, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from traffic import Request

PHASES = ("lookup", "h2d", "dispatch", "wait", "d2h", "idle-for-arrival")
SPIN_S = 0.002        # sleep until this close to a due time, then spin


@dataclasses.dataclass
class Record:
    req: Request
    due: float            # host-clock time it was due (closed: sent)
    start: float
    end: float = float("inf")
    ok: bool = False
    output: Optional[np.ndarray] = None
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def latency(self) -> float:
        return self.end - self.due if self.ok else float("inf")


@dataclasses.dataclass
class Window:
    start: float
    seconds: float
    records: List[Record]
    lateness: List[float]   # open loop: start - due where the sender idled

    @property
    def end(self) -> float:
        return self.start + self.seconds


def serve(model, req: Request, x, rec: Record) -> None:
    """Walk one request through the served path, timing each phase."""
    clock = time.perf_counter
    ph = rec.phases
    try:
        with TraceAnnotation("lookup"):
            t0 = clock()
            model.lookup(req.batch)
            t1 = clock()
        with TraceAnnotation("h2d"):
            xd = model.put(x)
            t2 = clock()
        with TraceAnnotation("dispatch"):
            yd = model.launch(xd)
            t3 = clock()
        with TraceAnnotation("wait"):
            jax.block_until_ready(yd)
            t4 = clock()
        with TraceAnnotation("d2h"):
            rec.output = model.fetch(yd)
            t5 = clock()
    except Exception as e:                 # noqa: BLE001 — a failed request
        print(f"request {req.index} failed: {type(e).__name__}: {e}")
        rec.end = clock()
        return
    ph["lookup"], ph["h2d"], ph["dispatch"] = t1 - t0, t2 - t1, t3 - t2
    ph["wait"], ph["d2h"] = t4 - t3, t5 - t4
    rec.end, rec.ok = t5, True


class _WindowSpan:
    """The ``window`` annotation, closed as soon as the window ends."""

    def __init__(self, seconds: float):
        self._ann = TraceAnnotation("window")
        self._ann.__enter__()
        self.start = time.perf_counter()
        self.end = self.start + seconds
        self._open = True

    def close_if_over(self, now: float) -> None:
        if self._open and now >= self.end:
            self._ann.__exit__(None, None, None)
            self._open = False


def request_input(inputs, req: Request):
    """A request's input: the leading ``batch`` items (and ``length``
    positions) of its pool entry."""
    x = inputs[req.input][:req.batch]
    return x[:, :req.length] if req.length else x


def drive(model, reqs: Iterable[Request], inputs, seconds: float,
          drain_s: float = 60.0) -> Window:
    """Serve the requests in order until the window closes.  A request
    with a due time is served from then on, whether or not the server
    has kept up, and every one of them is served (those not begun within
    ``drain_s`` after the window fail); one without is sent as soon as
    the previous one has returned, while the window is open."""
    records: List[Record] = []
    lateness: List[float] = []
    clock = time.perf_counter
    win = _WindowSpan(seconds)
    for req in reqs:
        now = clock()
        win.close_if_over(now)
        if req.due is None:
            if now >= win.end:
                break
            due = now
        else:
            due = win.start + req.due
            if now > win.end + drain_s:
                records.append(Record(req, due, now))
                continue
            if now < due:
                with TraceAnnotation("idle-for-arrival"):
                    if due - now > SPIN_S:
                        time.sleep(due - now - SPIN_S)
                    while clock() < due:
                        pass
                now = clock()
                lateness.append(now - due)
        rec = Record(req, due, now)
        serve(model, req, request_input(inputs, req), rec)
        records.append(rec)
    win.close_if_over(float("inf"))
    return Window(win.start, seconds, records, lateness)
