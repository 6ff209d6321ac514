#!/usr/bin/env python3
"""Record the small trace that ``bench/tests/test_trace.py`` reads.

    python3 bench/testdata/record.py [out_dir]

Run it on a host with a TPU.  Inside one ``window`` annotation it does
three rounds of: a chain of 8 matmuls dispatched (``dispatch``) and
waited for (``wait``), then 20 ms of ``idle-for-arrival``.  It writes
``small.xplane.pb`` into ``out_dir`` (default: beside this file) and
prints the trace's planes and lines and the reduction.  The profiler's
options are a traced run's (``trace.options``).
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

import trace as trace_lib  # noqa: E402

ROUNDS, IDLE_S = 3, 0.020


@jax.jit
def chain(x):
    for _ in range(8):
        x = jnp.tanh(x @ x)
    return x


def main() -> None:
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else Path(__file__).parent
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record.py needs a TPU")
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    chain(x).block_until_ready()
    tmp = Path(tempfile.mkdtemp(dir=out))
    jax.profiler.start_trace(str(tmp),
                             profiler_options=trace_lib.options())
    with TraceAnnotation("window"):
        for _ in range(ROUNDS):
            with TraceAnnotation("dispatch"):
                y = chain(x)
            with TraceAnnotation("wait"):
                y.block_until_ready()
            with TraceAnnotation("idle-for-arrival"):
                time.sleep(IDLE_S)
    jax.profiler.stop_trace()
    path = out / "small.xplane.pb"
    # the trace names source files by absolute path: keep the checkout's
    # location out of the committed file (same length, so it still parses)
    root = str(Path(__file__).resolve().parents[2]).encode()
    raw = trace_lib.find_xplane(tmp).read_bytes()
    path.write_bytes(raw.replace(root, b"<" + b"." * (len(root) - 2) + b">"))
    shutil.rmtree(tmp)
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(str(path)).planes:
        lines = [(line.name, sum(1 for _ in line.events))
                 for line in plane.lines]
        print(f"plane {plane.name!r}: {lines}")
        for line in plane.lines:
            for e in list(line.events)[:2]:
                print(f"   {line.name!r}: {e.name!r} start {e.start_ns} "
                      f"dur {e.duration_ns} stats {dict(e.stats)}")
    print(trace_lib.reduce(trace_lib.load(path, ("dispatch", "wait",
                                                 "idle-for-arrival"))))
    print(f"wrote {path} ({path.stat().st_size} bytes)")


if __name__ == "__main__":
    main()
