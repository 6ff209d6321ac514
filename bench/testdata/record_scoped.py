#!/usr/bin/env python3
"""Record the small scoped trace that ``bench/tests/test_layer_profile.py``
reads.

    python3 bench/testdata/record_scoped.py [out_dir]

Run it on a host with a TPU.  A jitted layer scan names its work as the
model forwards do (``jax.named_scope`` per layer): each of four layers
casts its f32 weight to bf16 and runs ``pw1`` (a matmul), ``act`` and
``ln`` (a normalization over the row); outside the scan ``head.fc``
multiplies once more, and a transpose runs in no scope.  Inside one
``window`` annotation it does three rounds of the call dispatched
(``dispatch``) and waited for (``wait``), then 20 ms of
``idle-for-arrival``.  It writes ``scoped.xplane.pb`` and the program's
optimized HLO text, ``scoped.hlo.txt``, into ``out_dir`` (default:
beside this file), and prints the per-layer reduction.  The profiler's
options are a traced run's (``trace.options``).
"""
from __future__ import annotations

import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

import layer_profile  # noqa: E402
import trace as trace_lib  # noqa: E402

ROUNDS, IDLE_S = 3, 0.020
LAYERS = ("pw1", "act", "ln", "head.fc")


@jax.jit
def scoped(x, ws, head):
    def layer(h, w):
        with jax.named_scope("pw1"):
            h = h @ w.astype(jnp.bfloat16)
        with jax.named_scope("act"):
            h = jnp.tanh(h)
        with jax.named_scope("ln"):
            hf = h.astype(jnp.float32)
            h = (hf * jax.lax.rsqrt(jnp.mean(hf * hf, -1, keepdims=True)
                                    + 1e-6)).astype(h.dtype)
        return h, None

    x, _ = jax.lax.scan(layer, x, ws)
    with jax.named_scope("head.fc"):
        y = x @ head
    return y.T


def main() -> None:
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else Path(__file__).parent
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_scoped.py needs a TPU")
    out.mkdir(parents=True, exist_ok=True)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    ws = jnp.full((4, 1024, 1024), 1e-3, jnp.float32)
    head = jnp.ones((1024, 512), jnp.bfloat16)
    scoped(x, ws, head).block_until_ready()
    root = str(BENCH.parent)
    hidden = "<" + "." * (len(root) - 2) + ">"
    hlo = scoped.lower(x, ws, head).compile().as_text()
    (out / "scoped.hlo.txt").write_text(hlo.replace(root, hidden))
    tmp = Path(tempfile.mkdtemp(dir=out))
    jax.profiler.start_trace(str(tmp),
                             profiler_options=trace_lib.options())
    with TraceAnnotation("window"):
        for _ in range(ROUNDS):
            with TraceAnnotation("dispatch"):
                y = scoped(x, ws, head)
            with TraceAnnotation("wait"):
                y.block_until_ready()
            with TraceAnnotation("idle-for-arrival"):
                time.sleep(IDLE_S)
    jax.profiler.stop_trace()
    path = out / "scoped.xplane.pb"
    # the trace names source files by absolute path: keep the checkout's
    # location out of the committed file (same length, so it still parses)
    raw = trace_lib.find_xplane(tmp).read_bytes()
    path.write_bytes(raw.replace(root.encode(), hidden.encode()))
    shutil.rmtree(tmp)
    from repro.obs.layers import op_layers
    table = op_layers(hlo, LAYERS)
    trace = trace_lib.load(path, ("dispatch", "wait", "idle-for-arrival"))
    for name, s, e in trace.ops["/device:TPU:0"][:40]:
        inst = layer_profile.instruction(name)
        print(f"   {inst} -> {table.get(inst)} {(e - s) * 1e-3:.1f} us "
              f"{re.sub(r'[{][^}]*[}]', '', name)[:100]}")
    print(layer_profile.layer_times(trace, table))
    print(trace_lib.reduce(trace))
    for f in (path, out / "scoped.hlo.txt"):
        print(f"wrote {f} ({f.stat().st_size} bytes)")


if __name__ == "__main__":
    main()
