#!/usr/bin/env python3
"""Bring-up smoke for one TPU chip: EdgeNeXt-S's served schedule, the
Pallas kernels it lowers to, and the model's forward pass, at full width.

    python chip_smoke.py

Run it from the repository root on a host with a TPU.  It runs in one
process, in this order, and stops at the first failure:

  device   place the compile cache; require a TPU (never the CPU)
  serve    ``ServeStore.request`` for edgenext-s at batch 1 and 16 and
           for rwkv6 at batch 1 (its ``rwkv_chunk`` launch); no lookup
           may come back degraded
  kernels  every distinct lowered launch of those schedules, and every
           depthwise shape of EdgeNeXt-S at batch 16, compiled as a
           Mosaic kernel (``interpret=False``, ``tpu_custom_call`` in the
           compiled text) and compared with its ``kernels/ref.py`` oracle
  model    ``edgenext.forward`` at batch 16 on the TPU against the same
           function on the host CPU, and the ``ibn_chunks=4`` schedule
           against the plain one on the TPU

Weights and inputs are random, made from ``SEED``.  Times printed on the
way are smoke readings, not metrics.  The last line of standard output
is a JSON object naming the device; it is printed only when every phase
passed.
"""
from __future__ import annotations

import functools
import json
import statistics
import sys
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# importing compiles nothing: the cache is placed in main() before any
# compile, and JAX picks its backend on the first device query
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.edgenext_s import CONFIG  # noqa: E402
from repro.core.workload import DWCONV  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.models import edgenext, params as param_lib  # noqa: E402
from repro.runtime.compile_cache import setup_compile_cache  # noqa: E402
from repro.search import get_workload  # noqa: E402
from repro.serve.store import ServeStore  # noqa: E402

SEED = 0
REQUESTS = (("edgenext-s", 1), ("edgenext-s", 16), ("rwkv6", 1))
DEPTHWISE_WORKLOAD = "edgenext-s-b16"
MODEL_BATCH = 16
TIMED_CALLS = 5
# the function of each workload's IBN activation layer (a Layer names
# the act layer, not its function): EdgeNeXt's GELU, RWKV-6's relu^2
ACTIVATION = {"edgenext-s": "gelu", "rwkv6": "relu2"}
# max |kernel - oracle| per kernel, both in float32 on the chip, with
# operands scaled so that every output is O(1).  A contraction rounded
# to bf16 misses these by ~100x (max|d| ~1e-2 on a v5e); see CHANGES.md
TOL = {"fused_ibn": 1e-4, "matmul_ln": 1e-4, "flash_attention": 1e-4,
       "rwkv_chunk": 1e-4, "depthwise_conv": 1e-5}
# EdgeNeXt-S logits: max |TPU - CPU| and max |ibn_chunks=4 - plain|,
# relative to the largest CPU logit (both in float32 under "highest")
MODEL_RTOL = 1e-4
CHUNKS_RTOL = 1e-5


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def check_device():
    devices = jax.devices()
    print(f"devices: {devices}")
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found platform {dev.platform!r}")
    return dev


def serve():
    """Serial lookups through the serving store; returns
    ``[(workload, layers, schedule)]``."""
    store = ServeStore(ROOT / ".search-cache")
    served = []
    for workload, batch in REQUESTS:
        t0 = time.perf_counter()
        r = store.request(workload, batch)
        host_s = time.perf_counter() - t0
        kinds = Counter(v["kernel"] for v in r.schedule.lowered.values())
        print(f"serve {r.workload}: outcome={r.outcome} "
              f"degraded={r.degraded} host_s={host_s} "
              f"lowered={dict(sorted(kinds.items()))}")
        if r.degraded:
            fail(f"degraded lookup for {r.workload}: {r.outcome} "
                 f"({r.error})")
        _, layers, _ = store.resolve(workload, batch)
        served.append((workload, layers, r.schedule))
    return served


# ---------------------------------------------------------------------------
# kernel cases: (kernel, label, kernel fn, oracle fn, operands)
# ---------------------------------------------------------------------------


def _normal(key, shape, scale=1.0):
    return scale * jax.random.normal(key, shape, jnp.float32)


def _ibn_case(key, layers, params, act):
    expand, project = layers
    m, d, f = expand.b * expand.ox * expand.oy, expand.c, expand.k
    ks = jax.random.split(key, 3)
    args = (_normal(ks[0], (m, d)), _normal(ks[1], (d, f), d ** -0.5),
            _normal(ks[2], (f, project.k), f ** -0.5))
    return (functools.partial(ops.fused_ibn, activation=act,
                              block_m=params["block_m"],
                              block_f=params["block_f"], interpret=False),
            functools.partial(ref.fused_ibn_ref, activation=act), args)


def _matmul_ln_case(key, layers, params, act):
    mac = layers[0]
    m, k, n = mac.b * mac.ox * mac.oy, mac.c * mac.fx * mac.fy, mac.k
    ks = jax.random.split(key, 5)
    args = (_normal(ks[0], (m, k)), _normal(ks[1], (k, n), k ** -0.5),
            _normal(ks[2], (n,), 0.1), 1.0 + _normal(ks[3], (n,), 0.1),
            _normal(ks[4], (n,), 0.1))
    return (functools.partial(ops.matmul_ln, block_m=params["block_m"],
                              block_k=params["block_k"], interpret=False),
            ref.matmul_ln_ref, args)


def _attention_case(key, layers, params, act):
    """XCA: the "tokens" are the C/h channels of a head, the head dim is
    the pixel count; q and k are L2-normalized, the scale is the learned
    temperature (1 at init), and nothing is masked."""
    qk = layers[0]
    ks = jax.random.split(key, 3)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    args = (unit(_normal(ks[0], (1, qk.b, qk.ox, qk.c))),
            unit(_normal(ks[1], (1, qk.b, qk.k, qk.c))),
            _normal(ks[2], (1, qk.b, qk.k, qk.c)))
    return (functools.partial(ops.flash_attention, causal=False, scale=1.0,
                              block_q=params["block_q"],
                              block_k=params["block_k"], interpret=False),
            functools.partial(ref.attention_ref, causal=False, scale=1.0),
            args)


def _wkv_case(key, layers, params, act):
    scan = layers[0]
    bh, t, k, v = scan.b, scan.ox, scan.c, scan.k
    ks = jax.random.split(key, 5)
    args = (_normal(ks[0], (bh, t, k), k ** -0.5),
            _normal(ks[1], (bh, t, k), k ** -0.5),
            _normal(ks[2], (bh, t, v)),
            -jnp.exp(_normal(ks[3], (bh, t, k), 0.5) - 1.0),   # log-decay
            _normal(ks[4], (bh, k), 0.1))
    return (functools.partial(ops.wkv_chunked, chunk=params["chunk"],
                              interpret=False), ref.wkv_ref, args)


CASE_MAKERS = {"fused_ibn": _ibn_case, "matmul_ln": _matmul_ln_case,
            "flash_attention": _attention_case, "rwkv_chunk": _wkv_case}


def lowered_cases(served):
    """One case per distinct (kernel, layer shapes, launch parameters):
    shapes come from the ``Layer``s each lowered key names, at the
    served batch."""
    seen, cases = set(), []
    for workload, layers, sched in served:
        by_name = {l.name: l for l in layers}
        for names, lk in sched.lowered.items():
            group = tuple(by_name[n] for n in names.split(" + "))
            params = {p: v for p, v in lk.items()
                      if p not in ("kernel", "ragged")}
            act = ACTIVATION[workload]
            ident = (lk["kernel"], tuple(l.signature for l in group),
                     tuple(sorted(params.items())), act)
            if ident in seen:
                continue
            seen.add(ident)
            key = jax.random.fold_in(jax.random.PRNGKey(SEED), len(cases))
            make = CASE_MAKERS[lk["kernel"]]
            kern, oracle, args = make(key, group, params, act)
            label = (f"{lk['kernel']} {workload}:{names} "
                     f"{[tuple(a.shape) for a in args]} "
                     f"{dict(sorted(params.items()))}")
            cases.append((lk["kernel"], label, kern, oracle, args))
    return cases


def depthwise_cases():
    """Every distinct depthwise shape of EdgeNeXt-S at batch 16: the
    stage kernels 3/5/7/9 and the SDTA split widths."""
    shapes = sorted({(l.b, l.oy, l.ox, l.c, l.fy, l.fx)
                     for l in get_workload(DEPTHWISE_WORKLOAD)
                     if l.op == DWCONV})
    cases = []
    for i, (b, h, w, c, fy, fx) in enumerate(shapes):
        ks = jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(SEED + 1), i), 3)
        args = (_normal(ks[0], (b, h, w, c)),
                _normal(ks[1], (fy, fx, c), 1.0 / fy),
                _normal(ks[2], (c,), 0.1))
        cases.append(("depthwise_conv",
                      f"depthwise_conv {[tuple(a.shape) for a in args]}",
                      functools.partial(ops.depthwise_conv2d,
                                        interpret=False),
                      ref.depthwise_conv2d_ref, args))
    return cases


def max_abs_diff(got, want) -> float:
    return max(float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                     - w.astype(jnp.float32))))
               for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def run_case(case) -> float:
    """Compile one kernel for the chip, run it and its oracle, and
    return max |kernel - oracle|."""
    kernel, label, kern, oracle, args = case
    t0 = time.perf_counter()
    compiled = jax.jit(kern).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    if "tpu_custom_call" not in compiled.as_text():
        fail(f"{label}: no tpu_custom_call in the compiled program")
    got = compiled(*args)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(oracle)(*args)
    delta = max_abs_diff(got, want)
    scale = max(float(jnp.max(jnp.abs(w))) for w in jax.tree.leaves(want))
    print(f"kernel {label}: max|d|={delta} tol={TOL[kernel]} "
          f"max|ref|={scale} compile_s={compile_s}")
    return delta


def kernels(served):
    for case in lowered_cases(served) + depthwise_cases():
        delta = run_case(case)
        if not delta <= TOL[case[0]]:          # NaN fails too
            fail(f"{case[1]}: max|d| {delta} > {TOL[case[0]]}")


def model(dev):
    params = param_lib.init_params(jax.random.PRNGKey(SEED),
                                   edgenext.param_defs(CONFIG))
    images = _normal(jax.random.PRNGKey(SEED + 2),
                     (MODEL_BATCH, CONFIG.img_size, CONFIG.img_size,
                      CONFIG.in_channels))
    fwd = jax.jit(functools.partial(edgenext.forward, CONFIG))
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        compiled = fwd.lower(params, images).compile()
        compile_s = time.perf_counter() - t0
        tpu = compiled(params, images).block_until_ready()
        times = []
        for _ in range(TIMED_CALLS):
            t0 = time.perf_counter()
            compiled(params, images).block_until_ready()
            times.append(time.perf_counter() - t0)
        chunked = jax.jit(functools.partial(edgenext.forward, CONFIG,
                                            ibn_chunks=4))(params, images)
        host = fwd(*jax.device_put((params, images), jax.devices("cpu")[0]))
    if list(tpu.devices())[0].platform != "tpu":
        fail("the forward pass did not run on the TPU")
    if tpu.shape != (MODEL_BATCH, CONFIG.num_classes):
        fail(f"logits shape {tpu.shape}")
    if not bool(jnp.isfinite(tpu).all()):
        fail("non-finite logits on the TPU")
    scale = float(jnp.max(jnp.abs(host)))
    d_host = max_abs_diff(tpu, jax.device_put(host, dev))
    d_chunk = max_abs_diff(chunked, tpu)
    print(f"model edgenext-s b={MODEL_BATCH}: max|logit|={scale} "
          f"max|tpu-cpu|={d_host} tol={MODEL_RTOL * scale} "
          f"max|chunks4-plain|={d_chunk} tol={CHUNKS_RTOL * scale}")
    print(f"smoke reading, not a metric: edgenext-s forward "
          f"b={MODEL_BATCH} (highest precision) on {dev.device_kind}: "
          f"compile_s={compile_s} median_s_of_{TIMED_CALLS}="
          f"{statistics.median(times)}")
    if not d_host <= MODEL_RTOL * scale:
        fail(f"TPU logits differ from CPU logits by {d_host}")
    if not d_chunk <= CHUNKS_RTOL * scale:
        fail(f"ibn_chunks=4 logits differ from plain by {d_chunk}")


def main() -> None:
    print(f"compile cache: {setup_compile_cache()}")
    dev = check_device()
    served = serve()
    kernels(served)
    model(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
