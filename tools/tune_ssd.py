#!/usr/bin/env python3
"""Sweep the SSD kernel's step on a TPU, at Granite 4.0-H Micro's prefill.

    python3 tools/tune_ssd.py [--out bench-out/tune_ssd.json]

At the served shapes (one 8192-token prompt, 64 SSD heads of 64, d_state
128, chunk 256, the conv's bf16 output ``xbc`` [1, 8192, 4352] and f32
``dt``) it times ``kernels.ssd_chunk.ssd`` at each candidate (heads a
step, sub-chunk; a sub-chunk of 256 is the elementwise form) over the 9
Mamba-2 layers of a period, each on its own operands, in ms a layer
(median host time of 15 calls after one to compile; it ranks
candidates, and the benchmark's trace gives the kernel's device time),
with the seconds its first call took to trace and compile; and the same
of XLA's chunked form (``models.mamba_hybrid.ssd_xla``).  Each
candidate's output is compared with XLA's form computed in full f32
(max |d| over max |ref|, first layer).  A candidate Mosaic refuses is
reported and skipped.  Then it runs the served forward with the kernel
and with XLA's form: the first call (warm request 0: trace, compile and
one prompt) and the median prompt after it.
"""
from __future__ import annotations

import argparse
import itertools
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.kernels import ssd_chunk as sc  # noqa: E402
from repro.models import mamba_hybrid  # noqa: E402

LAYERS, B, T, H, P, N, CHUNK = 9, 1, 8192, 64, 64, 128, 256
HEADS = (4, 8, 16, 32, 64)
SUBS = (16, 32, 64, 128, 256)
INTERPRET = False      # a rehearsal on the CPU sets it


def median_s(f, *args, n: int = 15) -> float:
    jax.block_until_ready(f(*args))
    times = []
    for _ in range(n):
        t = time.perf_counter()
        jax.block_until_ready(f(*args))
        times.append(time.perf_counter() - t)
    return statistics.median(times)


def layers(ssd):
    """A jitted run of ``ssd(*operands) -> (y, state)`` over every
    layer's own operands, each reduced to a scalar."""
    def run(operands):
        acc = jnp.float32(0)
        for ops_l in operands:
            y, _ = ssd(*ops_l)
            acc = acc + y[0, -1].sum()
        return acc
    return jax.jit(run)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", type=Path,
                    default=Path("bench-out/tune_ssd.json"))
    args = ap.parse_args()
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)

    def operands(key):
        ks = jax.random.split(key, 4)
        xbc = jax.nn.silu(jax.random.normal(ks[0], (B, T, H * P + 2 * N))
                          ).astype(jnp.bfloat16)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 4.0)
        A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
        D = jax.random.uniform(ks[3], (H,), minval=0.5, maxval=1.5)
        return xbc, dt, A, D

    per_layer = [operands(k)
                 for k in jax.random.split(jax.random.PRNGKey(0), LAYERS)]
    sizes = (H, P, N, CHUNK)
    rows = []

    def xla(xbc, dt, A, D):
        return mamba_hybrid.ssd_xla(xbc, dt, A, D, None, sizes)
    t = median_s(layers(xla), per_layer) / LAYERS
    rows.append({"form": "xla", "ms": t * 1e3})
    print(f"xla {t * 1e3:.4f} ms/layer", flush=True)
    xbc, dt, A, D = per_layer[0]
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda: mamba_hybrid.ssd_xla(
            xbc.astype(jnp.float32), dt, A, D, None, sizes))()
    scale = float(jnp.abs(want).max())

    for hb, sub in itertools.product(HEADS, SUBS):
        def kern(xbc, dt, A, D, hb=hb, sub=sub):
            return sc.ssd(xbc, dt, A, D, None, d_state=N, chunk=CHUNK,
                          heads_per_step=hb, sub=sub, interpret=INTERPRET)
        try:
            t0 = time.perf_counter()
            out, _ = jax.block_until_ready(jax.jit(kern)(*per_layer[0]))
            first = time.perf_counter() - t0
            t = median_s(layers(kern), per_layer) / LAYERS
        except jax.errors.JaxRuntimeError as e:
            print(f"heads {hb} sub {sub} refused: {str(e)[:160]}",
                  flush=True)
            continue
        err = float(jnp.abs(out - want).max()) / scale
        rows.append({"heads": hb, "sub": sub, "ms": t * 1e3,
                     "first_call_s": first, "rel_err": err})
        print(f"heads {hb} sub {sub} {t * 1e3:.4f} ms/layer, first call "
              f"{first:.2f} s, rel_err {err:.3e}", flush=True)
    del per_layer, want

    from repro.configs.granite_4_0_h_micro import CONFIG as cfg
    from repro.models.params import ParamDef
    defs = mamba_hybrid.param_defs(cfg)
    leaves, tree = jax.tree.flatten(
        defs, is_leaf=lambda d: isinstance(d, ParamDef))
    key = jax.random.PRNGKey(1)
    params = jax.tree.unflatten(tree, [
        (jax.random.normal(kk, d.shape, jnp.bfloat16) * 0.02)
        for kk, d in zip(jax.random.split(key, len(leaves)), leaves)])
    tokens = jax.random.randint(key, (1, T), 0, cfg.vocab_size)
    kernel = mamba_hybrid._ssd_kernel
    for form in ("kernel", "xla"):
        mamba_hybrid._ssd_kernel = kernel if form == "kernel" else \
            mamba_hybrid.ssd_xla

        @jax.jit
        def served(p, t):
            hidden, _ = mamba_hybrid.forward(cfg, p, {"tokens": t})
            return mamba_hybrid.logits_fn(cfg, p, hidden[:, -1:, :])

        t0 = time.perf_counter()
        jax.block_until_ready(served(params, tokens))
        first = time.perf_counter() - t0
        t = median_s(served, params, tokens, n=10)
        rows.append({"form": f"forward, {form}", "ms": t * 1e3,
                     "first_call_s": first})
        print(f"forward, {form}: {t * 1e3:.2f} ms/prompt ({T / t:.0f} "
              f"tokens/s), first call {first:.2f} s", flush=True)
    mamba_hybrid._ssd_kernel = kernel
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(rows, indent=1))


if __name__ == "__main__":
    main()
