#!/usr/bin/env python3
"""Sweep the WKV kernel's step on a TPU, at RWKV-6 1.6B's prefill.

    python3 tools/tune_wkv.py [--out bench-out/tune_wkv.json]

At the served shapes (one 512-token prompt, 32 heads of 64, bf16 r/k/v,
f32 log-decays, chunk 64) it times ``kernels.rwkv_chunk.wkv`` at each
candidate (heads a step, chunks a step, sub-chunk) over 24 layers, each
on its own operands and starting from the state the layer before left,
in ms a layer (median host time of 15 calls after one to compile; the
dispatch of a call's 97 arguments is in it, so it ranks candidates, and
the benchmark's trace gives the kernel's device time); and the same of
XLA's chunked form (``models.rwkv6.wkv_chunked_xla``) for
comparison.  Each candidate's output is compared with XLA's form
computed in full f32 (max |d| over max |ref|, first layer).  A
candidate Mosaic refuses is reported and skipped.  Then it times
``kernels.ops.wkv`` as the model calls it, with bf16 and with f32
inputs (full-f32 contractions), and the served forward.
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

from repro.kernels import ops, rwkv_chunk as wk  # noqa: E402
from repro.models import rwkv6  # noqa: E402

L, B, T, H, K, CHUNK = 24, 1, 512, 32, 64, 64
HEADS = (2, 4, 8, 16, 32)
CHUNKS = (1, 2, 8)
SUBS = (8, 16, 32)
INTERPRET = False      # a rehearsal on the CPU sets it


def median_s(f, *args, n: int = 15) -> float:
    jax.block_until_ready(f(*args))
    times = []
    for _ in range(n):
        t = time.perf_counter()
        jax.block_until_ready(f(*args))
        times.append(time.perf_counter() - t)
    return statistics.median(times)


def layers(wkv):
    """A jitted run of ``wkv(state, *operands) -> (out, state)`` over
    every layer's own operands, each layer starting from the state the
    one before left.  Unrolled, not scanned: in a scan over fixed
    operands XLA would hoist the work that does not depend on the state
    out of the loop."""
    def run(state, operands):
        acc = jnp.float32(0)
        for ops_l in operands:
            out, state = wkv(state, *ops_l)
            acc = acc + out[0, -1].astype(jnp.float32).sum()
        return state, acc
    return jax.jit(run)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", type=Path,
                    default=Path("bench-out/tune_wkv.json"))
    args = ap.parse_args()
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    shape = (B, T, H, K)

    def operands(key):
        ks = jax.random.split(key, 4)
        r, k, v = (jax.random.normal(kk, shape).astype(jnp.bfloat16) * 0.3
                   for kk in ks[:3])
        return r, k, v, -jnp.exp(jax.random.normal(ks[3], shape) * 0.5 - 1)

    per_layer = [operands(kk)
                 for kk in jax.random.split(jax.random.PRNGKey(0), L)]
    u = jax.random.normal(jax.random.PRNGKey(1), (H, K)) * 0.3
    s0 = jnp.zeros((B, H, K, K), jnp.float32)
    rows = []

    def xla(st, r, k, v, logw):
        return rwkv6.wkv_chunked_xla(r, k, v, logw, u, st, CHUNK)
    t = median_s(layers(xla), s0, per_layer) / L
    rows.append({"form": "xla", "ms": t * 1e3})
    print(f"xla {t * 1e3:.4f} ms/layer", flush=True)
    r, k, v, logw = per_layer[0]
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda: rwkv6.wkv_chunked_xla(
            *(x.astype(jnp.float32) for x in (r, k, v)), logw, u, s0,
            CHUNK))()
    scale = float(jnp.abs(want).max())

    G = wk.group_width(H, K)
    flat = [[x.reshape(B, T, H * K) for x in ops_l] for ops_l in per_layer]
    uu = u.reshape(1, 1, H * K)
    g0 = wk.state_to_groups(s0, G)
    for hb, cps, sub in itertools.product(HEADS, CHUNKS, SUBS):
        def kern(st, r, k, v, logw, hb=hb, cps=cps, sub=sub):
            return wk.wkv(r, k, v, logw, uu, st, K=K, chunk=CHUNK,
                          heads_per_step=hb, chunks_per_step=cps, sub=sub,
                          interpret=INTERPRET)
        try:
            out, _ = jax.jit(kern)(g0, *flat[0])
            t = median_s(layers(kern), g0, flat) / L
        except jax.errors.JaxRuntimeError as e:
            print(f"heads {hb} chunks {cps} sub {sub} refused: "
                  f"{str(e)[:160]}", flush=True)
            continue
        err = float(jnp.abs(out.astype(jnp.float32).reshape(shape)
                            - want).max()) / scale
        rows.append({"heads": hb, "chunks": cps, "sub": sub,
                     "ms": t * 1e3, "rel_err": err})
        print(f"heads {hb} chunks {cps} sub {sub} {t * 1e3:.4f} ms/layer "
              f"rel_err {err:.3e}", flush=True)

    def served_wkv(st, r, k, v, logw):
        return ops.wkv(r, k, v, logw, u, st, chunk=CHUNK)
    f32 = [[x.astype(jnp.float32) for x in ops_l] for ops_l in per_layer]
    for name, operands_ in (("bf16", per_layer), ("f32", f32)):
        t = median_s(layers(served_wkv), s0, operands_) / L
        rows.append({"form": f"ops.wkv {name} inputs", "ms": t * 1e3})
        print(f"ops.wkv {name} inputs {t * 1e3:.4f} ms/layer", flush=True)
    del per_layer, flat, f32

    from repro.configs.rwkv6_1_6b import CONFIG as cfg
    from repro.models.params import ParamDef
    defs = rwkv6.param_defs(cfg)
    leaves, tree = jax.tree.flatten(
        defs, is_leaf=lambda d: isinstance(d, ParamDef))
    key = jax.random.PRNGKey(1)
    params = jax.tree.unflatten(tree, [
        jax.random.normal(kk, d.shape, jnp.float32) * 0.02
        for kk, d in zip(jax.random.split(key, len(leaves)), leaves)])
    tokens = jax.random.randint(key, (1, T), 0, cfg.vocab_size)

    @jax.jit
    def served(p, t):
        hidden, _ = rwkv6.forward(cfg, p, {"tokens": t})
        return rwkv6.logits_fn(cfg, p, hidden[:, -1:, :])

    t = median_s(served, params, tokens, n=30)
    rows.append({"form": "forward", "ms": t * 1e3})
    print(f"forward {t * 1e3:.3f} ms/prompt ({T / t:.0f} tokens/s)",
          flush=True)
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(rows, indent=1))


if __name__ == "__main__":
    main()
