#!/usr/bin/env python3
"""Sweep ``stacked_proj``'s weight blocks on a TPU, at RWKV-6 1.6B's shapes.

    python3 tools/tune_stacked_proj.py [--out bench-out/tune.json]

For each projection shape of the model (K x N = 2048x2048, 2048x7168,
7168x2048; M = 512 rows, a 24-layer f32 stack) it times a 24-layer scan
of the kernel at each candidate (block_k, block_n), fitted to the
extents as ``kernels.ops`` fits its own blocks, and of XLA's slice,
cast and matmul for comparison, in ms a layer (median of 15 calls after
one to compile).  A candidate Mosaic refuses (scoped VMEM) is reported
and skipped.  Then it times the served forward (512-token prompt, f32
weights, bf16 compute) at the blocks ``kernels.ops`` uses.
"""
from __future__ import annotations

import argparse
import functools
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.kernels import ops, stacked_proj as sp  # noqa: E402

L, M = 24, 512
CANDIDATES = [(256, 2048), (512, 512), (512, 1024), (512, 2048),
              (1024, 512), (1024, 1024), (1024, 2048), (2048, 512)]


def median_s(f, *args, n: int = 15) -> float:
    jax.block_until_ready(f(*args))
    times = []
    for _ in range(n):
        t = time.perf_counter()
        jax.block_until_ready(f(*args))
        times.append(time.perf_counter() - t)
    return statistics.median(times)


def layer_scan(proj):
    """A jitted scan of ``proj(x, w, layer)`` over every layer."""
    def run(x, w):
        def body(acc, layer):
            return acc + proj(x, w, layer).astype(jnp.float32).sum(), None
        return lax.scan(body, jnp.float32(0), jnp.arange(L))[0]
    return jax.jit(run)


def xla_proj(x, w, layer):
    return x @ lax.dynamic_index_in_dim(w, layer, keepdims=False).astype(
        x.dtype)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", type=Path,
                    default=Path("bench-out/tune_stacked_proj.json"))
    args = ap.parse_args()
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    key = jax.random.PRNGKey(0)
    rows = []
    for K, N in [(2048, 2048), (2048, 7168), (7168, 2048)]:
        w = jax.random.normal(key, (L, K, N), jnp.float32) * 0.02
        x = jax.random.normal(key, (M, K), jnp.float32).astype(jnp.bfloat16)
        t = median_s(layer_scan(xla_proj), x, w) / L
        rows.append({"K": K, "N": N, "blocks": "xla", "ms": t * 1e3})
        print(f"K{K} N{N} xla {t * 1e3:.4f} ms/layer", flush=True)
        for bk, bn in CANDIDATES:
            # as the wrapper does: a block over the extent becomes the
            # largest multiple of 128 under it that divides the extent
            bk, bn = ops._lane_block(K, bk), ops._lane_block(N, bn)
            proj = functools.partial(sp.stacked_proj, block_m=M,
                                     block_k=bk, block_n=bn)
            try:
                t = median_s(layer_scan(proj), x, w) / L
            except jax.errors.JaxRuntimeError as e:
                print(f"K{K} N{N} {bk}x{bn} refused: {str(e)[:120]}",
                      flush=True)
                continue
            rows.append({"K": K, "N": N, "blocks": f"{bk}x{bn}",
                         "ms": t * 1e3})
            print(f"K{K} N{N} {bk}x{bn} {t * 1e3:.4f} ms/layer "
                  f"{K * N * 4 / t / 1e9:.1f} GB/s of f32 weights "
                  f"{2 * M * K * N / t / 1e12:.1f} TF/s", flush=True)
        del w, x

    from repro.configs.rwkv6_1_6b import CONFIG as cfg
    from repro.models import rwkv6
    from repro.models.params import ParamDef
    defs = rwkv6.param_defs(cfg)
    leaves, tree = jax.tree.flatten(
        defs, is_leaf=lambda d: isinstance(d, ParamDef))
    params = jax.tree.unflatten(tree, [
        jax.random.normal(k, d.shape, jnp.float32) * 0.02
        for k, d in zip(jax.random.split(key, len(leaves)), leaves)])
    tokens = jax.random.randint(key, (1, 512), 0, cfg.vocab_size)

    @jax.jit
    def served(p, t):
        hidden, _ = rwkv6.forward(cfg, p, {"tokens": t})
        return rwkv6.logits_fn(cfg, p, hidden[:, -1:, :])

    t = median_s(served, params, tokens, n=30)
    rows.append({"blocks": "forward", "ms": t * 1e3})
    print(f"forward {t * 1e3:.3f} ms/prompt ({512 / t:.0f} tokens/s)",
          flush=True)
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(rows, indent=1))


if __name__ == "__main__":
    main()
