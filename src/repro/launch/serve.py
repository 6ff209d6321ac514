"""Serving launcher: batched prefill + greedy decode for any --arch.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \
      --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, reduced
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import actshard, get_module, params as param_lib
from repro.runtime import (build_decode_step, build_prefill_step,
                           model_param_pspecs)
from repro.runtime.compile_cache import setup_compile_cache


def main() -> None:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    actshard.set_mesh(mesh)
    mod = get_module(cfg)
    defs = mod.param_defs(cfg)
    pspecs = model_param_pspecs(cfg, mesh, defs)
    named = lambda t: jax.tree.map(                       # noqa: E731
        lambda s: NamedSharding(mesh, s), t,
        is_leaf=lambda x: isinstance(x, P))
    params = jax.jit(
        lambda key: param_lib.init_params(key, defs),
        out_shardings=named(pspecs))(jax.random.PRNGKey(args.seed))

    B, S = args.batch, args.prompt_len
    total = S + args.gen
    rng = np.random.default_rng(args.seed)
    batch = {"tokens": rng.integers(0, cfg.vocab_size, (B, S),
                                    dtype=np.int32)}
    if cfg.embedding_inputs:
        batch["inputs_embeds"] = rng.standard_normal(
            (B, S, cfg.d_model)).astype(np.float32)
        if cfg.family == "audio":
            batch["tokens"] = batch["tokens"][:, :1]

    prefill = build_prefill_step(cfg, decode_len=total)
    decode = build_decode_step(cfg)
    t0 = time.monotonic()
    last_hidden, cache = jax.jit(prefill)(params, batch)
    jax.block_until_ready(last_hidden)
    t_prefill = time.monotonic() - t0

    jit_decode = jax.jit(decode, donate_argnums=(1,))
    tok = jnp.zeros((B, 1), jnp.int32)
    outputs = []
    t0 = time.monotonic()
    for _ in range(args.gen):
        tok1, logits, cache = jit_decode(params, cache, {"tokens": tok})
        tok = tok1[:, None]
        # keep device arrays in the timed loop: np.asarray here would
        # force a host sync per token and inflate ms/tok
        outputs.append(tok1)
    jax.block_until_ready(outputs)
    t_decode = time.monotonic() - t0

    gen = np.stack([np.asarray(o) for o in outputs], axis=1)
    print(f"arch={cfg.name} prefill[{B}x{S}]={t_prefill*1e3:.0f}ms "
          f"decode {args.gen} steps={t_decode*1e3:.0f}ms "
          f"({t_decode/args.gen*1e3:.1f} ms/tok)")
    print("generated (first seq):", gen[0][:16].tolist())
    actshard.set_mesh(None)


if __name__ == "__main__":
    main()
