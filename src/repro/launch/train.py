"""Training launcher: any --arch on any mesh, fault-tolerant.

End-to-end: config -> model -> sharded params/optimizer -> deterministic
data pipeline -> jit train_step with explicit shardings -> loop with
straggler watchdog, async checkpointing, and crash-resume (restore picks
up at the exact step with the exact data batch).

CPU-scale example (the quickstart):
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
      --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import AsyncCheckpointer, latest_step, restore_sharded
from repro.configs import SHAPES_BY_NAME, get_config, reduced
from repro.data.synthetic import make_dataset
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import actshard, get_module, params as param_lib
from repro.optim import AdamWState, adamw_init, warmup_cosine
from repro.runtime import batch_pspecs, build_train_step, model_param_pspecs
from repro.runtime.compile_cache import setup_compile_cache
from repro.runtime.watchdog import StragglerWatchdog


def main() -> None:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU smoke scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--production-mesh", action="store_true",
                    help="16x16 mesh (needs 256 devices)")
    ap.add_argument("--ibn-chunks", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    actshard.set_mesh(mesh)
    mod = get_module(cfg)

    shape = dataclasses.replace(SHAPES_BY_NAME["train_4k"],
                                seq_len=args.seq, global_batch=args.batch)
    ds = make_dataset(cfg, shape, seed=args.seed,
                      process_index=jax.process_index(),
                      process_count=jax.process_count())

    defs = mod.param_defs(cfg)
    pspecs = model_param_pspecs(cfg, mesh, defs)
    named = lambda t: jax.tree.map(                       # noqa: E731
        lambda s: NamedSharding(mesh, s), t,
        is_leaf=lambda x: isinstance(x, P))

    print(f"arch={cfg.name} params={param_lib.count_params(defs)/1e6:.1f}M "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    params = jax.jit(
        lambda key: param_lib.init_params(key, defs),
        out_shardings=named(pspecs))(jax.random.PRNGKey(args.seed))
    opt_state = jax.jit(adamw_init,
                        out_shardings=named(AdamWState(
                            count=P(), m=pspecs, v=pspecs)))(params)

    step0 = 0
    ckpt = None
    if args.ckpt_dir:
        ckpt = AsyncCheckpointer(args.ckpt_dir)
        if latest_step(args.ckpt_dir) is not None:
            like = {"params": params, "opt": opt_state}
            shardings = {"params": named(pspecs),
                         "opt": named(AdamWState(count=P(), m=pspecs,
                                                 v=pspecs))}
            step0, restored = restore_sharded(args.ckpt_dir, like, shardings)
            params, opt_state = restored["params"], restored["opt"]
            print(f"resumed from step {step0}")

    train_step = build_train_step(
        cfg, lr_schedule=warmup_cosine(args.lr, args.warmup, args.steps),
        ibn_chunks=args.ibn_chunks)
    b_pspecs = None
    jit_step = None

    watchdog = StragglerWatchdog(
        on_escalate=lambda msg: print(f"[watchdog] ESCALATE: {msg}"))

    for step in range(step0, args.steps):
        batch_np = ds.batch(step)
        if jit_step is None:
            struct = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch_np)
            b_pspecs = batch_pspecs(cfg, mesh, struct)
            jit_step = jax.jit(
                train_step,
                in_shardings=(named(pspecs),
                              named(AdamWState(count=P(), m=pspecs,
                                               v=pspecs)),
                              named(b_pspecs)),
                donate_argnums=(0, 1))
        batch = {k: jax.device_put(v, NamedSharding(mesh, b_pspecs[k]))
                 for k, v in batch_np.items()}
        watchdog.start()
        params, opt_state, metrics = jit_step(params, opt_state, batch)
        jax.block_until_ready(metrics["loss"])
        dt = watchdog.stop(step)
        if step % args.log_every == 0 or step == args.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            print(f"step {step:5d} loss={m['loss']:.4f} ce={m['ce']:.4f} "
                  f"gnorm={m['grad_norm']:.3f} lr={m['lr']:.2e} "
                  f"dt={dt*1e3:.0f}ms")
        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step + 1, {"params": params, "opt": opt_state})
    if ckpt:
        ckpt.save(args.steps, {"params": params, "opt": opt_state})
        ckpt.wait()
    actshard.set_mesh(None)
    print("done")


if __name__ == "__main__":
    main()
