"""GPipe-style pipeline parallelism over the ``model`` mesh axis.

The fixed production mesh (data=16, model=16) supports a fourth sharding
profile in spirit: stages ride the `model` axis — device s holds layers
[s·L/S, (s+1)·L/S) — and microbatches stream through the ring with
``lax.ppermute``.  Fill/drain bubbles cost (S−1)/(M+S−1) of the schedule;
with M=4·S microbatches the bubble is ~6%.

This is a self-contained, autodiff-compatible building block (ppermute
transposes to the reverse permute, so jax.grad runs 1F1B-equivalent
backward through the same ring); the dense TransformerLM block is the
demonstration workload (tests/test_pipeline.py validates exact
equivalence with sequential layer execution and gradient flow).

Why not a default profile: at 16 stages the bubble + per-microbatch
collective latency loses to FSDP for every assigned arch that fits in
HBM (all of them); PP becomes the right
tool when layer weights exceed a chip (≫15B dense at f32) — the
mechanism is here for that regime.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

Pytree = Any


def split_stages(params: Pytree, n_stages: int) -> Pytree:
    """[L, ...]-stacked layer params -> [n_stages, L/S, ...]."""
    def resh(p):
        L = p.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return p.reshape(n_stages, L // n_stages, *p.shape[1:])
    return jax.tree.map(resh, params)


def gpipe(
    block_fn: Callable[[Pytree, jax.Array], jax.Array],
    stage_params: Pytree,
    x_micro: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "model",
) -> jax.Array:
    """Run microbatches through the layer pipeline.

    block_fn      : (layer_params_slice [L/S, ...], h) -> h  (one stage =
                    a scan over its L/S layers, supplied by the caller)
    stage_params  : [S, L/S, ...] leaves (use ``split_stages``)
    x_micro       : [M, B_micro, ...] microbatched input
    Returns [M, B_micro, ...] outputs (same order as inputs).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_stages = sizes[axis]
    M = x_micro.shape[0]
    T = M + n_stages - 1                      # fill + steady + drain
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    p_specs = jax.tree.map(lambda _: P(axis), stage_params)
    x_spec = P()                              # microbatches replicated in;
    o_spec = P()                              # outputs gathered at the end

    def stage_prog(params_s, xm):
        # params_s: [1, L/S, ...] (this stage's slice); xm: [M, B, ...]
        my = jax.tree.map(lambda p: p[0], params_s)
        s = lax.axis_index(axis)
        h0 = jnp.zeros_like(xm[0])

        def step(carry, t):
            h_in = carry
            # stage 0 injects microbatch t while t < M
            inj = xm[jnp.minimum(t, M - 1)]
            h_cur = jnp.where(s == 0, jnp.where(t < M, inj, h_in), h_in)
            h_out = block_fn(my, h_cur)
            # emit: the LAST stage's output for microbatch t-(S-1)
            emit = h_out
            h_next = lax.ppermute(h_out, axis, perm)
            return h_next, emit

        _, emitted = lax.scan(step, h0, jnp.arange(T))
        # emitted: [T, B, ...] per stage; microbatch m finishes on the
        # last stage at t = m + S - 1
        out = emitted[n_stages - 1:]
        # only the last stage's values are the real outputs — broadcast
        # them to every device so out_specs can be replicated
        last = n_stages - 1
        out = lax.psum(
            jnp.where(s == last, out, jnp.zeros_like(out)), axis)
        return out

    from repro.runtime.sharding import shard_map
    return shard_map(
        stage_prog, mesh=mesh,
        in_specs=(p_specs, x_spec),
        out_specs=o_spec)(stage_params, x_micro)


def data_parallel(
    fn: Callable[[Pytree, Pytree], Pytree],
    *,
    mesh: Mesh,
    axis: str = "data",
) -> Callable[[Pytree, Pytree], Pytree]:
    """Data-parallel fan-out of a batched function over one mesh axis.

    fn(params, x) -> y, with every leaf of ``x`` and ``y`` batched on
    dim 0.  Params are replicated; the batch dim is sharded over
    ``axis``, so each device runs fn on its own B/devices shard — the
    serving policy's fan-out primitive (``serve.policy`` serves a
    batch-b arrival group as ``devices`` shards of the co-searched
    batch-b/devices schedule, and this is the launcher that does it).

    The global batch must divide the axis size; serving always has that
    by construction (the policy only fans out when it does).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = sizes[axis]

    from repro.runtime.sharding import shard_map
    sharded = shard_map(fn, mesh=mesh,
                        in_specs=(P(), P(axis)), out_specs=P(axis))

    def wrapped(params: Pytree, x: Pytree) -> Pytree:
        B = jax.tree.leaves(x)[0].shape[0]
        if B % n != 0:
            raise ValueError(
                f"batch {B} not divisible by {axis}={n} shards")
        return sharded(params, x)

    return wrapped


def microbatch(x: jax.Array, n_micro: int) -> jax.Array:
    """[B, ...] -> [M, B/M, ...]."""
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    return x.reshape(n_micro, B // n_micro, *x.shape[1:])


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    """GPipe schedule overhead: (S-1) / (M + S - 1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
