"""Seeded weights made on the device in one jitted call.

A configuration describes its weights as a pytree of ``Leaf``s (shape,
distribution, type).  ``make`` draws every leaf from one key in a single
jitted program, so the arrays are created on the device in the type they
are served in, and the same seed gives the same weights.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import jax

from traffic import rng

POOL = 1 << 20


@dataclasses.dataclass(frozen=True)
class Leaf:
    shape: Tuple[int, ...]
    dist: str = "normal"       # "normal": mean + std * N(0, 1)
    a: float = 0.0             # normal: std;  uniform: low
    b: float = 0.0             # normal: mean; uniform: high
    dtype: str = "float32"


def normal(shape, std: float, mean: float = 0.0) -> Leaf:
    return Leaf(tuple(shape), "normal", std, mean)


def fan_in(shape, fan: int = 0, gain: float = 1.0) -> Leaf:
    """N(0, gain^2 / fan), fan defaulting to all but the last dim."""
    fan = fan or math.prod(shape[:-1])
    return normal(shape, gain / math.sqrt(fan))


def uniform(shape, low: float, high: float) -> Leaf:
    return Leaf(tuple(shape), "uniform", low, high)


def _is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def key(seed: int) -> jax.Array:
    return jax.random.key(int(rng(seed, 3).integers(2 ** 31)))


def _scaled(leaf: Leaf, unit):
    """A standard normal or uniform draw mapped onto the leaf."""
    if leaf.dist == "normal":
        return leaf.b + leaf.a * unit
    return leaf.a + (leaf.b - leaf.a) * unit


def make(spec: Any, seed: int, dtype=None) -> Any:
    """Every leaf of ``spec`` drawn on the device in one jitted call.
    Leaves under ``POOL`` elements share one draw per distribution (a
    draw per leaf would make a program of hundreds of generators, slow
    to build); larger leaves get a draw each."""
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)

    def draw(k):
        k_big, k_normal, k_uniform = jax.random.split(k, 3)
        pooled = {}
        for dist, kk in (("normal", k_normal), ("uniform", k_uniform)):
            n = sum(math.prod(x.shape) for x in leaves
                    if x.dist == dist and math.prod(x.shape) < POOL)
            pooled[dist] = (jax.random.normal(kk, (n,)) if dist == "normal"
                            else jax.random.uniform(kk, (n,)))
        offset = {"normal": 0, "uniform": 0}
        out = []
        for i, leaf in enumerate(leaves):
            n = math.prod(leaf.shape)
            if n < POOL:
                o = offset[leaf.dist]
                unit = pooled[leaf.dist][o:o + n].reshape(leaf.shape)
                offset[leaf.dist] = o + n
            else:
                kk = jax.random.fold_in(k_big, i)
                unit = (jax.random.normal(kk, leaf.shape)
                        if leaf.dist == "normal"
                        else jax.random.uniform(kk, leaf.shape))
            out.append(_scaled(leaf, unit).astype(dtype or leaf.dtype))
        return jax.tree.unflatten(treedef, out)
    return jax.jit(draw)(key(seed))


def shapes(spec: Any) -> Any:
    return jax.tree.map(lambda leaf: leaf.shape, spec, is_leaf=_is_leaf)
