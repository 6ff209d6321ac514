"""One general generator for every traffic mix.

A mix is a JSON file ``bench/traffic/<name>.json`` of parameters:

    kind        "closed": one client sends its next request when the
                previous one has returned; "poisson": an open loop of
                requests due at Poisson arrival times, served whether or
                not the server keeps up
    batch       items per request (images, or prompts of ``length``): a
                whole number, or ``{"values": [...], "weights": [...]}``
    length      tokens per prompt (token configurations only), the same
                two forms
    pool        distinct inputs made at set-up; requests draw from it
    rate_per_s  mean arrivals per second ("poisson")
    profile     ("poisson", optional) ``[[seconds, relative rate], ...]``,
                repeated through the window: bursts, on/off sources;
                by default the rate is constant

Everything is drawn from the seed, and every seed gets the same work.
The open loop sends ``n = round(rate * seconds)`` requests, all due
inside the window: its gaps, in the profile's time, are the same set of
exponential quantiles ``-ln(1 - (i + 1/2) / n)`` in a seeded order,
mapped onto the clock through the profile's cumulative rate.  Shapes
(batch, length) come in exact proportion to their weights, in a seeded
order: over the ``n`` requests of an open loop, and over a cycle of
``CYCLE`` requests that a closed loop repeats.  A request of a smaller
shape takes the leading part of a pool entry of the largest one.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
from pathlib import Path
from typing import Iterator, List, Optional, Tuple

import numpy as np

KINDS = ("closed", "poisson")
CYCLE = 128               # shapes a closed loop repeats


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due: Optional[float]   # seconds after the window opens; None: closed
    batch: int
    length: int
    input: int             # index into the input pool


def load(path) -> dict:
    spec = json.loads(Path(path).read_text())
    validate(spec)
    return spec


def _choices(value, key: str) -> Tuple[List[int], List[float]]:
    """A size's values and weights: a whole number is one value."""
    if isinstance(value, int):
        value = {"values": [value], "weights": [1.0]}
    if not isinstance(value, dict):
        raise ValueError(f"traffic {key!r}: a whole number or "
                         "{'values': [...], 'weights': [...]}")
    vals, w = value.get("values", []), value.get("weights", [])
    if not (vals and len(vals) == len(w)
            and all(isinstance(v, int) and v >= 0 for v in vals)
            and all(x >= 0 for x in w) and sum(w) > 0):
        raise ValueError(f"traffic {key!r}: whole values >= 0, as many "
                         f"non-negative weights, some above 0: {value}")
    return list(vals), [float(x) for x in w]


def validate(spec: dict) -> None:
    if spec.get("kind") not in KINDS:
        raise ValueError(f"traffic kind must be one of {KINDS}: {spec}")
    if not (isinstance(spec.get("pool"), int) and spec["pool"] >= 1):
        raise ValueError("traffic needs a positive integer 'pool'")
    if "batch" not in spec:
        raise ValueError("traffic needs a 'batch'")
    if min(_choices(spec["batch"], "batch")[0]) < 1:
        raise ValueError("traffic 'batch' values must be >= 1")
    _choices(spec.get("length", 0), "length")
    if spec["kind"] == "poisson":
        if not spec.get("rate_per_s", 0) > 0:
            raise ValueError("poisson traffic needs rate_per_s > 0")
        prof = spec.get("profile", [[1.0, 1.0]])
        if not (prof and all(len(p) == 2 and p[0] > 0 and p[1] >= 0
                             for p in prof)
                and any(p[1] > 0 for p in prof)):
            raise ValueError("traffic 'profile': [[seconds > 0, relative "
                             f"rate >= 0], ...], some rate above 0: {prof}")


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per purpose; any non-negative seed."""
    return np.random.default_rng([stream, int(seed)])


def shapes(spec: dict) -> List[Tuple[int, int]]:
    """Every (batch, length) the mix sends, the ones to warm up."""
    bs, bw = _choices(spec["batch"], "batch")
    ls, lw = _choices(spec.get("length", 0), "length")
    return sorted({(b, n) for b, x in zip(bs, bw) if x > 0
                   for n, y in zip(ls, lw) if y > 0})


def largest(spec: dict) -> Tuple[int, int]:
    """The pool entries' (batch, length): the largest sent."""
    s = shapes(spec)
    return max(b for b, _ in s), max(n for _, n in s)


def _exact(vals, weights, count: int) -> np.ndarray:
    """``count`` values in exact proportion to the weights (largest
    remainders), in order of value."""
    w = np.asarray(weights) / sum(weights)
    k = np.floor(w * count).astype(int)
    k[np.argsort(-(w * count - k), kind="stable")[:count - k.sum()]] += 1
    return np.repeat(vals, k)


def shape_sequence(spec: dict, count: int, seed: int) -> np.ndarray:
    """``count`` (batch, length) pairs, in a seeded order."""
    bs, bw = _choices(spec["batch"], "batch")
    ls, lw = _choices(spec.get("length", 0), "length")
    r = rng(seed, 3)
    batch = r.permutation(_exact(bs, bw, count))
    length = r.permutation(_exact(ls, lw, count))
    return np.stack([batch, length], axis=1)


def poisson_due(spec: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times of an open loop, in seconds after the window opens."""
    prof = np.asarray(spec.get("profile", [[1.0, 1.0]]), dtype=float)
    span, rel = prof[:, 0], prof[:, 1] / (prof[:, 0] @ prof[:, 1]
                                         / prof[:, 0].sum())
    cycles = int(np.ceil(seconds / span.sum())) + 1
    t = np.concatenate([[0.0], np.cumsum(np.tile(span, cycles))])
    lam = np.concatenate([[0.0], np.cumsum(np.tile(span * rel, cycles))])
    lam *= spec["rate_per_s"]              # expected arrivals by t
    n = max(1, round(float(np.interp(seconds, t, lam))))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    return np.interp(np.cumsum(rng(seed, 1).permutation(gaps)), lam, t)


def requests(spec: dict, seed: int, seconds: float) -> Iterator[Request]:
    """The requests of one run, in the order they are sent; endless for
    a closed loop."""
    order = rng(seed, 2).permutation(spec["pool"])
    if spec["kind"] == "closed":
        due = itertools.repeat(None)
        seq = shape_sequence(spec, CYCLE, seed)
    else:
        due = poisson_due(spec, seconds, seed)
        seq = shape_sequence(spec, len(due), seed)
    for i, d in enumerate(due):
        b, n = seq[i % len(seq)]
        yield Request(i, None if d is None else float(d), int(b), int(n),
                      int(order[i % len(order)]))
