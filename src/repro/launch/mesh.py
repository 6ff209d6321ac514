"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state: a process that wants placeholder
devices sets ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
before first jax init, and smoke tests must keep seeing 1 device.  Every
axis is ``AxisType.Auto``: the sharding rules in ``runtime.sharding``
place arrays with ``NamedSharding`` and let the compiler propagate the
rest.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh() -> Mesh:
    """One pod: (data=16, model=16) = 256 chips on ICI."""
    shape = (16, 16)
    n = shape[0] * shape[1]
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            "importing jax")
    return jax.make_mesh(shape, ("data", "model"), devices=devices[:n],
                         axis_types=(AxisType.Auto,) * 2)


def make_host_mesh(*, model: int = 1) -> Mesh:
    """Tiny mesh over the real host devices (tests / examples)."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=jax.devices()[: data * model],
                         axis_types=(AxisType.Auto,) * 2)
