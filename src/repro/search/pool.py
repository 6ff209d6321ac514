"""Process pools for host-side search work.

Searches are pure host computation, but their modules import JAX.  A
pool worker that initialized a backend on a TPU host would contend with
its parent for the chip, and a forked worker inherits the parent's
threads and JAX state.  So workers are spawned (a fresh interpreter)
and pin JAX to the CPU before anything in them can initialize a
backend.
"""
from __future__ import annotations

import multiprocessing
from concurrent.futures import ProcessPoolExecutor


def _pin_cpu() -> None:
    import jax
    jax.config.update("jax_platforms", "cpu")


def cpu_process_pool(max_workers: int) -> ProcessPoolExecutor:
    """A ``spawn`` pool whose workers can never take the chip."""
    return ProcessPoolExecutor(
        max_workers=max_workers,
        mp_context=multiprocessing.get_context("spawn"),
        initializer=_pin_cpu)
