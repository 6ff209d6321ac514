"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests must see the real
single-device CPU backend; a test that needs more devices starts its
own process with its own flags."""
import jax
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def key():
    return jax.random.PRNGKey(0)


def assert_tree_allclose(a, b, rtol=1e-5, atol=1e-5):
    flat_a = jax.tree.leaves(a)
    flat_b = jax.tree.leaves(b)
    assert len(flat_a) == len(flat_b)
    for x, y in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(x, dtype=np.float32),
                                   np.asarray(y, dtype=np.float32),
                                   rtol=rtol, atol=atol)
