"""Pipeline parallelism: GPipe ring == sequential layer execution,
gradients flow, bubble accounting."""
import subprocess
import sys
import textwrap

import pytest

from repro.runtime.pipeline import bubble_fraction

# JAX_PLATFORMS=cpu: the image ships libtpu; without the override the
# child process burns 60+s probing a TPU backend that does not exist.
ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
       "JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}


def _run(code: str) -> str:
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=ENV,
                       cwd="/root/repo", timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_gpipe_matches_sequential():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import lax
        from repro.runtime.pipeline import gpipe, microbatch, split_stages
        mesh = jax.make_mesh((1, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        L, D, B, M = 8, 16, 8, 4
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        W = jax.random.normal(ks[0], (L, D, D)) * (0.5 / D ** 0.5)
        x = jax.random.normal(ks[1], (B, D))

        def layer(w, h):
            return jnp.tanh(h @ w) + h

        def block_fn(ws, h):          # one stage = scan over its layers
            return lax.scan(lambda c, w: (layer(w, c), None), h, ws)[0]

        # sequential reference
        ref = lax.scan(lambda c, w: (layer(w, c), None), x, W)[0]

        out = gpipe(block_fn, split_stages(W, 4), microbatch(x, M),
                    mesh=mesh)
        out = out.reshape(B, D)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print("fwd OK")

        # gradients flow through the ring (ppermute transposes cleanly)
        def loss(W):
            o = gpipe(block_fn, split_stages(W, 4), microbatch(x, M),
                      mesh=mesh)
            return (o ** 2).sum()

        def loss_ref(W):
            o = lax.scan(lambda c, w: (layer(w, c), None), x, W)[0]
            return (o ** 2).sum()

        g = jax.jit(jax.grad(loss))(W)
        g_ref = jax.jit(jax.grad(loss_ref))(W)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=2e-4, atol=2e-4)
        print("bwd OK")
    """)
    assert "fwd OK" in out and "bwd OK" in out


def test_bubble_fraction():
    assert bubble_fraction(1, 4) == pytest.approx(3 / 4)
    assert bubble_fraction(16, 4) == pytest.approx(3 / 19)
    # the deployment guidance: M = 4S keeps the bubble under ~16%
    assert bubble_fraction(64, 16) < 0.20
