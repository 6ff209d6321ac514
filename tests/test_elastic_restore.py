"""Elastic rescaling: a checkpoint written under one mesh restores onto a
DIFFERENT topology (sharding tree changes, values identical) — the
restart path after losing/gaining pods."""
import subprocess
import sys
import textwrap


# JAX_PLATFORMS=cpu: the image ships libtpu; without the override the
# child process burns 60+s probing a TPU backend that does not exist.
ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
       "JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}


def test_restore_across_meshes(tmp_path):
    code = f"""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import restore_sharded, save_checkpoint
    from repro.configs import get_config, reduced
    from repro.models import get_module, params as PL
    from repro.runtime import model_param_pspecs

    cfg = reduced(get_config('olmo-1b'))
    mod = get_module(cfg)
    defs = mod.param_defs(cfg)

    # write under a (2, 4) mesh
    mesh_a = jax.make_mesh((2, 4), ("data", "model"),
                           axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ps_a = model_param_pspecs(cfg, mesh_a, defs)
    named_a = jax.tree.map(lambda s: NamedSharding(mesh_a, s), ps_a,
                           is_leaf=lambda x: isinstance(x, P))
    params = jax.jit(lambda k: PL.init_params(k, defs),
                     out_shardings=named_a)(jax.random.PRNGKey(0))
    save_checkpoint({str(repr(str(tmp_path)))}, 5, params)

    # restore under a (4, 2) mesh — different shard layout
    mesh_b = jax.make_mesh((4, 2), ("data", "model"),
                           axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ps_b = model_param_pspecs(cfg, mesh_b, defs)
    named_b = jax.tree.map(lambda s: NamedSharding(mesh_b, s), ps_b,
                           is_leaf=lambda x: isinstance(x, P))
    step, restored = restore_sharded({str(repr(str(tmp_path)))}, params,
                                     named_b, step=5)
    assert step == 5
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the restored tree actually carries the new sharding
    leaf = jax.tree.leaves(restored)[0]
    assert leaf.sharding.mesh.devices.shape == (4, 2)
    print("elastic OK")
    """
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=ENV,
                       cwd="/root/repo", timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "elastic OK" in r.stdout
