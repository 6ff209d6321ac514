"""Compile the served path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
chip that is described, not attached.  It refuses what interpret mode
accepts — a lane block that is neither a multiple of 128 nor the full
extent, a primitive Mosaic cannot lower — so each case here compiles one
launch at EdgeNeXt-S / rwkv6 width, with the launch parameters that
``search.lower`` emits for the searched schedule, and asserts that the
compiled program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a fixture (never at import time): only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.costmodel import HWSpec
from repro.core.workload import DWCONV
from repro.kernels import ops
from repro.search import auto_schedule, get_workload


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU library: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off:
    an entry written for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def served():
    """(layers by name, lowered launches) of the searched schedules."""
    out = {}
    for name in ("edgenext-s", "rwkv6"):
        layers = get_workload(name)
        sched = auto_schedule(layers, HWSpec(), workload=name)
        out[name] = ({l.name: l for l in layers}, sched.lowered)
    return out


def _launch(served, workload, kernel, head):
    """The lowered launch of ``kernel`` whose group starts at ``head``,
    with its layers."""
    by_name, lowered = served[workload]
    for names, lk in lowered.items():
        parts = names.split(" + ")
        if lk["kernel"] == kernel and parts[0] == head:
            return [by_name[p] for p in parts], lk
    raise AssertionError(f"no {kernel} launch at {head} in {workload}")


def _ibn(served, workload, head):
    (expand, project), lk = _launch(served, workload, "fused_ibn", head)
    m, d, f = expand.b * expand.ox * expand.oy, expand.c, expand.k
    act = "relu2" if workload == "rwkv6" else "gelu"
    return (functools.partial(ops.fused_ibn, activation=act,
                              block_m=lk["block_m"], block_f=lk["block_f"],
                              interpret=False),
            [(m, d), (d, f), (f, project.k)])


def _matmul_ln(served, workload, head):
    (mac, _), lk = _launch(served, workload, "matmul_ln", head)
    m, k, n = mac.b * mac.ox * mac.oy, mac.c * mac.fx * mac.fy, mac.k
    return (functools.partial(ops.matmul_ln, block_m=lk["block_m"],
                              block_k=lk["block_k"], interpret=False),
            [(m, k), (k, n), (n,), (n,), (n,)])


def _attention(served, workload, head):
    (qk,), lk = _launch(served, workload, "flash_attention", head)
    return (functools.partial(ops.flash_attention, causal=False,
                              block_q=lk["block_q"], block_k=lk["block_k"],
                              interpret=False),
            [(1, qk.b, qk.ox, qk.c)] + [(1, qk.b, qk.k, qk.c)] * 2)


def _wkv(served, workload, head):
    (scan,), lk = _launch(served, workload, "rwkv_chunk", head)
    bh, t, k, v = scan.b, scan.ox, scan.c, scan.k
    return (functools.partial(ops.wkv_chunked, chunk=lk["chunk"],
                              interpret=False),
            [(bh, t, k), (bh, t, k), (bh, t, v), (bh, t, k), (bh, k)])


def _depthwise(served, workload, head):
    by_name, _ = served[workload]
    dw = by_name[head]
    assert dw.op == DWCONV
    return (functools.partial(ops.depthwise_conv2d, interpret=False),
            [(dw.b, dw.oy, dw.ox, dw.c), (dw.fy, dw.fx, dw.c), (dw.c,)])


CASES = {
    "fused_ibn-stage0": (_ibn, "edgenext-s", "s0.conv0.pw1"),
    "fused_ibn-stage3": (_ibn, "edgenext-s", "s3.conv0.pw1"),
    "matmul_ln-stage1": (_matmul_ln, "edgenext-s", "s1.sdta0.proj"),
    "flash_attention-xca-stage1": (_attention, "edgenext-s", "s1.sdta0.qk"),
    "depthwise-c160": (_depthwise, "edgenext-s", "s2.conv0.dw"),
    "depthwise-c304": (_depthwise, "edgenext-s", "s3.conv0.dw"),
    "rwkv_chunk": (_wkv, "rwkv6", "blk0.tmix.wkv"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, served):
    build, workload, head = CASES[case]
    fn, shapes = build(served, workload, head)
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# XLA's intra-chunk scores: an f32 [..., C, C, K] tensor at chunk 64
_SCORES = re.compile(r"= f32\[(?:\d+,)*64,64,64\]")
# the weight stacks of the served RWKV-6 and their per-layer slices
_STACK = re.compile(r"= bf16\[(?:24,|1,)?(?:2048,2048|2048,7168|7168,2048)\]"
                    r"\S* convert\(")
# XLA's chunked SSD decay: an f32 [..., Q, Q] tensor at Granite's chunk 256
_DECAY = re.compile(r"= f32\[(?:\d+,)*256,256\]")
_SLICE = re.compile(r"= \w+\[(?:1,)?(?:2048,2048|2048,7168|7168,2048)\]"
                    r"\S* dynamic-slice\(")


@pytest.fixture
def kernels_for_tpu(monkeypatch):
    """The forward's kernels lowered for the described chip: here the
    backend is the CPU, where ``ops`` would take the interpreter."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def test_rwkv6_forward_streams_its_weight_stacks(one_chip, kernels_for_tpu):
    """The served RWKV-6 forward at full width (f32 weights, bf16
    compute, 512 tokens): every projection is a kernel that reads its
    layer from the whole f32 stack, so no cast or slice of a weight stack
    is left to XLA, and the WKV is the ``wkv_chunk`` kernel, so XLA
    builds no [C, C, K] intra-chunk score tensor."""
    from repro.configs.rwkv6_1_6b import CONFIG
    from repro.models import rwkv6
    from repro.models.params import ParamDef

    params = jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.float32,
                                       sharding=one_chip),
        rwkv6.param_defs(CONFIG),
        is_leaf=lambda d: isinstance(d, ParamDef))
    tokens = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one_chip)

    def served(p, t):
        hidden, _ = rwkv6.forward(CONFIG, p, {"tokens": t})
        return rwkv6.logits_fn(CONFIG, p, hidden[:, -1:, :])

    hlo = jax.jit(served).lower(params, tokens).compile().as_text()
    assert not _STACK.findall(hlo)
    assert not _SLICE.findall(hlo)
    assert not _SCORES.findall(hlo)
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 9
    assert all("/while/body/" in line for line in kernels)
    assert sum("/tmix/wkv/" in line and "wkv_chunk" in line
               for line in kernels) == 1


def test_rwkv6_train_step_compiles_for_v5e_through_the_kernels(
        one_chip, kernels_for_tpu):
    """A one-chip train step (the model at its reduced size): the WKV
    kernel runs forward, and again where the layer's remat recomputes
    it, and its custom VJP takes XLA's chunked form backward."""
    from repro.configs import get_config, reduced
    from repro.models import rwkv6
    from repro.models.params import ParamDef
    from repro.optim import adamw_init, warmup_cosine
    from repro.runtime import build_train_step

    cfg = reduced(get_config("rwkv6-1.6b"))
    params = jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.float32,
                                       sharding=one_chip),
        rwkv6.param_defs(cfg), is_leaf=lambda d: isinstance(d, ParamDef))
    opt = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(adamw_init, params))
    tokens = jax.ShapeDtypeStruct((1, 64), jnp.int32, sharding=one_chip)
    step = build_train_step(cfg, lr_schedule=warmup_cosine(1e-3, 2, 10))
    hlo = jax.jit(step).lower(params, opt, {"tokens": tokens,
                                            "labels": tokens}
                              ).compile().as_text()
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("wkv_chunk" in line for line in kernels) == 2


def test_granite_prefill_compiles_for_v5e_and_fits_one_chip(
        one_chip, kernels_for_tpu):
    """The served Granite 4.0-H Micro forward at full width and the
    cell's 8192 tokens (bf16 weights): every projection of the unrolled
    period (9 Mamba-2 x 4, one attention x 4, 10 MLPs x 2) is a
    ``stacked_proj`` kernel in the layer scan, every Mamba-2 layer's SSD
    the ``ssd_chunk`` kernel in its ``ssd`` scope, so XLA builds no
    [.., 256, 256] f32 decay, and weights plus temporaries fit one v5e's
    16 GB."""
    from repro.configs.granite_4_0_h_micro import CONFIG
    from repro.models import mamba_hybrid
    from repro.models.params import ParamDef

    params = jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.bfloat16,
                                       sharding=one_chip),
        mamba_hybrid.param_defs(CONFIG),
        is_leaf=lambda d: isinstance(d, ParamDef))
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)

    def served(p, t):
        hidden, _ = mamba_hybrid.forward(CONFIG, p, {"tokens": t})
        return mamba_hybrid.logits_fn(CONFIG, p, hidden[:, -1:, :])

    compiled = jax.jit(served).lower(params, tokens).compile()
    hlo = compiled.as_text()
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    ssd = [line for line in kernels if "ssd_chunk" in line]
    assert len(kernels) - len(ssd) == 60
    assert len(ssd) == 9 and all("/mamba/ssd/" in line for line in ssd)
    assert all("/while/body/" in line for line in kernels)
    assert not _DECAY.findall(hlo)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9


def _granite_on_a_mesh(topo):
    """The reduced Granite forward on a described 2x2 v5e, batch over
    'data' and the projections over 'model': its optimized HLO."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import SHAPES_BY_NAME, get_config, reduced
    from repro.launch.specs import input_specs
    from repro.models import actshard, mamba_hybrid
    from repro.models.params import ParamDef
    from repro.runtime import batch_pspecs, model_param_pspecs

    cfg = reduced(get_config("granite-4.0-h-micro"))
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    defs = mamba_hybrid.param_defs(cfg)
    params = jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.float32), defs,
        is_leaf=lambda d: isinstance(d, ParamDef))
    batch = input_specs(cfg, dataclasses.replace(
        SHAPES_BY_NAME["train_4k"], seq_len=32, global_batch=4))

    def named(tree):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                            is_leaf=lambda s: isinstance(s, P))

    actshard.set_mesh(mesh, "tp")
    try:
        fwd = jax.jit(
            lambda p, b: mamba_hybrid.forward(cfg, p, b)[0],
            in_shardings=(named(model_param_pspecs(cfg, mesh, defs,
                                                   profile="tp")),
                          named(batch_pspecs(cfg, mesh, batch, "tp"))))
        return fwd.lower(params, batch).compile().as_text(), cfg
    finally:
        actshard.set_mesh(None)


def test_granite_forward_on_a_v5e_mesh_keeps_xla_s_chunked_ssd(
        topo, one_chip, kernels_for_tpu):
    """Under an ``actshard`` mesh the partitioner cannot split a Pallas
    call, so Granite keeps XLA's ``ssd_chunked`` there: no kernel, and
    the intra-chunk decay is XLA's [.., chunk, chunk] f32 tensor."""
    hlo, cfg = _granite_on_a_mesh(topo)
    Q = cfg.mamba_chunk_size
    assert "tpu_custom_call" not in hlo
    assert re.search(rf"= f32\[(?:\d+,)*{Q},{Q}\]", hlo)


def test_stacked_proj_compiles_for_v5e_at_decodes_one_row(one_chip):
    """cmix.value's projection, the widest K, from a [24, 7168, 2048] f32
    stack at decode's M = 1 (the forward above runs M = 512)."""
    args = [jax.ShapeDtypeStruct((1, 7168), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((24, 7168, 2048), jnp.float32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)]
    fn = functools.partial(ops.stacked_proj, interpret=False)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rwkv6_forward_on_a_v5e_mesh_leaves_its_projections_to_xla(
        topo, one_chip, kernels_for_tpu):
    """On a mesh the partitioner cannot split a Pallas call (it refuses
    one), so RWKV-6 keeps XLA's projections there.  The forward on a
    described 2x2 v5e, batch over 'data' and the projections over
    'model' (the serving layout), compiles with no kernel, and gathers
    neither a weight stack nor the whole batch onto a chip."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import SHAPES_BY_NAME, get_config, reduced
    from repro.launch.specs import input_specs
    from repro.models import actshard, rwkv6
    from repro.models.params import ParamDef
    from repro.runtime import batch_pspecs, model_param_pspecs

    cfg = reduced(get_config("rwkv6-1.6b"))
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    defs = rwkv6.param_defs(cfg)
    params = jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.float32), defs,
        is_leaf=lambda d: isinstance(d, ParamDef))
    batch = input_specs(cfg, dataclasses.replace(
        SHAPES_BY_NAME["train_4k"], seq_len=32, global_batch=4))

    def named(tree):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                            is_leaf=lambda s: isinstance(s, P))

    actshard.set_mesh(mesh, "tp")
    try:
        fwd = jax.jit(
            lambda p, b: rwkv6.forward(cfg, p, b)[0],
            in_shardings=(named(model_param_pspecs(cfg, mesh, defs,
                                                   profile="tp")),
                          named(batch_pspecs(cfg, mesh, batch, "tp"))))
        hlo = fwd.lower(params, batch).compile().as_text()
    finally:
        actshard.set_mesh(None)

    assert "tpu_custom_call" not in hlo
    weights = {tuple(s.shape[i:]) for i in (0, 1)
               for g, names in rwkv6.STACKED.items()
               for s in (params["blocks"][g][n] for n in names)}
    gathered = [tuple(int(d) for d in dims.split(","))
                for line in hlo.splitlines()
                if re.search(r" all-gather(-start)?\(", line)
                for dims in re.findall(r"\w+\[([\d,]+)\]",
                                       line.split(" all-gather")[0])]
    assert gathered                  # the features of the split heads
    assert all(shape[0] == 2 for shape in gathered)    # half the batch
    assert not weights & set(gathered)
