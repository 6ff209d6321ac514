"""The program names its own work: layer scopes in the model forwards,
the op -> layer table read back from the compiled HLO
(``repro.obs.layers``), and the serving store's ``serve.request`` span,
which a tracer made with ``profiler=True`` puts on a ``jax.profiler``
trace."""
import dataclasses
import functools
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs.edgenext_s import reduced_edgenext
from repro.configs.rwkv6_1_6b import CONFIG as RWKV6
from repro.core.costmodel import HWSpec
from repro.models import edgenext, rwkv6
from repro.models.params import abstract_params
from repro.core.workload import Layer
from repro.obs.layers import layer_classes, layer_of, op_layers
from repro.search import get_workload, layer_scopes
from repro.serve import ServeStore

# the scheduler classes whose layers every compiled forward must name
CLASSES = ("pwconv", "dwconv", "norm", "softmax", "scan")
# the instructions that do a layer's arithmetic: each must have a layer
_MATH = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\s"
                   r"(?:dot|convolution)\(.*$", re.M)


def _wanted(classes):
    return {n for n, (op, _) in classes.items() if op in CLASSES}


def _unattributed_math(hlo, table, with_op_name=False):
    """The matmuls and convolutions ``table`` gives no layer (with
    ``with_op_name``, of those that kept their ``op_name``)."""
    found = [(m.group(1), "op_name=" in m.group(0))
             for m in _MATH.finditer(hlo)]
    assert found
    return [n for n, named in found
            if n not in table and (named or not with_op_name)]


@pytest.mark.parametrize("ibn_chunks", [0, 2], ids=["plain", "chunked"])
def test_edgenext_ops_cover_every_layer_of_the_chain(ibn_chunks):
    cfg = reduced_edgenext()
    params = abstract_params(edgenext.param_defs(cfg))
    images = jax.ShapeDtypeStruct((2, cfg.img_size, cfg.img_size, 3),
                                  jnp.uint8)
    fwd = jax.jit(functools.partial(edgenext.forward, cfg,
                                    ibn_chunks=ibn_chunks))
    classes = layer_scopes("edgenext-reduced")
    hlo = fwd.lower(params, images).compile().as_text()
    table = op_layers(hlo, classes)
    found = set(table.values())
    assert _wanted(classes) <= found
    # the chunked path's matmuls run inside a lax.scan in the block scope
    assert _unattributed_math(hlo, table) == []
    assert found <= set(classes)
    assert {"s1.sdta0.sm", "s3.sdta0.dw0", "head.fc"} <= found


def test_rwkv6_ops_cover_the_chain_in_its_scan_spelling():
    cfg = dataclasses.replace(RWKV6, num_layers=2, d_model=128, d_ff=256,
                              vocab_size=512, num_heads=2, num_kv_heads=2)
    params = abstract_params(rwkv6.param_defs(cfg))
    tokens = jax.ShapeDtypeStruct((1, 64), jnp.int32)

    def served(p, t):
        hidden, _ = rwkv6.forward(cfg, p, {"tokens": t})
        return rwkv6.logits_fn(cfg, p, hidden[:, -1:, :])

    classes = layer_scopes("rwkv6")
    hlo = jax.jit(served).lower(params, tokens).compile().as_text()
    table = op_layers(hlo, classes)
    found = set(table.values())
    # XLA's CPU compiler rebuilds the WKV chunk's batched dots without
    # their op_name; compiled for a TPU, every matmul keeps it
    assert _unattributed_math(hlo, table, with_op_name=True) == []
    in_scan = {n.split(".", 1)[1] for n in _wanted(classes)
               if n.startswith("blk")}
    assert in_scan == {"ln1", "tmix.rkvg", "tmix.wkv", "tmix.gn",
                       "tmix.out", "ln2", "cmix.key", "cmix.value"}
    assert in_scan | {"embed", "head.ln", "head.logits"} <= found
    assert not any(n.startswith("blk") for n in found)


def test_layer_classes_carry_the_scheduler_op_and_ibn_role():
    edge = layer_scopes("edgenext-s")
    assert edge["s1.conv0.pw1"] == ("pwconv", "expand")
    assert edge["s1.conv0.act"] == ("act", "act")
    assert edge["s2.sdta0.pw2"] == ("pwconv", "project")
    assert edge["s2.sdta0.qkv"] == ("pwconv", None)
    assert edge["s2.sdta0.sm"] == ("softmax", None)
    assert edge["s0.conv0.dw"] == ("dwconv", None)
    rwkv = layer_scopes("rwkv6")
    assert rwkv["tmix.wkv"] == rwkv["blk7.tmix.wkv"] == ("scan", None)
    assert rwkv["cmix.key"] == ("pwconv", "expand")
    assert rwkv["embed"] == rwkv["head.logits"] == (None, None)
    assert edge.keys() == {layer.name for layer in get_workload("edgenext-s")}
    with pytest.raises(KeyError):
        layer_scopes("vit-tiny")            # no forward runs its chain


def test_layer_classes_take_the_spelling_from_the_caller():
    chain = [Layer("blk0.tmix.wkv", "scan"), Layer("blk1.tmix.wkv", "scan"),
             Layer("blk0.cmix.key", "pwconv", ibn_role="expand")]
    assert layer_classes(chain) == {
        "blk0.tmix.wkv": ("scan", None), "blk1.tmix.wkv": ("scan", None),
        "blk0.cmix.key": ("pwconv", "expand")}
    got = layer_classes(chain, rwkv6.chain_scope, ("embed",))
    assert got["tmix.wkv"] == ("scan", None)
    assert got["cmix.key"] == ("pwconv", "expand")
    assert got["embed"] == (None, None)


@pytest.mark.parametrize("layer,scope", [
    ("blk3.tmix.wkv", "tmix.wkv"), ("blk12.ln1", "ln1"),
    ("embed", "embed"), ("blkx.ln1", "blkx.ln1")])
def test_rwkv6_spells_a_chain_layer_without_its_block(layer, scope):
    assert rwkv6.chain_scope(layer) == scope


@pytest.mark.parametrize("op_name,want", [
    ("jit(f)/jit(main)/s1.conv0/pw1/dot_general", "s1.conv0.pw1"),
    ("jit(f)/s1.conv0/ln/jit(_var)/reduce_sum", "s1.conv0.ln"),
    ("jit(f)/while/body/checkpoint/tmix/wkv/while/body/exp", "tmix.wkv"),
    ("jit(f)/while/body/ln1/rsqrt", "ln1"),
    # a layer scope in a scan inside the block scope (chunked _ibn_mlp)
    ("jit(f)/s1.conv0/while/body/closed_call/pw1/dot_general",
     "s1.conv0.pw1"),
    ("jit(f)/transpose(jvp(g))/s1.conv0/remat/pw1/add", "s1.conv0.pw1"),
    # a Pallas kernel in its layer scope: compiled for a TPU, and run by
    # the interpreter elsewhere
    ("jit(f)/while/body/closed_call/checkpoint/tmix/rkvg/"
     "jit(stacked_proj)/pallas_call", "tmix.rkvg"),
    ("jit(f)/while/body/closed_call/checkpoint/cmix/value/"
     "jit(stacked_proj)/while/body/dot_general", "cmix.value"),
    # the WKV kernel, named ``wkv_chunk``, counts to its scope's layer
    ("jit(f)/while/body/closed_call/checkpoint/tmix/wkv/jit(wkv)/"
     "wkv_chunk/pallas_call", "tmix.wkv"),
    ("jit(f)/while/body/closed_call/checkpoint/tmix/wkv/jit(wkv)/"
     "wkv_chunk/while/body/while/body/closed_call/closed_call/exp",
     "tmix.wkv"),
    ("jit(f)/s1.conv0/reshape", None),          # block scope, no layer
    ("jit(f)/s1.conv0/pw9/dot_general", None),  # renamed: missing
    ("jit(f)/pw1", None),                       # a primitive, not a scope
    ("reduce_sum", None),
])
def test_layer_of_reads_the_innermost_known_scope(op_name, want):
    names = {"s1.conv0.pw1", "s1.conv0.ln", "pw1", "tmix.wkv", "ln1",
             "tmix.rkvg", "cmix.value"}
    assert layer_of(op_name, names) == want


HLO = """HloModule m, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(f)/b0/pw1/mul"}
  ROOT %add.2 = f32[8]{0} add(%multiply.1, %param_0), metadata={op_name="jit(f)/b0/res/add"}
}

ENTRY %main.4 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %copy.3 = f32[8]{0} copy(%Arg_0.1)
  %fusion = f32[8]{0} fusion(%copy.3), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(f)/b0/pw1/mul"}
  %fusion.1 = f32[8]{0} fusion(%fusion), kind=kLoop, calls=%fused_computation
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/reshape"}
  ROOT %tanh.5 = f32[8]{0} tanh(%fusion.2), metadata={op_name="jit(f)/b0/act/tanh"}
}
"""


def test_a_fusion_counts_to_its_own_layer_else_its_roots():
    table = op_layers(HLO, {"b0.pw1", "b0.res", "b0.act"})
    assert table["fusion"] == "b0.pw1"       # the op it was built around
    assert table["fusion.1"] == table["fusion.2"] == "b0.res"
    assert table["tanh.5"] == "b0.act"
    assert "copy.3" not in table and "Arg_0.1" not in table


# a weight cast hoisted out of a layer scan keeps no op_name: it counts
# to the layer that consumes it inside the loop
HOISTED = """HloModule m

%body (p: (s32[], f32[4,8], f32[8])) -> (s32[], f32[4,8], f32[8]) {
  %p = (s32[], f32[4,8], f32[8]) parameter(0)
  %get-tuple-element.1 = f32[4,8]{1,0} get-tuple-element(%p), index=1
  %get-tuple-element.2 = f32[8]{0} get-tuple-element(%p), index=2
  %dynamic-slice.3 = f32[8]{0} dynamic-slice(%get-tuple-element.1), metadata={op_name="jit(f)/while/body/dynamic_slice"}
  %multiply.4 = f32[8]{0} multiply(%dynamic-slice.3, %get-tuple-element.2), metadata={op_name="jit(f)/while/body/tmix/rkvg/mul"}
  %get-tuple-element.0 = s32[] get-tuple-element(%p), index=0
  ROOT %tuple.5 = (s32[], f32[4,8], f32[8]) tuple(%get-tuple-element.0, %get-tuple-element.1, %multiply.4)
}

ENTRY %main (w: f32[4,8], x: f32[8]) -> f32[8] {
  %w = f32[4,8]{1,0} parameter(0), metadata={op_name="w"}
  %x = f32[8]{0} parameter(1), metadata={op_name="x"}
  %convert.6 = f32[4,8]{1,0} convert(%w)
  %constant.7 = s32[] constant(0)
  %tuple.8 = (s32[], f32[4,8], f32[8]) tuple(%constant.7, %convert.6, %x)
  %while.9 = (s32[], f32[4,8], f32[8]) while(%tuple.8), condition=%cond, body=%body
  ROOT %get-tuple-element.10 = f32[8]{0} get-tuple-element(%while.9), index=2
}
"""


def test_a_cast_hoisted_out_of_a_scan_counts_to_its_consumer():
    table = op_layers(HOISTED, {"tmix.rkvg", "tmix.wkv"})
    assert table["convert.6"] == "tmix.rkvg"
    assert table["multiply.4"] == "tmix.rkvg"
    assert "constant.7" not in table and "while.9" not in table


def _request_spans(tracer):
    return [s for r in tracer.roots for s in r.walk()
            if s.name == "serve.request"]


@pytest.fixture(scope="module")
def warm_dir(tmp_path_factory):
    path = tmp_path_factory.mktemp("store")
    ServeStore(path, HWSpec()).warm(["edgenext-reduced"], batches=(1,))
    return path


def test_request_without_a_flagged_tracer_makes_no_profiler_call(
        warm_dir, monkeypatch):
    def refuse(*_a, **_kw):
        raise AssertionError("a profiler call with tracing off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    store = ServeStore(warm_dir, HWSpec())
    assert store.request("edgenext-reduced").outcome == "disk"
    assert store.request("edgenext-reduced").outcome == "mem"
    with obs.tracing() as tr:                   # no profiler flag
        store.request("edgenext-reduced")
    (sp,) = _request_spans(tr)
    assert sp.attrs == {"workload": "edgenext-reduced", "batch": 1,
                        "outcome": "mem"}


def test_request_counters_are_unchanged_and_spans_name_the_rung(warm_dir):
    store = ServeStore(warm_dir, HWSpec())
    with obs.tracing() as tr:
        store.request("edgenext-reduced")
        store.request("edgenext-reduced")
    # the disk replay and the memory hit each count a cache hit
    assert tr.counters == {"cache.hit": 2, "serve.store.mem_hit": 1}
    assert [s.attrs["outcome"] for s in _request_spans(tr)] == \
        ["disk", "mem"]
    assert not [s for r in tr.roots for s in r.walk()
                if s.name == "serve.lookup"]


def test_flagged_tracer_puts_the_request_span_on_the_host_plane(
        warm_dir, tmp_path):
    from jax.profiler import ProfileData
    store = ServeStore(warm_dir, HWSpec())
    store.request("edgenext-reduced")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.tracing(obs.Tracer(profiler=True)) as tr:
            for _ in range(3):
                store.request("edgenext-reduced")
        store.request("edgenext-reduced")       # tracing off again
    finally:
        jax.profiler.stop_trace()
    (path,) = Path(tmp_path).rglob("*.xplane.pb")
    events = [e for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name == "serve.request"]
    assert len(events) == 3 == len(_request_spans(tr))
    assert all(e.duration_ns > 0 for e in events)
