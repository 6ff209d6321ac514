"""repro.search acceptance + property tests.

The load-bearing claims:
  * the auto-scheduler REDISCOVERS the paper's three contributions
    (dual dataflow, pixelwise fusion, IBN fusion) from enumeration —
    nothing consults ibn_role / reconfigurable / fuse_* flags — and its
    EDP is <= the hand-coded ``+ibn-fusion`` config under identical
    accounting;
  * it generalizes: valid Pareto fronts on two non-EdgeNeXt workloads;
  * ``lower`` emits Pallas block parameters that pass the existing
    kernel-vs-ref correctness checks.
"""
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.configs.edgenext_s import CONFIG, reduced_edgenext
from repro.core import dataflow
from repro.core.costmodel import HWSpec
from repro.core.fusion import spill_edges
from repro.core.schedule import evaluate_stack
from repro.core.workload import (DWCONV, MAC_OPS, Layer, edgenext_workload,
                                 efficientvit_workload, ibn_groups,
                                 mobilevit_workload, total_macs,
                                 vit_workload)
from repro.search import (auto_schedule, cached_search, dse, edp_best,
                          evaluate_schedule, hw_variants, load_schedule,
                          pareto_front, save_schedule, sweep, sweep_memory)
from repro.search import lower, mapper, partition, tiler

WL = edgenext_workload(CONFIG)
HW = HWSpec()
SCHED = auto_schedule(WL, HW, workload="edgenext-s")


# ---------------------------------------------------------------------------
# acceptance: rediscovery on EdgeNeXt-S
# ---------------------------------------------------------------------------


def test_auto_edp_beats_hand_stack():
    hand = evaluate_stack(WL, HW)
    assert SCHED.cost["edp"] <= hand[-1].edp * (1 + 1e-9)
    assert SCHED.cost["latency_s"] <= hand[-1].latency_s * (1 + 1e-9)
    assert SCHED.cost["energy_j"] <= hand[-1].energy_j * (1 + 1e-9)


def test_auto_rediscovers_dual_dataflow():
    """Per-layer searched mappings never lose to the paper's selector,
    and depthwise layers leave the fixed OX|C regime."""
    for l in WL:
        if l.op not in MAC_OPS:
            continue
        hand = dataflow.cycles(
            l, dataflow.select_mapping(l, reconfigurable=True))
        got = dataflow.cycles(l, tuple(SCHED.mappings[l.name]))
        assert got <= hand, (l.name, SCHED.mappings[l.name])
    for l in WL:
        if l.op == DWCONV:
            assert dataflow.cycles(l, tuple(SCHED.mappings[l.name])) <= \
                dataflow.cycles(l, "CFX")


def test_auto_rediscovers_pixelwise_fusion():
    """Every nonlinear layer ends up fused into a producer."""
    nonlinear = [l.name for l in WL if l.op not in MAC_OPS]
    assert set(SCHED.fused_nonlinear) == set(nonlinear)


def test_auto_rediscovers_ibn_fusion():
    """Each spilling IBN expand/project pair lands in one fusion group,
    and the searched spill-edge set matches the hand-coded +ibn-fusion
    edges."""
    g_of = {}
    for gi, g in enumerate(SCHED.groups):
        for name in g:
            g_of[name] = gi
    for exp, _act, proj in ibn_groups(WL):
        if exp.output_bytes > HW.act_budget_bytes:
            assert g_of[exp.name] == g_of[proj.name], exp.name
    legacy = spill_edges(WL, HW.act_budget_bytes, fuse_nonlinear=True,
                         fuse_ibn=True)
    assert {(p, c) for p, c, _ in SCHED.edges} == \
        {(e.producer, e.consumer) for e in legacy}


def test_auto_evaluation_is_consistent():
    nc = evaluate_schedule(WL, SCHED, HW)
    assert nc.edp == pytest.approx(SCHED.cost["edp"])
    assert nc.latency_s == pytest.approx(SCHED.cost["latency_s"])


def test_stack_include_auto_row():
    """core.schedule wiring: the auto row rides along the Fig 8 stack
    and is never worse than the final hand config."""
    rows = evaluate_stack(WL, HW, include_auto=True)
    assert [r.name for r in rows][-1] == "auto"
    assert rows[-1].edp <= rows[-2].edp * (1 + 1e-9)


def test_fixed_array_schedule_is_worse():
    """Restricting the search to one fixed-wiring mapping must cost
    latency vs the reconfigurable search (the Fig 3 argument)."""
    fixed = auto_schedule(WL, HW, reconfigurable=False)
    assert fixed.cost["latency_s"] > SCHED.cost["latency_s"]


def test_fixed_wiring_costed_with_column_void_penalty():
    """Regression: a non-reconfigurable schedule's headline cost must
    include the adder-tree column-void penalty the mapper optimized
    against — not the reconfigurable cycle count of the same dim pair."""
    fixed = auto_schedule(WL, HW, reconfigurable=False)
    assert fixed.fixed_wiring
    nc = evaluate_schedule(WL, fixed, HW)
    wired_cycles = sum(
        dataflow.cycles_generic(l, tuple(fixed.mappings[l.name]),
                                HW.rows, HW.cols, fixed_wiring=True)
        for l in WL if l.op in MAC_OPS)
    compute_cycles = sum(lc.compute_cycles for lc in nc.layers)
    assert compute_cycles == wired_cycles


# ---------------------------------------------------------------------------
# generalization: two non-EdgeNeXt workloads
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,layers", [
    ("vit-tiny", vit_workload()),
    ("efficientvit-b0", efficientvit_workload()),
    ("mobilevit-s", mobilevit_workload()),
])
def test_auto_generalizes(name, layers):
    assert total_macs(layers) > 0
    sched = auto_schedule(layers, HW, workload=name)
    hand = evaluate_stack(layers, HW)
    assert sched.cost["edp"] <= hand[-1].edp * (1 + 1e-9), name
    assert len(sched.groups) > 0 and sched.cost["latency_s"] > 0


def test_mobilevit_workload_registered():
    """The second hybrid-ViT graph: published MobileViT-S scale (~2
    GMACs at 256x256), batch-4 serving shape scaling, FFN ibn triples
    for the fusion analyses, both shapes in the CLI registry."""
    from repro.search import WORKLOADS, get_workload
    wl = get_workload("mobilevit-s")
    g = total_macs(wl) / 1e9
    assert 1.5 < g < 2.5, g
    assert len(ibn_groups(wl)) == sum((2, 4, 3))      # one per block
    wl4 = get_workload("mobilevit-s-b4")
    assert total_macs(wl4) == 4 * total_macs(wl)
    assert {"mobilevit-s", "mobilevit-s-b4"} <= set(WORKLOADS)
    sched = auto_schedule(wl4, HW, workload="mobilevit-s-b4")
    assert sched.cost["edp"] <= \
        evaluate_stack(wl4, HW)[-1].edp * (1 + 1e-9)


@pytest.mark.parametrize("name,layers", [
    ("vit-tiny", vit_workload()),
    ("efficientvit-b0", efficientvit_workload()),
])
def test_dse_pareto_front_valid(name, layers):
    pts = sweep(layers, hw_variants(
        HW, pe_shapes=((8, 8), (16, 16), (32, 32)), sram_kb=(256, 512)),
        workload=name)
    front = pareto_front(pts)
    assert front, name
    # no front point is dominated by any swept point
    for p in front:
        assert not any(dse.dominates(q, p) for q in pts), p.label
    # every off-front point is dominated by some front point
    on = {p.label for p in front}
    for p in pts:
        if p.label not in on:
            assert any(dse.dominates(q, p) for q in front), p.label
    assert edp_best(pts).edp <= min(p.edp for p in front) * (1 + 1e-9)


# ---------------------------------------------------------------------------
# mapper properties
# ---------------------------------------------------------------------------


def test_generic_cycles_match_legacy_mappings():
    for l in WL:
        if l.macs == 0:
            continue
        for name, (pair, fixed) in dataflow.LEGACY_MAPPINGS.items():
            assert dataflow.cycles(l, name) == dataflow.cycles_generic(
                l, pair, fixed_wiring=fixed)


def test_best_mapping_lower_bounded_by_macs():
    for l in WL:
        if l.macs == 0:
            continue
        mc = mapper.best_mapping(l, HW.rows, HW.cols)
        assert mc.cycles * HW.rows * HW.cols >= l.macs
        assert 0 < mc.utilization <= 1.0


def test_temporal_orders_cover_and_pixelwise_exists():
    pw1 = next(l for l in WL if l.ibn_role == "expand")
    t = mapper.best_temporal(pw1, HW, require_pixelwise=True)
    assert t is not None and t.pixelwise
    free = mapper.best_temporal(pw1, HW)
    assert free.sram_bytes <= t.sram_bytes


# ---------------------------------------------------------------------------
# tiler properties
# ---------------------------------------------------------------------------


def test_tiler_skips_infeasible_budgets():
    exp, _a, proj = ibn_groups(WL)[0]
    assert tiler.optimize_tile(exp, proj, local_buffer=0) is None
    t = tiler.optimize_tile(exp, proj, local_buffer=HW.output_rf_bytes)
    assert t is not None and t.buffer_bytes <= HW.output_rf_bytes


def test_tiler_beats_fixed_candidate_list():
    """Budget-driven enumeration never loses to the legacy 9-candidate
    list."""
    from repro.core.fusion import optimize_tile as legacy_tile
    for exp, _a, proj in ibn_groups(WL):
        ours = tiler.optimize_tile(exp, proj,
                                   local_buffer=HW.output_rf_bytes)
        legacy = legacy_tile(exp, proj, local_buffer=HW.output_rf_bytes)
        assert ours.sram_traffic <= legacy.sram_traffic


def test_tiler_traffic_monotone_in_budget():
    exp, _a, proj = ibn_groups(WL)[0]
    prev = None
    for kb in (2, 8, 24, 96):
        t = tiler.optimize_tile(exp, proj, local_buffer=kb * 1024)
        assert t is not None
        if prev is not None:
            assert t.sram_traffic <= prev
        prev = t.sram_traffic


def test_divisor_search_beats_pow2_baseline_on_edgenext():
    """The acceptance criterion: under identical (tile-aware, ragged-
    edge) cost accounting, the divisor/imperfect-factor search achieves
    EDP <= the pow2-only baseline — and on EdgeNeXt-S strictly better
    (the stage-4 XCA group tiles at 304 exactly instead of a ragged
    256 + 48 split that re-streams the weights twice)."""
    pow2 = auto_schedule(WL, HW, workload="edgenext-s", tile_mode="pow2")
    assert SCHED.cost["edp_tiled"] < pow2.cost["edp_tiled"]
    assert SCHED.cost["edp"] <= pow2.cost["edp"] * (1 + 1e-9)
    assert SCHED.cost["sram_tiled_bytes"] < pow2.cost["sram_tiled_bytes"]
    # the honest baseline too: never lose to the PR-1 seed space
    # (pow2 + extent + budget pivots), under the same accounting
    legacy = auto_schedule(WL, HW, workload="edgenext-s",
                           tile_mode="legacy")
    assert SCHED.cost["edp_tiled"] <= legacy.cost["edp_tiled"] * (1 + 1e-9)
    # all three tile modes must hash to distinct schedule keys
    assert len({SCHED.key, pow2.key, legacy.key}) == 3


def test_edgenext_schedule_exercises_ragged_tiles():
    """The searched EdgeNeXt-S schedule must actually contain imperfect
    tiles (ragged channel slabs on the 640-wide stage-3 IBNs) — the odd
    stage dims are the whole point of the divisor enumeration."""
    assert any(t.get("ragged_x") or t.get("ragged_c")
               for t in SCHED.tiles.values())
    for t in SCHED.tiles.values():
        assert t["buffer_bytes"] <= HW.output_rf_bytes


def test_serving_batch_workload_schedules():
    """batch>1 serving shape: pixel extents scale by the batch while the
    channel extents keep the odd stage dims; the search must stay
    feasible and no worse than the hand stack."""
    from repro.core.workload import edgenext_serving_workload
    wl = edgenext_serving_workload(batch=4)
    assert sum(l.macs for l in wl) == 4 * sum(l.macs for l in WL)
    sched = auto_schedule(wl, HW, workload="edgenext-s-b4")
    hand = evaluate_stack(wl, HW)
    assert sched.cost["edp"] <= hand[-1].edp * (1 + 1e-9)
    assert sched.cost["edp_tiled"] <= auto_schedule(
        wl, HW, workload="edgenext-s-b4",
        tile_mode="pow2").cost["edp_tiled"] * (1 + 1e-9)


def test_golden_edgenext_schedule():
    """Regression pin: the searched EdgeNeXt-S schedule (groups + tiles
    + EDP) must reproduce the checked-in snapshot.  Intentional cost-
    model changes show up as a reviewed diff — regenerate with:
      PYTHONPATH=src python -m repro.search --workload edgenext-s \
          --golden tests/golden/edgenext_s_schedule.json
    """
    p = Path(__file__).parent / "golden" / "edgenext_s_schedule.json"
    gold = json.loads(p.read_text())
    assert gold["version"] == SCHED.version, \
        "SEARCH_VERSION bumped — regenerate the golden snapshot"
    assert [list(g) for g in SCHED.groups] == gold["groups"]
    assert SCHED.tiles == gold["tiles"]
    assert SCHED.cost["edp"] == pytest.approx(gold["cost"]["edp"])
    assert SCHED.cost["edp_tiled"] == \
        pytest.approx(gold["cost"]["edp_tiled"])


def test_tile_group_rejects_incompatible_chains():
    a = Layer("a", "pwconv", k=32, c=16, ox=64)
    b = Layer("b", "pwconv", k=16, c=64, ox=64)      # width mismatch
    assert tiler.tile_group([a, b], local_buffer=1 << 20) is None
    c = Layer("c", "pwconv", k=16, c=32, ox=64)
    t = tiler.tile_group([a, c], local_buffer=1 << 20)
    assert t is not None and t.buffer_bytes <= 1 << 20


# ---------------------------------------------------------------------------
# partitioner properties
# ---------------------------------------------------------------------------


def _cycles_map(layers):
    return {l.name: mapper.best_mapping(l, HW.rows, HW.cols).cycles
            for l in layers if l.op in MAC_OPS}


def test_partition_covers_chain_exactly():
    part = partition.partition_chain(WL, _cycles_map(WL), HW)
    idx = 0
    for g in part.groups:
        assert g.start == idx
        assert g.end > g.start
        idx = g.end
    assert idx == len(WL)


def test_partition_respects_tiny_budget():
    """With no activation SRAM every inter-group tensor spills; the DP
    must still terminate and fuse what the local buffer allows."""
    part = partition.partition_chain(WL, _cycles_map(WL), HW,
                                     act_budget=0)
    assert part.edges, "everything spills at zero budget"
    for e in part.edges:
        assert e.nbytes > 0


# ---------------------------------------------------------------------------
# cache + CLI
# ---------------------------------------------------------------------------


def test_schedule_json_roundtrip(tmp_path):
    p = tmp_path / "sched.json"
    save_schedule(SCHED, p)
    back = load_schedule(p)
    assert back is not None
    assert back.key == SCHED.key
    assert back.mappings == SCHED.mappings
    assert tuple(back.edges) == tuple(SCHED.edges)
    assert back.cost["edp"] == pytest.approx(SCHED.cost["edp"])
    assert back.placements == SCHED.placements
    assert back.hw["hierarchy"]["levels"][0]["name"] == "rf"


def test_stale_v4_artifacts_rejected(tmp_path):
    """A SEARCH_VERSION=4 cache entry must never be replayed as a
    current result: load_schedule refuses it and cached_search
    re-searches.  (v7: lane-aligned launch blocks in ``lowered``.)"""
    from repro.search.cache import SEARCH_VERSION, schedule_key
    assert SEARCH_VERSION == 7
    wl = edgenext_workload(reduced_edgenext())
    key = schedule_key(wl, HW)
    path = tmp_path / f"edgenext-reduced-{key}.json"
    save_schedule(SCHED, path)
    doc = json.loads(path.read_text())
    doc["version"] = 4                   # a stale v4 artifact at the
    path.write_text(json.dumps(doc))     # exact current cache path
    assert load_schedule(path) is None
    sched = cached_search(wl, HW, workload="edgenext-reduced",
                          cache_dir=tmp_path)
    assert sched.version == SEARCH_VERSION
    assert sched.workload == "edgenext-reduced"
    # the refreshed artifact replaced the stale one
    assert json.loads(path.read_text())["version"] == SEARCH_VERSION


def test_schedule_places_every_mac_layer():
    """Loop placements: every MAC layer carries an operand -> level map
    over real hierarchy levels; on the paper design the input tile and
    psum block sit in the PE-coupled RF and the weights stream from the
    SRAM."""
    for l in WL:
        if l.op not in MAC_OPS:
            continue
        d = SCHED.placements[l.name]
        assert set(d) == {"input", "weight", "output"}
        assert set(d.values()) <= set(HW.hierarchy.names)
    pw1 = next(l for l in WL if l.ibn_role == "expand")
    assert SCHED.placements[pw1.name] == \
        {"input": "rf", "output": "rf", "weight": "sram"}


def test_memory_sweep_beats_fixed_paper_spec():
    """The hierarchy-sizing DSE acceptance: on EdgeNeXt-S at least one
    swept L1/L2 sizing lands on the Pareto front with lower EDP than the
    fixed paper spec, and the paper sizing reproduces the paper EDP
    exactly (it is a grid point)."""
    kb = 1024
    pts = sweep_memory(WL, HW, sizings={"rf": (16 * kb, 32 * kb),
                                        "sram": (512 * kb, 1024 * kb)},
                       workload="edgenext-s")
    base = next(p for p in pts
                if dict(p.mem) == {"rf": 32 * kb, "sram": 512 * kb})
    assert base.edp == SCHED.cost["edp"]
    front = pareto_front(pts)
    assert any(p.edp < base.edp for p in front)
    for p in front:
        assert not any(dse.dominates(q, p) for q in pts), p.label
    assert {len(p.mem) for p in pts} == {2}


def test_cached_search_hits(tmp_path):
    wl = edgenext_workload(reduced_edgenext())
    s1 = cached_search(wl, HW, workload="edgenext-reduced",
                       cache_dir=tmp_path)
    files = list(tmp_path.glob("*.json"))
    assert len(files) == 1
    s2 = cached_search(wl, HW, workload="edgenext-reduced",
                       cache_dir=tmp_path)
    assert s2.key == s1.key and s2.cost["edp"] == s1.cost["edp"]


def test_cli_smoke(tmp_path):
    out = tmp_path / "sched.json"
    r = subprocess.run(
        [sys.executable, "-m", "repro.search", "--workload",
         "edgenext-reduced", "--out", str(out)],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "cost.edp" in r.stdout
    art = json.loads(out.read_text())
    assert art["workload"] == "edgenext-reduced"


# ---------------------------------------------------------------------------
# lowering: searched block parameters drive the real kernels
# ---------------------------------------------------------------------------


def test_lowered_params_well_formed():
    """Every launch block is one the TPU lowering accepts: lane blocks
    (fused_ibn block_f, matmul_ln block_k) a multiple of 128 or the
    whole extent, row blocks a power of two."""
    assert SCHED.lowered, "EdgeNeXt must lower at least the IBN kernels"
    by_name = {l.name: l for l in WL}
    for name, lk in SCHED.lowered.items():
        assert lk["kernel"] in ("fused_ibn", "matmul_ln",
                                "flash_attention", "rwkv_chunk"), name
        head = by_name[name.split(" + ")[0]]
        lane, ext = {"fused_ibn": ("block_f", head.k),
                     "matmul_ln": ("block_k",
                                   head.c * head.fx * head.fy),
                     }.get(lk["kernel"], (None, 0))
        for k, v in lk.items():
            if k == lane:
                assert v % 128 == 0 or v == ext, (name, k, v, ext)
            elif k.startswith("block_"):
                assert v >= 1 and (v & (v - 1)) == 0, (name, k, v)


def test_lowered_ibn_matches_ref():
    """The searched fused_ibn block sizes must pass the kernel-vs-ref
    check (interpret mode, small operands)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    lk = next(v for v in SCHED.lowered.values()
              if v["kernel"] == "fused_ibn")
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    m, d, f = 96, 48, 160
    x = jax.random.normal(ks[0], (m, d))
    w1 = jax.random.normal(ks[1], (d, f)) * 0.1
    w2 = jax.random.normal(ks[2], (f, d)) * 0.1
    out = ops.fused_ibn(x, w1, w2, block_m=lk["block_m"],
                        block_f=lk["block_f"])
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.fused_ibn_ref(x, w1, w2)),
        rtol=3e-5, atol=3e-5)


def test_lowered_ragged_ibn_matches_ref():
    """Lowering an IBN with odd extents (197 pixels, d_ff=304) must emit
    imperfect blocks with the raggedness reported explicitly, and those
    block params must still pass the kernel-vs-ref check (the padded
    final blocks are masked in-kernel)."""
    import jax
    from repro.kernels import ops, ref

    exp = Layer("e", "pwconv", k=304, c=160, ox=197)
    proj = Layer("p", "pwconv", k=160, c=304, ox=197)
    lk = lower.lower_ibn(exp, proj, local_buffer=HW.output_rf_bytes)
    assert lk.ragged["m"] == 197 % lk.params["block_m"]
    assert lk.ragged["f"] == 304 % lk.params["block_f"]
    assert lk.ragged["m"] or lk.ragged["f"], "odd extents must go ragged"
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (197, 160))
    w1 = jax.random.normal(ks[1], (160, 304)) * 0.1
    w2 = jax.random.normal(ks[2], (304, 160)) * 0.1
    out = ops.fused_ibn(x, w1, w2, block_m=lk.params["block_m"],
                        block_f=lk.params["block_f"])
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.fused_ibn_ref(x, w1, w2)),
        rtol=3e-5, atol=3e-5)


def test_lowered_matmul_ln_matches_ref():
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    mln = [v for v in SCHED.lowered.values() if v["kernel"] == "matmul_ln"]
    params = mln[0] if mln else {"block_m": 32, "block_k": 32}
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    m, k, n = 64, 64, 48
    x = jax.random.normal(ks[0], (m, k))
    w = jax.random.normal(ks[1], (k, n)) * 0.1
    b = jax.random.normal(ks[2], (n,)) * 0.1
    g = jnp.ones((n,))
    be = jnp.zeros((n,))
    bk = min(params["block_k"], k)
    out = ops.matmul_ln(x, w, b, g, be,
                        block_m=min(params["block_m"], m), block_k=bk)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.matmul_ln_ref(x, w, b, g, be)),
        rtol=3e-5, atol=3e-5)


def test_snap_subsublane_never_exceeds_extent():
    """Regression: for extents below the 8-row sublane (late-stage
    7-pixel rows) every emitted block must fit the extent, with the
    ragged metadata matching the launch — including the infeasible-
    buffer fallback of lower_ibn, which used to emit a raw 8-row block
    against a 7-row extent (larger than the padded extent it claimed)."""
    for ext in (1, 2, 3, 5, 7):
        b, r = lower._snap(64, lower._SUBLANE, 256, ext)
        assert 1 <= b <= ext, (ext, b)
        assert r == ext % b, (ext, b, r)
    exp = Layer("e", "pwconv", k=304, c=160, ox=7)
    proj = Layer("p", "pwconv", k=160, c=304, ox=7)
    for buf in (0, HW.output_rf_bytes):    # fallback + searched paths
        lk = lower.lower_ibn(exp, proj, local_buffer=buf)
        assert lk.params["block_m"] <= 7, (buf, lk.params)
        assert lk.ragged["m"] == 7 % lk.params["block_m"], (buf, lk)
        assert lk.ragged["f"] == 304 % lk.params["block_f"], (buf, lk)


def test_subsublane_ibn_oracle():
    """In-kernel mask oracle at a sub-sublane pixel extent: the lowered
    fused_ibn blocks for a 7-pixel IBN must reproduce the reference
    exactly (the padded rows/columns contribute nothing)."""
    import jax
    from repro.kernels import ops, ref

    exp = Layer("e", "pwconv", k=52, c=40, ox=7)
    proj = Layer("p", "pwconv", k=40, c=52, ox=7)
    lk = lower.lower_ibn(exp, proj, local_buffer=HW.output_rf_bytes)
    assert lk.params["block_m"] <= 7
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(ks[0], (7, 40))
    w1 = jax.random.normal(ks[1], (40, 52)) * 0.1
    w2 = jax.random.normal(ks[2], (52, 40)) * 0.1
    out = ops.fused_ibn(x, w1, w2, block_m=lk.params["block_m"],
                        block_f=lk.params["block_f"])
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.fused_ibn_ref(x, w1, w2)),
        rtol=3e-5, atol=3e-5)


def test_subsublane_matmul_ln_oracle():
    """7 pixel rows x 13-wide reduction: both the row block and the
    ragged final reduction block sit below the sublane; the masked
    kernel must still match the reference (no over-read, no stats
    contamination from the padding)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    mac = Layer("m", "pwconv", k=24, c=13, ox=7)
    norm = Layer("n", "norm", c=24, ox=7)
    lk = lower.lower_matmul_ln(mac, norm, tile_x=7, tile_c=13)
    assert lk.params["block_m"] <= 7
    assert lk.params["block_k"] <= 13
    assert lk.ragged["m"] == 7 % lk.params["block_m"]
    assert lk.ragged["k"] == 13 % lk.params["block_k"]
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(ks[0], (7, 13))
    w = jax.random.normal(ks[1], (13, 24)) * 0.1
    b = jax.random.normal(ks[2], (24,)) * 0.1
    g, be = jnp.ones((24,)), jnp.zeros((24,))
    out = ops.matmul_ln(x, w, b, g, be, block_m=lk.params["block_m"],
                        block_k=lk.params["block_k"])
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.matmul_ln_ref(x, w, b, g, be)),
        rtol=3e-5, atol=3e-5)


def test_subsublane_attention_oracle():
    """7-token sequence through the lowered flash-attention blocks: the
    online softmax over a ragged sub-sublane kv extent must match the
    reference (kv_len masks the padded keys)."""
    import jax
    from repro.kernels import ops, ref

    qk = Layer("qk", "matmul", b=2, k=7, c=8, ox=7)
    lk = lower.lower_attention(qk, tile_x=4, seq=7)
    assert lk.params["block_q"] <= 7 and lk.params["block_k"] <= 7
    assert lk.ragged["q"] == 7 % lk.params["block_q"]
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (1, 2, 7, 8))
    k = jax.random.normal(ks[1], (1, 2, 7, 8))
    v = jax.random.normal(ks[2], (1, 2, 7, 8))
    out = ops.flash_attention(q, k, v, causal=False,
                              block_q=lk.params["block_q"],
                              block_k=lk.params["block_k"])
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_lowered_attention_matches_ref():
    import jax
    from repro.kernels import ops, ref

    vit = vit_workload(img_size=64, patch=16, dim=64, depth=1, heads=2)
    sched = auto_schedule(vit, HW, workload="vit-16tok")
    fa = [v for v in sched.lowered.values()
          if v["kernel"] == "flash_attention"]
    assert fa, "ViT attention must lower to flash_attention blocks"
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 2, 16, 32))
    k = jax.random.normal(ks[1], (1, 2, 16, 32))
    v = jax.random.normal(ks[2], (1, 2, 16, 32))
    out = ops.flash_attention(q, k, v, causal=False,
                              block_q=fa[0]["block_q"],
                              block_k=fa[0]["block_k"])
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
