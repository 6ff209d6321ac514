"""Search fast-path acceptance: memoization correctness + instrumentation.

The wall-clock speedup itself is reported as ``search.perf.*`` BENCH
rows (benchmarks/dse.py) — never asserted here, where a noisy CI box
would make it flake.  What IS asserted is the half that must never
regress silently:

  * dedup-on and dedup-off (brute-force) ``auto_schedule`` produce
    BIT-IDENTICAL Schedule documents on every registered workload —
    the memo tables, pruned enumeration, and hoisted DP are exact;
  * the memo actually bites: hit rate > 0.5 on MobileViT-S;
  * layer/HW signatures capture content and nothing else (cosmetic
    renames keep cache keys, dim changes break them);
  * placement-aware headline costing is bit-neutral on the paper's
    3-level design and splits the rows on a deeper hierarchy;
  * the process-pool DSE fan-out returns the same points as serial.
"""
import dataclasses
import subprocess
import sys

import pytest

from repro.core.costmodel import HWSpec, cost_network_scheduled
from repro.core.memory import split_sram_hierarchy
from repro.core.workload import MAC_OPS, Layer
from repro.search import (WORKLOADS, auto_schedule, evaluate_schedule,
                          get_workload, schedule_key, sweep_memory)
from repro.search import mapper, partition
from repro.search.memo import SearchMemo
from repro.search.perf import PerfRecorder

HW = HWSpec()
KB = 1024


# ---------------------------------------------------------------------------
# dedup-on == dedup-off, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", WORKLOADS)
def test_dedup_modes_bit_identical(name):
    """The acceptance property: for every registered workload the
    memoized fast path and the brute-force reference produce the same
    Schedule JSON — same key, same decisions, same costs (floats
    compared exactly, not approximately)."""
    wl = get_workload(name)
    fast = auto_schedule(wl, HW, workload=name, dedup=True)
    brute = auto_schedule(wl, HW, workload=name, dedup=False)
    assert fast.key == brute.key
    assert fast.cost == brute.cost          # exact float equality
    assert dataclasses.asdict(fast) == dataclasses.asdict(brute)


def test_dedup_modes_bit_identical_on_deep_hierarchy():
    """Same property on a 4-level hierarchy, where placements and
    residence levels actually differ from the paper design."""
    hw = HWSpec(hierarchy=split_sram_hierarchy())
    wl = get_workload("edgenext-s")
    fast = auto_schedule(wl, hw, dedup=True)
    brute = auto_schedule(wl, hw, dedup=False)
    assert dataclasses.asdict(fast) == dataclasses.asdict(brute)


def test_dedup_modes_bit_identical_pow2_and_fixed():
    """Ablation modes ride the same fast path: tile_mode and the
    fixed-wiring restriction must stay bit-exact too."""
    wl = get_workload("edgenext-reduced")
    for kw in ({"tile_mode": "pow2"}, {"tile_mode": "legacy"},
               {"reconfigurable": False}):
        fast = auto_schedule(wl, HW, dedup=True, **kw)
        brute = auto_schedule(wl, HW, dedup=False, **kw)
        assert dataclasses.asdict(fast) == dataclasses.asdict(brute), kw


def test_memo_hit_rate_on_mobilevit():
    """MobileViT-S registers 156 layers but far fewer unique shapes —
    the memo must catch more than half of all lookups."""
    perf = PerfRecorder()
    auto_schedule(get_workload("mobilevit-s"), HW,
                  workload="mobilevit-s", perf=perf)
    assert perf.hit_rate() > 0.5, perf.counters
    # and the per-table counters all saw traffic
    for table in ("spatial", "temporal", "group_tile"):
        hits = perf.counters.get(f"memo.{table}.hit", 0)
        assert hits > 0, (table, perf.counters)


def test_best_temporal_fast_equals_brute_per_layer():
    """Mapper-level equivalence, both pixelwise-constrained and free,
    including the TemporalChoice internals (placement, level bytes,
    exact energy)."""
    wl = get_workload("edgenext-s")
    memo = SearchMemo()
    seen = set()
    for l in wl:
        if l.op not in MAC_OPS or l.signature in seen:
            continue
        seen.add(l.signature)
        for rp in (False, True):
            fast = mapper.best_temporal(l, HW, require_pixelwise=rp,
                                        memo=memo)
            brute = mapper.best_temporal(l, HW, require_pixelwise=rp,
                                         brute=True)
            assert fast == brute, (l.name, rp)


def test_partition_fast_equals_brute():
    """Partitioner-level equivalence: the hoisted/memoized DP and the
    original per-span derivation return identical groups, edges, and
    total cost."""
    wl = get_workload("mobilevit-s")
    cyc = {l.name: mapper.best_mapping(l, HW.rows, HW.cols).cycles
           for l in wl if l.op in MAC_OPS}
    fast = partition.partition_chain(wl, cyc, HW, memo=SearchMemo())
    brute = partition.partition_chain(wl, cyc, HW)
    assert fast.groups == brute.groups
    assert fast.edges == brute.edges
    assert fast.cost_pj == brute.cost_pj    # exact float equality


# ---------------------------------------------------------------------------
# signatures + cache keys
# ---------------------------------------------------------------------------


def test_layer_signature_ignores_name_and_annotations():
    a = Layer("a", "pwconv", k=64, c=32, ox=196, ibn_role="expand",
              ibn_id=7)
    b = Layer("totally.different", "pwconv", k=64, c=32, ox=196)
    c = Layer("a", "pwconv", k=64, c=33, ox=196)
    d = Layer("a", "matmul", k=64, c=32, ox=196)
    assert a.signature == b.signature
    assert a.signature != c.signature
    assert a.signature != d.signature


def test_schedule_key_stable_across_cosmetic_renames():
    """The cache-key satellite: renaming layers (or dropping the ibn
    annotations) keeps the key; changing any dim or the HW breaks it."""
    wl = get_workload("edgenext-reduced")
    renamed = [dataclasses.replace(l, name=f"layer{i}", ibn_role=None,
                                   ibn_id=-1)
               for i, l in enumerate(wl)]
    assert schedule_key(wl, HW) == schedule_key(renamed, HW)
    bumped = list(wl)
    bumped[0] = dataclasses.replace(wl[0], k=wl[0].k + 1)
    assert schedule_key(bumped, HW) != schedule_key(wl, HW)
    hw2 = dataclasses.replace(HW, sram_bytes=HW.sram_bytes * 2)
    assert schedule_key(wl, hw2) != schedule_key(wl, HW)
    assert schedule_key(wl, HW, "pow2") != schedule_key(wl, HW)


def test_cached_replay_remaps_renamed_layers(tmp_path):
    """A rename-stable cache key must deliver a *usable* schedule after
    the rename: the replayed artifact's name-keyed decisions are
    remapped positionally onto the new names, and evaluating it equals
    evaluating a fresh search on the renamed chain."""
    from repro.search import cached_search
    wl = get_workload("edgenext-reduced")
    s1 = cached_search(wl, HW, workload="edgenext-reduced",
                       cache_dir=tmp_path)
    renamed = [dataclasses.replace(l, name=f"renamed{i}")
               for i, l in enumerate(wl)]
    s2 = cached_search(renamed, HW, workload="edgenext-reduced",
                       cache_dir=tmp_path)
    assert len(list(tmp_path.glob("*.json"))) == 1   # same key: a hit
    assert s2.key == s1.key
    assert set(s2.mappings) == {l.name for l in renamed
                                if l.op in MAC_OPS}
    fresh = auto_schedule(renamed, HW, workload="edgenext-reduced")
    assert s2.mappings == fresh.mappings
    assert s2.groups == fresh.groups
    nc = evaluate_schedule(renamed, s2, HW)
    assert nc.energy_j == fresh.cost["energy_j"]
    assert nc.latency_s == fresh.cost["latency_s"]


def test_guards_reject_contradictory_modes():
    wl = get_workload("edgenext-reduced")
    with pytest.raises(ValueError):
        auto_schedule(wl, HW, dedup=False, memo=SearchMemo())
    with pytest.raises(ValueError):
        sweep_memory(wl, HW, sizings={"rf": (16 * KB, 32 * KB)},
                     memo=SearchMemo(), parallel=2)


def test_signature_field_lists_track_the_dataclasses():
    """Canary: the content signatures hand-enumerate the fields they
    hash (``_layer_signature``, ``_hw_signature``, the hierarchy
    signatures, and ``auto_schedule``'s hw_doc).  Adding a field to any
    of these dataclasses MUST update those enumerations (and bump
    SEARCH_VERSION) or two differing specs would silently share memo
    entries and cache keys — this assert is the tripwire."""
    from repro.core.memory import MemoryLevel
    assert {f.name for f in dataclasses.fields(Layer)} == {
        "name", "op", "b", "k", "c", "ox", "oy", "fx", "fy", "bits",
        "ibn_role", "ibn_id", "scan_kind"}, \
        "Layer grew a field: update workload._layer_signature"
    assert {f.name for f in dataclasses.fields(HWSpec)} == {
        "rows", "cols", "clock_hz", "bits", "e_mac", "static_mw",
        "hierarchy"}, \
        "HWSpec grew a field: update costmodel._hw_signature + " \
        "auto_schedule's hw_doc"
    assert {f.name for f in dataclasses.fields(MemoryLevel)} == {
        "name", "bytes", "pj_per_byte", "bus_bytes_per_cycle",
        "serves", "partitions"}, \
        "MemoryLevel grew a field: update MemoryHierarchy.signature/" \
        "cap_signature"


def test_hw_signature_content_addressed():
    assert HWSpec().signature == HW.signature
    assert HWSpec(rows=8).signature != HW.signature
    assert HWSpec(e_sram_byte=2.0).signature != HW.signature
    assert HWSpec(hierarchy=split_sram_hierarchy()).signature \
        != HW.signature
    h = HW.hierarchy
    assert h.cap_signature == \
        HW.hierarchy.resized("sram", pj_per_byte=9.9).cap_signature
    assert h.signature != \
        HW.hierarchy.resized("sram", pj_per_byte=9.9).signature
    assert h.cap_signature != \
        HW.hierarchy.resized("sram", bytes=256 * KB).cap_signature


# ---------------------------------------------------------------------------
# placement-aware headline costing (ROADMAP satellite)
# ---------------------------------------------------------------------------


SCHED = auto_schedule(get_workload("edgenext-s"), HW,
                      workload="edgenext-s")


def _traffic_rows(nc):
    return [lc.traffic for lc in nc.layers]


def test_placement_costing_neutral_on_paper_design():
    """On the 3-level paper hierarchy every placed fill resolves to the
    SRAM, so the placement-aware rows reproduce the lumped
    stream-level accounting bit-exactly (the golden EdgeNeXt snapshot
    changed only its version field in this PR)."""
    wl = get_workload("edgenext-s")
    mappings = {k: tuple(v) for k, v in SCHED.mappings.items()}
    with_pl = cost_network_scheduled(
        wl, HW, mappings=mappings,
        fused_nonlinear=set(SCHED.fused_nonlinear),
        edges=SCHED.spill_edge_list(), placements=SCHED.placements)
    lumped = cost_network_scheduled(
        wl, HW, mappings=mappings,
        fused_nonlinear=set(SCHED.fused_nonlinear),
        edges=SCHED.spill_edge_list())
    assert _traffic_rows(with_pl) == _traffic_rows(lumped)
    assert with_pl.energy_j == lumped.energy_j


def test_placement_costing_splits_rows_on_deep_hierarchy():
    """On the 4-level split-SRAM design, weights whose tiles exceed the
    small L1 are placed (and now also *charged*) at the L2 — the rows
    follow the mapper's placements instead of lumping everything at the
    stream level."""
    hw = HWSpec(hierarchy=split_sram_hierarchy())
    wl = get_workload("edgenext-s")
    sched = auto_schedule(wl, hw, workload="edgenext-s")
    assert any(p["weight"] == "l2" for p in sched.placements.values())
    nc = evaluate_schedule(wl, sched, hw)
    tr = nc.traffic_bytes()
    assert tr["l2"] > 0
    mappings = {k: tuple(v) for k, v in sched.mappings.items()}
    lumped = cost_network_scheduled(
        wl, hw, mappings=mappings,
        fused_nonlinear=set(sched.fused_nonlinear),
        edges=sched.spill_edge_list())
    assert tr["l1"] < lumped.traffic_bytes()["l1"]
    # total operand bytes conserved — only the level attribution moved
    assert sum(tr.values()) == sum(lumped.traffic_bytes().values())


# ---------------------------------------------------------------------------
# FastViT workload (satellite)
# ---------------------------------------------------------------------------


def test_fastvit_workload_registered():
    from repro.core.workload import ibn_groups, total_macs
    wl = get_workload("fastvit-s")
    g = total_macs(wl) / 1e9
    assert 1.0 < g < 2.0, g                 # SA12-like scale
    assert len(ibn_groups(wl)) == sum((2, 2, 6, 2))   # one FFN per block
    wl4 = get_workload("fastvit-s-b4")
    assert total_macs(wl4) == 4 * total_macs(wl)
    assert {"fastvit-s", "fastvit-s-b4"} <= set(WORKLOADS)
    # repeat-heavy by construction: far fewer unique shapes than layers
    assert len({l.signature for l in wl}) < len(wl) / 2
    from repro.core.schedule import evaluate_stack
    sched = auto_schedule(wl, HW, workload="fastvit-s")
    assert sched.cost["edp"] <= evaluate_stack(wl, HW)[-1].edp * (1 + 1e-9)


# ---------------------------------------------------------------------------
# incremental DSE + process-pool fan-out
# ---------------------------------------------------------------------------


def test_sweep_memory_dedup_matches_brute():
    """A sweep-wide shared memo must not leak decisions across variants:
    every point equals its from-scratch counterpart."""
    wl = get_workload("edgenext-reduced")
    sizings = {"rf": (16 * KB, 32 * KB), "sram": (256 * KB, 512 * KB)}
    fast = sweep_memory(wl, HW, sizings=sizings, dedup=True)
    brute = sweep_memory(wl, HW, sizings=sizings, dedup=False)
    assert len(fast) == len(brute) == 4
    for a, b in zip(fast, brute):
        assert a.mem == b.mem
        assert dataclasses.asdict(a.schedule) == \
            dataclasses.asdict(b.schedule)


def test_sweep_memory_parallel_matches_serial():
    """Process-pool fan-out returns the same points as serial AND
    merges the workers' PerfRecorder tables back (the --profile --jobs
    fix): phase wall times and memo counters must be non-zero, not the
    silently-empty recorder the pool used to leave behind."""
    wl = get_workload("edgenext-reduced")
    sizings = {"rf": (16 * KB, 32 * KB)}
    serial = sweep_memory(wl, HW, sizings=sizings)
    perf = PerfRecorder()
    par = sweep_memory(wl, HW, sizings=sizings, parallel=2, perf=perf)
    assert [p.label for p in par] == [p.label for p in serial]
    for a, b in zip(par, serial):
        assert dataclasses.asdict(a.schedule) == \
            dataclasses.asdict(b.schedule)
    # merged worker profiles: every search phase accumulated real time
    for phase in ("spatial", "partition", "temporal", "evaluate"):
        assert perf.phase_s.get(phase, 0.0) > 0.0, (phase, perf.phase_s)
    hits = sum(v for k, v in perf.counters.items() if k.endswith(".hit"))
    miss = sum(v for k, v in perf.counters.items() if k.endswith(".miss"))
    assert hits + miss > 0 and perf.hit_rate() > 0.0
    assert perf.rows("perf")               # renders as BENCH/CLI rows


def test_shared_memo_accumulates_across_variants():
    """Spatial mappings are hierarchy-independent: the second variant
    of a memory sweep must hit the shared spatial table, and group
    tiles shared across equal residence capacities must hit too."""
    wl = get_workload("edgenext-reduced")
    perf = PerfRecorder()
    sweep_memory(wl, HW, sizings={"sram": (256 * KB, 512 * KB)},
                 perf=perf)
    c = perf.counters
    assert c["memo.spatial.hit"] > c["memo.spatial.miss"]
    # sram-only sweep keeps the rf residence budget: per-capacity group
    # tiles from variant 1 serve variant 2 entirely
    assert c["memo.group_tile.hit"] > c["memo.group_tile.miss"]


def test_caller_supplied_memo_reports_to_caller_perf():
    """Passing BOTH memo= and perf= (the documented cross-sweep
    sharing) must land the memo hit/miss counters on the caller's
    recorder, not the memo's private default one."""
    wl = get_workload("edgenext-reduced")
    memo, perf = SearchMemo(), PerfRecorder()
    sweep_memory(wl, HW, sizings={"sram": (256 * KB, 512 * KB)},
                 memo=memo, perf=perf)
    assert perf.counters and perf.hit_rate() > 0.0, perf.counters
    assert perf.counters.get("memo.spatial.hit", 0) > 0


# ---------------------------------------------------------------------------
# instrumentation + CLI
# ---------------------------------------------------------------------------


def test_perf_recorder_rows():
    p = PerfRecorder()
    with p.phase("a"):
        pass
    p.count("memo.spatial.hit", 3)
    p.count("memo.spatial.miss")
    assert p.hit_rate() == pytest.approx(0.75)
    assert p.hit_rate("spatial") == pytest.approx(0.75)
    names = [r[0] for r in p.rows("x")]
    assert "x.phase.a_ms" in names
    assert "x.memo.spatial.hit_rate" in names
    assert "x.total_ms" in names


def test_cli_profile_smoke(tmp_path):
    r = subprocess.run(
        [sys.executable, "-m", "repro.search", "--workload",
         "edgenext-reduced", "--profile"],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "perf.auto.speedup," in r.stdout
    assert "perf.memo.hit_rate," in r.stdout
    assert "cost.edp" in r.stdout
