"""The ``repro.obs`` observability stack: tracer semantics, no-op
neutrality, search-stack instrumentation, cache replay provenance, the
explain report, and the CLI surface.

The load-bearing property is *neutrality*: with no active tracer every
hook is a no-op and ``auto_schedule`` produces bit-identical Schedule
documents traced vs untraced (the golden pins in ``test_search.py``
stay authoritative for the absolute results).  Everything else — span
nesting, decision-provenance counters, structured cache replay
outcomes, the markdown explain — is the new observable surface this
file pins.
"""
import dataclasses
import json
import subprocess
import sys
import threading

import pytest

from repro import obs
from repro.core.costmodel import HWSpec
from repro.obs.tracer import Span, Tracer
from repro.search import auto_schedule, get_workload, sweep_memory
from repro.search.cache import (SEARCH_VERSION, cached_search,
                                schedule_key)
from repro.search.perf import PerfRecorder

HW = HWSpec()
KB = 1024
_SIZINGS = {"rf": (16 * KB, 32 * KB)}
ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
       "JAX_PLATFORMS": "cpu"}


def _wl():
    return get_workload("edgenext-reduced")


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_span_nesting_counters_gauges_events():
    t = Tracer()
    with t.span("outer", workload="x"):
        t.count("hits", 2)
        t.count("hits")
        t.gauge("util", 0.5)
        t.gauge("util", 0.75)           # last write wins
        t.event("marker", layer="a")
        with t.span("inner"):
            pass
    assert [r.name for r in t.roots] == ["outer"]
    outer = t.roots[0]
    assert outer.attrs == {"workload": "x"}
    assert [c.name for c in outer.children] == ["marker", "inner"]
    assert outer.dur_s >= outer.children[1].dur_s >= 0.0
    assert outer.children[0].dur_s == 0.0          # events are instant
    assert t.counters == {"hits": 3}
    assert t.gauges == {"util": 0.75}
    assert t.span_count() == 3


def test_span_json_roundtrip():
    t = Tracer()
    with t.span("a", k=1):
        t.event("e", v="s")
    doc = t.roots[0].to_json()
    back = Span.from_json(json.loads(json.dumps(doc)))
    assert [s.name for s in back.walk()] == ["a", "e"]
    assert back.attrs == {"k": 1}
    assert back.children[0].attrs == {"v": "s"}


def test_threads_get_independent_stacks_and_tids():
    t = Tracer()

    def work(tag):
        with t.span(tag):
            with t.span(f"{tag}.child"):
                pass

    th = [threading.Thread(target=work, args=(f"t{i}",)) for i in (0, 1)]
    with t.span("main"):
        for x in th:
            x.start()
        for x in th:
            x.join()
    # thread roots never nest under another thread's open span: the
    # main span and both worker spans are all roots, on distinct tids
    names = sorted(r.name for r in t.roots)
    assert names == ["main", "t0", "t1"]
    tids = {r.tid for r in t.roots}
    assert len(tids) == 3
    for r in t.roots:
        for c in r.children:
            assert c.tid == r.tid


def test_merge_tables_rebases_and_accumulates():
    w = Tracer()                 # the "worker"
    with w.span("auto"):
        w.count("k", 2)
        w.gauge("g", 1.0)
        with w.span("spatial"):
            pass
    host = Tracer()
    host.count("k", 1)
    with host.span("dse"):
        host.merge_tables(w.to_tables(), offset=10.0, label="worker0")
    dse = host.roots[0]
    assert [c.name for c in dse.children] == ["auto"]
    merged = dse.children[0]
    assert merged.attrs["worker"] == "worker0"
    assert merged.t0 >= 10.0 and merged.children[0].t0 >= 10.0
    assert merged.tid != dse.tid           # own track in the viewer
    assert host.counters == {"k": 3}
    assert host.gauges == {"g": 1.0}


def test_ambient_hooks_are_noops_without_tracer():
    assert obs.current() is None
    with obs.span("nothing", k=1):         # shared nullcontext
        obs.count("c")
        obs.gauge("g", 1.0)
        obs.event("e")
    assert obs.current() is None
    with obs.tracing() as t:
        assert obs.current() is t
        with obs.tracing() as t2:          # nesting restores the outer
            assert obs.current() is t2
        assert obs.current() is t
    assert obs.current() is None


# ---------------------------------------------------------------------------
# search-stack instrumentation
# ---------------------------------------------------------------------------


def test_tracing_keeps_schedules_bit_identical():
    """The acceptance property: an active tracer never changes a
    searched schedule."""
    wl = _wl()
    plain = auto_schedule(wl, HW, workload="edgenext-reduced")
    with obs.tracing():
        traced = auto_schedule(wl, HW, workload="edgenext-reduced")
    assert dataclasses.asdict(plain) == dataclasses.asdict(traced)


def test_auto_span_tree_and_provenance_counters():
    with obs.tracing() as t:
        auto_schedule(_wl(), HW, workload="edgenext-reduced")
    assert [r.name for r in t.roots] == ["auto"]
    auto = t.roots[0]
    kids = [c.name for c in auto.children]
    for name in ("spatial", "partition", "tiles", "temporal", "lower",
                 "evaluate"):
        assert name in kids, kids
    part = next(c for c in auto.children if c.name == "partition")
    assert "fusion" in [c.name for c in part.children]
    # decision provenance: enumerated vs pruned vs evaluated
    c = t.counters
    assert c["mapper.spatial.pairs_enumerated"] > 0
    assert c["mapper.spatial.factored_evaluated"] > 0
    assert c["fusion.spans_probed"] > c["fusion.groups"] > 0
    assert c["mapper.temporal.tiles_evaluated"] > 0
    assert any(k.startswith("lower.kernel.") for k in c)
    # per-layer mapping events + fusion cut events with the traffic
    # delta justifying each boundary
    evs = [s for s in auto.walk() if s.name == "mapper.spatial"]
    assert evs and all("mapping" in e.attrs for e in evs)
    cuts = [s for s in auto.walk() if s.name == "fusion.cut"]
    assert len(cuts) == c["fusion.groups"]
    assert all("boundary_spill_bytes" in e.attrs for e in cuts)
    assert any(e.attrs.get("margin_pj") is not None for e in cuts)
    assert 0.0 < t.gauges["auto.spatial_util"] <= 1.0


def test_dse_span_wraps_auto_serial_and_parallel():
    wl = _wl()
    with obs.tracing() as t:
        pts = sweep_memory(wl, HW, sizings=_SIZINGS,
                           workload="edgenext-reduced")
    assert [r.name for r in t.roots] == ["dse"]
    autos = [c for c in t.roots[0].children if c.name == "auto"]
    assert len(autos) == len(pts) == 2

    with obs.tracing() as tp:
        ptsp = sweep_memory(wl, HW, sizings=_SIZINGS,
                            workload="edgenext-reduced", parallel=2)
    dse = tp.roots[0]
    autos = [c for c in dse.children if c.name == "auto"]
    assert len(autos) == 2
    # worker trees were merged back: labeled, rebased into the dse
    # interval, each on its own track id
    assert sorted(a.attrs.get("worker", "") for a in autos) == \
        ["worker0", "worker1"]
    for a in autos:
        assert dse.t0 <= a.t0 <= dse.t0 + dse.dur_s
        assert a.tid != dse.tid
    assert tp.counters.get("mapper.spatial.pairs_enumerated", 0) > 0
    assert [p.edp for p in ptsp] == [p.edp for p in pts]


# ---------------------------------------------------------------------------
# cache replay provenance (+ the tile_mode threading bugfix)
# ---------------------------------------------------------------------------


def _counters(fn):
    with obs.tracing() as t:
        out = fn()
    return out, t.counters


def test_cache_miss_then_hit(tmp_path):
    wl = _wl()
    _, c1 = _counters(lambda: cached_search(
        wl, HW, workload="w", cache_dir=tmp_path))
    assert c1.get("cache.miss") == 1 and c1.get("cache.store") == 1
    assert "cache.hit" not in c1
    _, c2 = _counters(lambda: cached_search(
        wl, HW, workload="w", cache_dir=tmp_path))
    assert c2.get("cache.hit") == 1
    assert "cache.miss" not in c2 and "cache.rename_remap" not in c2


def test_cache_rename_remap(tmp_path):
    wl = _wl()
    cached_search(wl, HW, workload="w", cache_dir=tmp_path)
    renamed = [dataclasses.replace(l, name=f"r{i}")
               for i, l in enumerate(wl)]
    sched, c = _counters(lambda: cached_search(
        renamed, HW, workload="w", cache_dir=tmp_path))
    assert c.get("cache.hit") == 1 and c.get("cache.rename_remap") == 1
    assert set(sched.mappings) <= {f"r{i}" for i in range(len(wl))}


def test_cache_version_reject(tmp_path):
    wl = _wl()
    cached_search(wl, HW, workload="w", cache_dir=tmp_path)
    art = next(tmp_path.glob("w-*.json"))
    doc = json.loads(art.read_text())
    doc["version"] = SEARCH_VERSION - 1
    art.write_text(json.dumps(doc))
    _, c = _counters(lambda: cached_search(
        wl, HW, workload="w", cache_dir=tmp_path))
    assert c.get("cache.version_reject") == 1
    assert c.get("cache.miss") == 1 and c.get("cache.store") == 1


@pytest.mark.parametrize("breakage", ["truncate", "key_mismatch"])
def test_cache_corrupt(tmp_path, breakage):
    wl = _wl()
    cached_search(wl, HW, workload="w", cache_dir=tmp_path)
    art = next(tmp_path.glob("w-*.json"))
    if breakage == "truncate":
        art.write_text(art.read_text()[:40])
    else:
        doc = json.loads(art.read_text())
        doc["key"] = "0" * 16
        art.write_text(json.dumps(doc))
    _, c = _counters(lambda: cached_search(
        wl, HW, workload="w", cache_dir=tmp_path))
    assert c.get("cache.corrupt") == 1
    assert c.get("cache.miss") == 1 and c.get("cache.store") == 1


def test_cache_replay_events_carry_outcomes(tmp_path):
    wl = _wl()
    with obs.tracing() as t:
        cached_search(wl, HW, workload="w", cache_dir=tmp_path)
        cached_search(wl, HW, workload="w", cache_dir=tmp_path)
    evs = [s for r in t.roots for s in r.walk()
           if s.name == "cache.replay"]
    assert [e.attrs["outcome"] for e in evs] == ["miss", "hit"]


def test_cached_search_threads_tile_mode(tmp_path):
    """The satellite bugfix: tile_mode reaches both the key and the
    search, so a pow2 request neither replays nor stores a
    full-enumeration artifact."""
    wl = _wl()
    assert schedule_key(wl, HW, tile_mode="pow2") != schedule_key(wl, HW)
    full = cached_search(wl, HW, workload="w", cache_dir=tmp_path)
    p2, c = _counters(lambda: cached_search(
        wl, HW, workload="w", cache_dir=tmp_path, tile_mode="pow2"))
    assert c.get("cache.miss") == 1            # distinct key: no replay
    assert p2.tile_mode == "pow2" and full.tile_mode == "full"
    assert len(list(tmp_path.glob("w-*.json"))) == 2
    # the pow2 artifact replays as pow2 on the next request
    p2b, c2 = _counters(lambda: cached_search(
        wl, HW, workload="w", cache_dir=tmp_path, tile_mode="pow2"))
    assert c2.get("cache.hit") == 1
    assert dataclasses.asdict(p2b) == dataclasses.asdict(p2)


# ---------------------------------------------------------------------------
# PerfRecorder edge cases (compatibility view over the tracer)
# ---------------------------------------------------------------------------


def test_hit_rate_zero_lookups_and_table_restriction():
    p = PerfRecorder()
    assert p.hit_rate() == 0.0                 # no lookups: not a crash
    assert p.hit_rate("spatial") == 0.0
    p.count("memo.spatial.hit", 3)
    p.count("memo.spatial.miss", 1)
    p.count("memo.temporal.miss", 4)
    assert p.hit_rate("spatial") == pytest.approx(0.75)
    assert p.hit_rate("temporal") == 0.0
    assert p.hit_rate() == pytest.approx(3 / 8)
    assert p.hit_rate("nosuch") == 0.0


def test_merge_disjoint_and_overlapping():
    p = PerfRecorder()
    with p.phase("a"):
        pass
    a0 = p.phase_s["a"]
    p.count("memo.t.hit", 2)
    p.merge({"b": 0.5}, {"memo.t.miss": 1})            # disjoint
    assert p.phase_s == {"a": a0, "b": 0.5}
    p.merge({"a": 1.0, "b": 0.25}, {"memo.t.hit": 3})  # overlapping
    assert p.phase_s["a"] == pytest.approx(a0 + 1.0)
    assert p.phase_s["b"] == pytest.approx(0.75)
    assert p.counters == {"memo.t.hit": 5, "memo.t.miss": 1}
    assert p.total_s == pytest.approx(sum(p.phase_s.values()))


def test_rows_ordering_stable():
    p = PerfRecorder()
    for name in ("zeta", "alpha", "mid"):
        with p.phase(name):
            pass
    p.count("memo.b.hit")
    p.count("memo.a.miss")
    names = [r[0] for r in p.rows("x")]
    assert names == [r[0] for r in p.rows("x")]        # idempotent
    assert names[:3] == ["x.phase.alpha_ms", "x.phase.mid_ms",
                         "x.phase.zeta_ms"]            # sorted
    assert names[3:] == ["x.total_ms", "x.memo.a.hit_rate",
                         "x.memo.b.hit_rate", "x.memo.hit_rate"]


def test_perf_recorder_phases_nest_under_active_span():
    p = PerfRecorder()
    with obs.tracing() as t:
        with obs.span("auto"):
            with p.phase("spatial"):
                pass
    assert [c.name for c in t.roots[0].children] == ["spatial"]
    assert p.phase_s["spatial"] > 0.0          # legacy table still fed


# ---------------------------------------------------------------------------
# exporters + explain
# ---------------------------------------------------------------------------


def test_chrome_trace_and_bench_rows():
    with obs.tracing() as t:
        with obs.span("auto", workload="w"):
            obs.count("fusion.groups", 2)
            obs.gauge("auto.edp", 1.5)
            obs.event("cache.replay", outcome="hit")
    doc = obs.chrome_trace(t)
    names = [e["name"] for e in doc["traceEvents"]]
    assert names == ["auto", "cache.replay"]
    ev = doc["traceEvents"][0]
    assert ev["ph"] == "X" and ev["dur"] >= 0 and \
        ev["args"] == {"workload": "w"}
    assert doc["otherData"]["counters"] == {"fusion.groups": 2}
    json.dumps(doc)                            # serializable end to end
    rows = obs.bench_rows(t)
    byname = {n: v for n, v, _ in rows}
    assert byname["search.obs.spans"] == 2.0
    assert byname["search.obs.fusion.groups"] == 2.0
    assert byname["search.obs.auto.edp"] == 1.5


def test_explain_report_content():
    wl = _wl()
    sched = auto_schedule(wl, HW, workload="edgenext-reduced")
    out = obs.explain_schedule(wl, sched)      # hw rebuilt from artifact
    for section in ("## Schedule explain: edgenext-reduced",
                    "### Per-level traffic / energy breakdown",
                    "### Per-layer mapping decisions",
                    "### Fusion groups"):
        assert section in out
    for level in ("rf", "sram", "dram"):
        assert f"| {level} |" in out
    for name in sched.mappings:
        assert name in out
    assert "**total**" in out and "100.0%" in out
    # every markdown table row must keep its header's column count:
    # mapping labels carry '|' and must arrive escaped ("\|" does not
    # split a GFM cell, a bare "|" does)
    header_cols = None
    for line in out.splitlines() + [""]:
        if not line.startswith("|"):
            header_cols = None
            continue
        cols = line.count("|") - line.count("\\|")
        if header_cols is None:
            header_cols = cols
        assert cols == header_cols, line
    # explicit hw and artifact-reconstructed hw agree exactly
    assert out == obs.explain_schedule(wl, sched, HW)


# ---------------------------------------------------------------------------
# CLI + import purity
# ---------------------------------------------------------------------------


def test_cli_trace_explain_smoke(tmp_path):
    trace = tmp_path / "t.json"
    r = subprocess.run(
        [sys.executable, "-m", "repro.search", "--workload",
         "edgenext-reduced", "--trace", str(trace), "--explain"],
        capture_output=True, text=True, timeout=300, env=ENV,
        cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.loads(trace.read_text())
    evs = doc["traceEvents"]
    by = {}
    for e in evs:
        by.setdefault(e["name"], []).append(e)
    auto = by["auto"][0]

    def inside(e):
        return (auto["ts"] <= e["ts"] and
                e["ts"] + e["dur"] <= auto["ts"] + auto["dur"] + 1e3)

    for name in ("spatial", "fusion", "tiles", "lower", "evaluate"):
        assert name in by, sorted(by)
        assert all(inside(e) for e in by[name]), name
    assert doc["otherData"]["counters"]["fusion.groups"] > 0
    assert "search.obs.spans," in r.stdout
    assert "### Per-layer mapping decisions" in r.stdout
    assert "# wrote trace" in r.stdout


def test_cli_dse_trace_nests_autos(tmp_path):
    trace = tmp_path / "t.json"
    r = subprocess.run(
        [sys.executable, "-m", "repro.search", "--workload",
         "edgenext-reduced", "--dse-mem", "rf", "--trace", str(trace)],
        capture_output=True, text=True, timeout=300, env=ENV,
        cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.loads(trace.read_text())
    dse = [e for e in doc["traceEvents"] if e["name"] == "dse"]
    autos = [e for e in doc["traceEvents"] if e["name"] == "auto"]
    assert len(dse) == 1 and len(autos) >= 2
    lo, hi = dse[0]["ts"], dse[0]["ts"] + dse[0]["dur"]
    assert all(lo <= a["ts"] <= hi for a in autos)
