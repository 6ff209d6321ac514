"""Chunked-recurrence (SCAN) op class: acceptance + property tests.

The load-bearing claims of the scan subsystem:
  * ``rwkv6`` / ``recurrentgemma`` resolve from the workload registry
    (with ``-b<N>`` batch variants) and auto_schedule returns schedules
    whose *searched* chunk beats the fixed chunk=64 baseline on EDP;
  * the chunk-carry dimension (``ox``) is never spatially split — the
    scan mapping enumerator only offers carry-free dims and the scan
    cycle model rejects carry-dim mappings outright;
  * fusion never pulls a scan into a multi-compute tile, and a
    nonlinear tail may cross the chunk boundary only when the [K, V]
    carry state fits a local-level budget;
  * lowering emits real ``rwkv_chunk`` launch params with the searched
    chunk as the block size (ragged final chunk reported explicitly),
    for WKV scans only: Mamba-2's scalar-decay SSD lowers to nothing;
  * the Pallas kernel agrees with the model-level chunked WKV.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dataflow
from repro.core.costmodel import HWSpec
from repro.core.workload import SCAN, Layer, scan_state_bytes, total_macs
from repro.search import (WORKLOADS, auto_schedule, evaluate_schedule,
                          get_workload)
from repro.search import mapper, partition
from repro.search.auto import _auto_schedule

HW = HWSpec()
RWKV_WL = get_workload("rwkv6")
RWKV_SCHED = auto_schedule(RWKV_WL, HW, workload="rwkv6")
RG_WL = get_workload("recurrentgemma")
RG_SCHED = auto_schedule(RG_WL, HW, workload="recurrentgemma")


def _fixed64(wl, name):
    return _auto_schedule(wl, HW, workload=name, reconfigurable=True,
                          tile_mode="full", spatial_mode="factored",
                          dedup=True, memo=None, perf=None, scan_chunk=64)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_scan_workloads_registered():
    assert {"rwkv6", "recurrentgemma"} <= set(WORKLOADS)
    assert sum(l.op == SCAN for l in RWKV_WL) == 24
    assert sum(l.op == SCAN for l in RG_WL) == 18       # 2 of every 3 blocks
    # batch variants resolve through the same -b<N> family as the ViTs
    b4 = get_workload("rwkv6-b4")
    assert total_macs(b4) == 4 * total_macs(RWKV_WL)
    scans = [l for l in b4 if l.op == SCAN]
    assert scans and all(l.b == 4 * 32 for l in scans)


def test_scan_layer_shapes():
    wkv = next(l for l in RWKV_WL if l.op == SCAN)
    assert (wkv.b, wkv.ox, wkv.c, wkv.k) == (32, 512, 64, 64)
    assert scan_state_bytes(wkv) == 4 * 64 * 64
    lru = next(l for l in RG_WL if l.op == SCAN)
    assert (lru.b, lru.ox, lru.c, lru.k) == (1, 448, 1, 2560)


# ---------------------------------------------------------------------------
# acceptance: searched chunk beats the fixed-64 baseline
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("wl,sched,name", [
    (RWKV_WL, RWKV_SCHED, "rwkv6"),
    (RG_WL, RG_SCHED, "recurrentgemma"),
], ids=["rwkv6", "recurrentgemma"])
def test_searched_chunk_beats_fixed64(wl, sched, name):
    """auto_schedule's two-pass chunk selection must never lose to the
    fixed chunk=64 baseline — it re-evaluates the winner in full and
    keeps whichever schedule is actually cheaper."""
    ref = _fixed64(wl, name)
    assert sched.cost["edp"] <= ref.cost["edp"]
    chunks = {t["chunk"] for t in sched.tiles.values() if "chunk" in t}
    assert len(chunks) == 1                      # one network-level chunk
    assert chunks.pop() in (8, 16, 32, 64, 128, 256)


def test_scan_tiles_record_state_residency():
    for l in RWKV_WL:
        if l.op != SCAN:
            continue
        t = RWKV_SCHED.tiles[l.name]
        assert t["state_bytes"] == scan_state_bytes(l)
        assert t["level"] in {lv.name for lv in HW.hierarchy.levels}
        assert RWKV_SCHED.placements[l.name]["state"] == t["level"]


def test_scan_replay_reproduces_search_cost():
    """evaluate_schedule re-derives scan cycles from the stored mapping
    and chunk; the replayed cost must equal the searched one."""
    for sched, wl in ((RWKV_SCHED, RWKV_WL), (RG_SCHED, RG_WL)):
        nc = evaluate_schedule(wl, sched, HW)
        assert nc.edp == sched.cost["edp"]
        assert nc.energy_j == sched.cost["energy_j"]


# ---------------------------------------------------------------------------
# property: the carry dim is never spatially split
# ---------------------------------------------------------------------------


def test_scan_mappings_never_split_carry():
    carry = {"ox", "oy", "fx", "fy"}
    for wl in (RWKV_WL, RG_WL):
        for l in wl:
            if l.op != SCAN:
                continue
            ms = list(mapper.enumerate_scan_mappings(l))
            assert ms, l.name
            for m in ms:
                dims = {d for d, _ in dataflow.as_mapping(m)[0] +
                        dataflow.as_mapping(m)[1]} \
                    if not isinstance(m[0], str) else set(m)
                assert not (dims & carry), (l.name, m)


def test_scan_cycle_model_rejects_carry_dim():
    l = next(l for l in RWKV_WL if l.op == SCAN)
    with pytest.raises(ValueError):
        dataflow.cycles_scan(l, ("ox", "c"), 16, 16, chunk=64)
    with pytest.raises(ValueError):
        dataflow.cycles_scan(l, ("k", "oy"), 16, 16, chunk=64)


def test_searched_scan_mappings_are_carry_free():
    for sched, wl in ((RWKV_SCHED, RWKV_WL), (RG_SCHED, RG_WL)):
        by_name = {l.name: l for l in wl}
        for lname, m in sched.mappings.items():
            if by_name[lname].op != SCAN:
                continue
            flat = m if isinstance(m[0], str) else [
                d for axis in m for d, _ in axis]
            assert set(flat) <= {"b", "k", "c"}, (lname, m)


# ---------------------------------------------------------------------------
# property: fusion legality around the carry
# ---------------------------------------------------------------------------


def test_scan_never_shares_a_tile_with_other_compute():
    """No searched group may contain a scan plus another compute layer:
    the carry serializes the chunk loop, so depth-first co-tiling with a
    neighboring GEMM is illegal by construction."""
    for sched, wl in ((RWKV_SCHED, RWKV_WL), (RG_SCHED, RG_WL)):
        by_name = {l.name: l for l in wl}
        for g in sched.groups:
            sl = [by_name[n] for n in g]
            n_compute = sum(partition._is_compute(l) for l in sl)
            if any(l.op == SCAN for l in sl):
                assert n_compute == 1, g


def test_oversized_state_forces_scan_to_stand_alone():
    """A nonlinear tail may ride the chunk loop only while the carried
    [K, V] state fits a local level; blow the state past every budget
    and the partitioner must cut at the chunk boundary."""
    norm = Layer("tail.norm", "norm", b=1, ox=64, k=4096)
    big = Layer("big.scan", SCAN, b=1, ox=64, c=4096, k=4096)   # 64 MB
    small = Layer("small.scan", SCAN, b=1, ox=64, c=8, k=8)     # 256 B
    for scan, may_fuse in ((big, False), (small, True)):
        part = partition.partition_chain([scan, norm], {}, HW)
        fused = any(g.start == 0 and g.end == 2 and g.fused_nonlinear
                    for g in part.groups)
        if not may_fuse:
            assert not fused, "oversized state fused across the carry"


# ---------------------------------------------------------------------------
# lowering: the searched chunk drives the real kernel
# ---------------------------------------------------------------------------


def test_lowered_rwkv_chunk_params():
    for sched, wl in ((RWKV_SCHED, RWKV_WL), (RG_SCHED, RG_WL)):
        by_name = {l.name: l for l in wl}
        scan_lowered = {n: lk for n, lk in sched.lowered.items()
                        if lk["kernel"] == "rwkv_chunk"}
        scan_names = {l.name for l in wl if l.op == SCAN}
        assert set(scan_lowered) == scan_names
        for n, lk in scan_lowered.items():
            l = by_name[n]
            assert lk["chunk"] == sched.tiles[n]["chunk"]
            assert (lk["bh"], lk["t"], lk["k"], lk["v"]) == \
                (l.b, l.ox, l.c, l.k)
            want_ragged = l.ox % lk["chunk"]
            assert lk.get("ragged", {}).get("t", 0) == want_ragged


def test_ssd_scan_lowers_to_no_kernel():
    """No kernel computes Mamba-2's scalar-decay SSD: its scans emit no
    launch and each counts as an unlowered group, where ``rwkv_chunk``
    (WKV with a bonus) would compute another recurrence."""
    from repro import obs
    from repro.search import lower
    wl = get_workload("granite-h-micro")
    ssd = [l for l in wl if l.op == SCAN]
    assert len(ssd) == 36 and {l.scan_kind for l in ssd} == {"ssd"}
    assert lower.lower_scan(ssd[0], {"chunk": 256}) is None
    wkv_alike = Layer(ssd[0].name, SCAN, b=ssd[0].b, k=ssd[0].k,
                      c=ssd[0].c, ox=ssd[0].ox)
    assert wkv_alike.signature != ssd[0].signature
    with obs.tracing() as t:
        sched = auto_schedule(wl, HW, workload="granite-h-micro")
    assert not {n for n, lk in sched.lowered.items()
                if lk["kernel"] == "rwkv_chunk"}
    assert not {n for n in sched.lowered if ".mamba.ssd" in n}
    assert t.counters["lower.groups_unlowered"] >= len(ssd)
    assert "lower.kernel.rwkv_chunk" not in t.counters


def test_lint_refuses_rwkv_chunk_on_an_ssd_scan():
    from repro.check import lint_doc
    wl = get_workload("granite-h-micro")
    ssd = next(l for l in wl if l.op == SCAN)
    forged = {"kernel": "rwkv_chunk", "chunk": 256, "bh": ssd.b,
              "t": ssd.ox, "k": ssd.c, "v": ssd.k}
    found = lint_doc({"lowered": {ssd.name: forged}}, wl)
    assert [f.code for f in found] == ["lint.scan_kind"]
    wkv = next(l for l in RWKV_WL if l.op == SCAN)
    assert lint_doc({"lowered": {wkv.name: RWKV_SCHED.lowered[wkv.name]}},
                    RWKV_WL) == []


def test_recurrentgemma_seq_is_ragged():
    """The RG workload is deliberately non-dividing (448 = 3*128 + 64)
    so the ragged-chunk path is exercised whenever the search picks a
    chunk above 64."""
    lru = next(l for l in RG_WL if l.op == SCAN)
    assert lru.ox % 128 != 0 and lru.ox % 64 == 0


# ---------------------------------------------------------------------------
# kernel vs model: interpret-mode cross-checks
# ---------------------------------------------------------------------------


def test_kernel_matches_model_wkv():
    """kernels.rwkv_chunk (Pallas, interpret mode) == models.rwkv6's
    chunked WKV (pure JAX) on identical inputs, ragged T included."""
    from repro.kernels import ops
    from repro.models import rwkv6 as m
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    B, T, H, K = 1, 50, 2, 8
    r = jax.random.normal(ks[0], (B, T, H, K)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, K)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, K)) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], (B, T, H, K)) * 0.5)
    u = jax.random.normal(ks[4], (H, K)) * 0.5
    state0 = jnp.zeros((B, H, K, K), jnp.float32)
    want, st_want = m.wkv_chunked_xla(r, k, v, logw, u, state0, chunk=16)

    def flat(x):                                   # [B,T,H,K] -> [BH,T,K]
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, K)
    out, st = ops.wkv_chunked(flat(r), flat(k), flat(v), flat(logw),
                              jnp.tile(u, (B, 1)), chunk=16,
                              interpret=True)
    np.testing.assert_allclose(
        np.asarray(out.reshape(B, H, T, K).transpose(0, 2, 1, 3)),
        np.asarray(want), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st.reshape(B, H, K, K)),
                               np.asarray(st_want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch", [
    "rwkv6-1.6b",
    # the RG reduced forward compiles the conv1d+LRU scan — slow lane,
    # matching the _HEAVY convention in test_arch_smoke
    pytest.param("recurrentgemma-2b", marks=pytest.mark.slow),
])
def test_scan_model_forward_smoke(arch):
    """Reduced-config forward pass of the two scan models: finite
    hidden states at a ragged T (not a chunk multiple)."""
    from repro.configs import get_config, reduced
    from repro.models import get_module, params as P
    cfg = reduced(get_config(arch))
    mod = get_module(cfg)
    params = P.init_params(jax.random.PRNGKey(0), mod.param_defs(cfg))
    T = 11
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, T), 0,
                                cfg.vocab_size)
    hidden, _ = mod.forward(cfg, params, {"tokens": tokens}, remat=False)
    assert hidden.shape[:2] == (1, T)
    assert np.isfinite(np.asarray(hidden)).all()
