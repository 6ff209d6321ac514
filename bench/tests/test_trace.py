"""The trace reduction on a small trace recorded on a TPU v5e chip
(``bench/testdata/record.py``): three rounds of eight bf16 2048x2048
matmuls (``dispatch``, ``wait``), each followed by 20 ms of
``idle-for-arrival``."""
from pathlib import Path

import numpy as np
import pytest

import trace as T

SMALL = Path(__file__).resolve().parents[1] / "testdata" / "small.xplane.pb"
PHASES = ("dispatch", "wait", "idle-for-arrival")


@pytest.fixture(scope="module")
def small():
    return T.load(SMALL, PHASES)


def test_device_ops_and_window_are_found(small):
    assert list(small.ops) == ["/device:TPU:0"]
    assert len(small.ops["/device:TPU:0"]) >= 3 * 8
    lo, hi = T.window_of(small)
    assert {n for n, _, _ in small.host} == set(PHASES) | {"window"}
    assert all(lo <= s and e <= hi for n, s, e in small.host)


def test_device_clock_is_shifted_onto_the_hosts(small):
    """Each program's device end lands between the host's launch and its
    completion callback: the shift found is 1-3 ms on this chip."""
    (offset,) = small.offset_ns.values()
    assert 1e6 < offset < 3e6
    rounds = [(s, e) for n, s, e in small.host if n == "wait"]
    fusions = [(s, e) for n, s, e in small.ops["/device:TPU:0"]
               if "fusion" in n]
    assert len(rounds) == 3 and len(fusions) == 24
    for i, (ws, we) in enumerate(rounds):
        chain = fusions[8 * i: 8 * i + 8]
        assert ws - 2e6 < chain[0][0] and chain[-1][1] <= we


def test_busy_union_matches_a_timeline(small):
    """Busy time against a 1 us timeline of the same op intervals."""
    r = T.reduce(small)
    lo, hi = T.window_of(small)
    n = int((hi - lo) / 1000) + 1
    line = np.zeros(n, bool)
    for s, e in T.clip(((s, e) for _, s, e in small.ops["/device:TPU:0"]),
                          lo, hi):
        line[int((s - lo) / 1000):int((e - lo) / 1000) + 1] = True
    ops = len(small.ops["/device:TPU:0"])
    assert r["busy_s"] == pytest.approx(line.sum() * 1e-6, abs=2 * ops * 1e-6)
    assert r["busy_s"] <= sum(e - s for _, s, e in small.ops[
        "/device:TPU:0"]) * 1e-9


def test_idle_share_and_gaps_add_up(small):
    r = T.reduce(small)
    assert r["window_s"] > 3 * 0.020
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    gaps = dict(r["idle_gaps"])
    assert r["busy_s"] + sum(gaps.values()) == pytest.approx(r["window_s"])
    # the sleeps are the longest gaps, and they are 3 x 20 ms
    assert r["idle_gaps"][0][0] == "idle-for-arrival"
    assert 3 * 0.019 < gaps["idle-for-arrival"] < 3 * 0.030


def test_top_op_is_the_matmul_chain(small):
    r = T.reduce(small)
    label, seconds = r["top_ops"][0]
    assert "bf16[2048,2048]" in label and "fusion" in label
    # 24 matmuls of 2 x 2048^3 flops at most at the chip's bf16 peak
    assert seconds >= 24 * 2 * 2048 ** 3 / 197e12
    assert seconds <= r["busy_s"]
