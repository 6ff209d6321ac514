import itertools

import numpy as np
import pytest

import traffic

BIG_SEED = 3_000_000_017          # more than 32 signed bits hold


def take(spec, seed, seconds=10.0, n=None):
    it = traffic.requests(spec, seed, seconds)
    return list(itertools.islice(it, n) if n else it)


def test_closed_loop_cycles_the_pool_in_a_seeded_order():
    spec = {"kind": "closed", "batch": 64, "pool": 8}
    reqs = take(spec, BIG_SEED, n=24)
    firsts = [r.input for r in reqs[:8]]
    assert sorted(firsts) == list(range(8))
    assert [r.input for r in reqs[8:16]] == firsts
    assert all(r.due is None and r.batch == 64 for r in reqs)
    assert [r.input for r in take(spec, BIG_SEED, n=8)] == firsts
    assert [r.input for r in take(spec, BIG_SEED + 1, n=8)] != firsts


def test_poisson_gaps_are_one_set_of_exponential_quantiles():
    rate, seconds = 400.0, 10.0
    spec = {"kind": "poisson", "batch": 1, "pool": 256, "rate_per_s": rate}
    a = take(spec, BIG_SEED, seconds)
    b = take(spec, 7, seconds)
    n = round(rate * seconds)
    assert len(a) == len(b) == n
    due_a = np.array([r.due for r in a])
    gaps_a = np.diff(due_a, prepend=0.0)
    gaps_b = np.diff([r.due for r in b], prepend=0.0)
    # the same gaps in another order: every request is due in the window
    np.testing.assert_allclose(np.sort(gaps_a), np.sort(gaps_b))
    assert not np.allclose(gaps_a, gaps_b)
    assert 0 < due_a[0] and np.all(np.diff(due_a) > 0)
    assert due_a[-1] < seconds
    assert abs(gaps_a.mean() - 1 / rate) < 0.01 / rate
    # exponential: the median gap is ln 2 / rate, the 90th 2.30 / rate
    assert np.median(gaps_a) * rate == pytest.approx(np.log(2), rel=1e-3)
    assert np.quantile(gaps_a, 0.9) * rate == pytest.approx(
        -np.log(0.1), rel=1e-2)
    assert [r.due for r in take(spec, BIG_SEED, seconds)] == list(due_a)


def test_length_and_batch_are_carried():
    spec = {"kind": "closed", "batch": 1, "length": 512, "pool": 32}
    r = take(spec, 5, n=1)[0]
    assert (r.batch, r.length) == (1, 512)


def test_a_profile_bends_the_arrivals_and_keeps_the_work():
    """On/off bursts at 3x the mean rate: every arrival falls in an on
    period, as many as at a constant rate, the same for every seed."""
    rate, seconds = 200.0, 9.0
    flat = {"kind": "poisson", "batch": 1, "pool": 16, "rate_per_s": rate}
    bursty = dict(flat, profile=[[0.5, 1.0], [1.0, 0.0]])
    a, b = take(bursty, BIG_SEED, seconds), take(bursty, 7, seconds)
    assert len(a) == len(b) == len(take(flat, 7, seconds)) == rate * seconds
    due = np.array([r.due for r in a])
    assert np.all(due % 1.5 <= 0.5 + 1e-9) and due[-1] < seconds
    per_burst = np.bincount((due // 1.5).astype(int))
    assert len(per_burst) == 6            # 3x the rate for 0.5 s in 1.5 s
    assert np.all(np.abs(per_burst - 3 * rate * 0.5) < 0.2 * 3 * rate * 0.5)
    assert not np.allclose(due, [r.due for r in b])


def test_a_size_distribution_is_sent_in_exact_proportion():
    spec = {"kind": "poisson", "pool": 8, "rate_per_s": 100.0,
            "batch": {"values": [1, 8, 64], "weights": [0.6, 0.3, 0.1]}}
    reqs = take(spec, BIG_SEED, 10.0)
    sizes = [r.batch for r in reqs]
    assert [sizes.count(b) for b in (1, 8, 64)] == [600, 300, 100]
    assert sizes != [r.batch for r in take(spec, 7, 10.0)]
    assert traffic.shapes(spec) == [(1, 0), (8, 0), (64, 0)]
    assert traffic.largest(spec) == (64, 0)
    closed = dict(spec, kind="closed")
    cyc = [r.batch for r in take(closed, 7, n=2 * traffic.CYCLE)]
    assert cyc[:traffic.CYCLE] == cyc[traffic.CYCLE:]
    assert sorted(set(cyc)) == [1, 8, 64]


def test_prompt_lengths_vary_by_weight():
    spec = {"kind": "closed", "batch": 1, "pool": 4,
            "length": {"values": [512, 4096], "weights": [3, 1]}}
    assert traffic.shapes(spec) == [(1, 512), (1, 4096)]
    assert traffic.largest(spec) == (1, 4096)
    lengths = [r.length for r in take(spec, 3, n=traffic.CYCLE)]
    assert lengths.count(512) == 3 * lengths.count(4096)


@pytest.mark.parametrize("bad", [
    {"kind": "bursty", "batch": 1, "pool": 1},
    {"kind": "closed", "batch": 0, "pool": 1},
    {"kind": "closed", "batch": 1},
    {"kind": "poisson", "batch": 1, "pool": 1},
    {"kind": "closed", "batch": {"values": [1, 2], "weights": [1]},
     "pool": 1},
    {"kind": "poisson", "batch": 1, "pool": 1, "rate_per_s": 1.0,
     "profile": [[1.0, 0.0]]},
])
def test_bad_mixes_are_refused(bad):
    with pytest.raises(ValueError):
        traffic.validate(bad)


def test_every_mix_in_the_benchmark_is_valid():
    for path in (traffic.Path(__file__).resolve().parents[1]
                 / "traffic").glob("*.json"):
        traffic.load(path)
