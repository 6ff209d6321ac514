"""The trace reduction's interval arithmetic on hand-made traces."""
import pytest

import trace as T


def test_merge_clip_complement():
    busy = T.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert T.clip(busy, 1, 6) == [(1, 3), (5, 6)]
    assert T.complement(busy, -1, 10) == [(-1, 0), (3, 5), (8, 10)]


def test_attribute_splits_gaps_by_phase():
    phases = [("wait", 0, 4), ("d2h", 4, 6), ("lookup", 9, 10)]
    gaps = [(2, 5), (5, 8), (8, 12)]
    got = T.attribute(gaps, phases)
    assert got == {"wait": 2, "d2h": 2, T.OTHER: 5, "lookup": 1}
    assert sum(got.values()) == sum(e - s for s, e in gaps)


def test_op_labels_drop_numbers_and_layouts():
    assert T.op_label("%fusion.7 = f32[64,48]{1,0:T(8,128)} fusion(f32[64,"
                      "192]{1,0} %x.2), kind=kOutput") == "fusion f32[64,48]"
    assert T.op_label("%while.3 = (s32[]{:T(128)}, /*index=1*/bf16[2,4]"
                      "{1,0}) while(%t)") == "while (s32[], bf16[2,4])"


def test_self_time_leaves_out_nested_ops():
    got = T.self_times([("loop", 0, 10), ("a", 1, 3), ("b", 4, 9),
                        ("c", 5, 6), ("d", 11, 12)])
    assert [(n, own) for n, _, _, own in got] == [
        ("loop", 3), ("a", 2), ("b", 4), ("c", 1), ("d", 1)]


def test_reduce_averages_over_devices_and_names_ops():
    ms = 1_000_000
    trace = T.Trace(
        ops={"/device:TPU:0": [("%while.1 = s32[] while()", 0, 6 * ms),
                               ("%fusion.2 = f32[8] fusion()", ms, 3 * ms),
                               ("%fusion.3 = f32[8] fusion()", 3 * ms,
                                5 * ms)],
             "/device:TPU:1": [("%fusion.2 = f32[8] fusion()", 0, 2 * ms)]},
        host=[("window", 0, 10 * ms), ("wait", 0, 6 * ms),
              ("idle-for-arrival", 6 * ms, 10 * ms)])
    r = T.reduce(trace)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx((0.006 + 0.002) / 2)
    assert r["idle_share"] == pytest.approx(1 - 0.004 / 0.010)
    assert r["top_ops"] == [["fusion f32[8]", pytest.approx(0.003)],
                            ["while s32[]", pytest.approx(0.001)]]
    assert dict(r["idle_gaps"]) == {"idle-for-arrival": pytest.approx(0.004),
                                    "wait": pytest.approx(0.002)}


def test_a_trace_cut_short_ends_the_window_with_its_ops():
    s = 1_000_000_000
    trace = T.Trace(ops={"/device:TPU:0": [("a", 0, 3 * s)]},
                    host=[("window", 0, 10 * s)])
    r = T.reduce(trace)
    assert r["truncated"] and r["window_s"] == pytest.approx(3.0)
    assert r["idle_share"] == pytest.approx(0.0)
    trace.ops["/device:TPU:0"].append(("b", 9.5 * s, 9.6 * s))
    r = T.reduce(trace)
    assert not r["truncated"] and r["window_s"] == pytest.approx(10.0)


def test_reduce_needs_one_window_and_some_ops():
    with pytest.raises(ValueError):
        T.reduce(T.Trace({"/device:TPU:0": [("a", 0, 1)]}, []))
    with pytest.raises(ValueError):
        T.reduce(T.Trace({}, [("window", 0, 1)]))
