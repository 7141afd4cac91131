"""The trace reduction on a small synthetic event list."""
import pytest

from chipbench import trace


def test_union_merges_overlaps_and_clips():
    ev = [(0, 10, "a"), (5, 15, "b"), (20, 30, "c"), (28, 40, "d")]
    assert trace.union_ns(ev, 0, 100) == 15 + 20
    assert trace.union_ns(ev, 8, 25) == (15 - 8) + (25 - 20)
    assert trace.union_ns([], 0, 10) == 0


def test_gaps_between_busy_intervals():
    ev = [(10, 20, "a"), (15, 30, "b"), (50, 60, "c")]
    assert trace.gaps_ns(ev, 0, 100) == [(0, 10), (30, 50), (60, 100)]
    assert trace.gaps_ns(ev, 12, 55) == [(30, 50)]


def test_reduce_busy_idle_ops_and_breakdown():
    dev = {"/device:TPU:0": [(0, 400, "k1"), (500, 900, "k2"),
                             (950, 1000, "k1")],
           "/device:TPU:1": [(0, 1000, "k1")]}
    host = [(400, 500, "np.stack"), (380, 520, "outer"),
            (900, 950, "dispatch")]
    r = trace.reduce(dev, {"/device:TPU:0": 2}, host, 0, 1000)
    assert r["window_s"] == pytest.approx(1e-6)
    # device 0 busy 850 ns, device 1 busy 1000 ns
    assert r["busy_s"] == pytest.approx((850 + 1000) / 2 / 1e9)
    assert r["idle_share"] == pytest.approx(1 - 925 / 1000)
    assert r["op_count"] == {"k1": 3, "k2": 1}
    assert r["op_s"]["k1"] == pytest.approx((400 + 50 + 1000) / 1e9)
    ops = r["breakdown"]["device_ops"]
    assert [o[0] for o in ops] == ["k1", "k2"]
    gaps = r["breakdown"]["idle_gaps"]
    # longest gap first, named by the innermost host event covering it
    assert gaps[0] == ["np.stack", pytest.approx(100 / 1e9)]
    assert gaps[1] == ["dispatch", pytest.approx(50 / 1e9)]


def test_reduce_refuses_empty_window():
    with pytest.raises(ValueError):
        trace.reduce({"/device:TPU:0": []}, {}, [], 10, 10)
