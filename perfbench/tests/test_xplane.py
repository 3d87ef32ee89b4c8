"""The reduction, on a hand-made two-device trace and on a recorded one."""

import os

import pytest

from perfbench import xplane
from perfbench.xplane import Op, Trace

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "fixtures")


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert xplane.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert xplane.total([(0, 3), (5, 8)]) == 6
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 8)]) == [(0, 2), (3, 5), (8, 10)]
    assert xplane.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def hand_made() -> Trace:
    """Two fits of 100 ns each on two devices (times in ns).

    device 0, fit 1: a 40 ns convolution fusion at 10, a 10 ns all-reduce at
    50 of which the last 4 ns overlap a 20 ns loop fusion at 56; a ``while``
    from 10 to 76 holds them all. fit 2 (starts at 100): a 30 ns convolution
    at 110 and an all-reduce 140-150, bare.
    device 1: a convolution 10-60, an all-reduce 60-62; then 110-150 and an
    all-reduce 150-170.
    """
    d0 = [
        Op("%while.1 = while(...)", 10, 66),
        Op("%fusion.1 = f32[8,8] fusion(f32[64,8] %x), kind=kOutput", 10, 40),
        Op("%all-reduce.1 = all-reduce(...)", 50, 10),
        Op("%fusion.2 = f32[8] fusion(f32[8] %y), kind=kLoop", 56, 20),
        Op("%fusion.1 = f32[8,8] fusion(f32[64,8] %x), kind=kOutput", 110, 30),
        Op("%all-reduce.1 = all-reduce(...)", 140, 10),
    ]
    d1 = [
        Op("%fusion.1 = f32[8,8] fusion(f32[64,8] %x), kind=kOutput", 10, 50),
        Op("%all-reduce.1 = all-reduce(...)", 60, 2),
        Op("%fusion.1 = f32[8,8] fusion(f32[64,8] %x), kind=kOutput", 110, 40),
        Op("%all-reduce.1 = all-reduce(...)", 150, 20),
    ]
    spans = [("fit", 0, 90), ("model_read", 90, 100), ("fit", 100, 180), ("model_read", 180, 200)]
    return Trace({0: d0, 1: d1}, spans)


def test_hand_made_two_device_trace():
    r = xplane.reduce(hand_made(), chips=2)
    assert r.window == (0, 200)
    # busy union: device 0 = [10,76] + [110,150] = 106; device 1 = [10,62] + [110,170] = 112
    assert xplane.total(r.busy[0]) == 106 and xplane.total(r.busy[1]) == 112
    assert r.busy_s_mean == pytest.approx(109e-9)
    assert r.window_s == pytest.approx(200e-9)
    # the idle share is the idlest device's
    assert r.idle_share_worst() == pytest.approx(1 - 106 / 200)
    # all leaf operations, worst device (the while is no leaf): 106 and 112
    assert r.device_time() == pytest.approx(112e-9)
    # matrix-multiply time, worst device: 40 + 30 and 50 + 40
    assert r.device_time(xplane.is_matmul) == pytest.approx(90e-9)
    assert len(r.fit_spans()) == 2
    # part of each fit span in which NO device ran anything
    # fit 1 [0,90]: busy union over devices [10,76] -> 24; fit 2 [100,180]: [110,170] -> 20
    assert r.host_wait_per_fit() == [24, 20]
    b = r.breakdown()
    assert b["device_ops"][0][0].startswith("%fusion.1 = f32[8,8] fusion")
    assert b["device_ops"][0][1] == pytest.approx((40 + 30 + 50 + 40) / 2 / 1e9)
    assert all("while" not in name for name, _ in b["device_ops"])
    # idle gaps of the idlest device (0): [0,10]+[76,90] in fit, [90,100] in
    # model_read, [100,110]+[150,180] in fit, [180,200] in model_read
    assert dict(b["idle_gaps"]) == {"fit": pytest.approx(64e-9), "model_read": pytest.approx(30e-9)}


def test_empty_traces_are_errors():
    with pytest.raises(RuntimeError, match="device planes"):
        xplane.reduce(Trace({}, [("fit", 0, 1)]), chips=1)
    with pytest.raises(RuntimeError, match="host spans"):
        xplane.reduce(Trace({0: [Op("x", 0, 1)]}, []), chips=1)
    with pytest.raises(RuntimeError, match="no operation ran"):
        xplane.reduce(Trace({0: [Op("x", 50, 1)]}, [("fit", 0, 10)]), chips=1)


def test_recorded_trace():
    """Three tiny host-partition fits (512-row partitions of 96 columns),
    traced on a TPU v5e in this PR: what ``load`` finds in a real file."""
    trace = xplane.load(os.path.join(FIXTURES, "pca_tiny_tpu_v5e.xplane.pb"))
    assert sorted(trace.devices) == [0] and len(trace.devices[0]) == 906
    assert [name for name, _, _ in trace.spans] == ["fit", "model_read"] * 3
    r = xplane.reduce(trace, chips=1)
    assert len(r.fit_spans()) == 3
    assert r.window_s == pytest.approx(0.063989872, rel=1e-6)
    assert r.busy_s_mean == pytest.approx(0.000412528, rel=1e-6)
    assert r.idle_share_worst() == pytest.approx(0.99355, abs=1e-5)
    # leaf operations only (the eigensolver's while and conditional are
    # containers), and the matrix multiplications among them
    assert sum(xplane.is_container(op) for op in trace.devices[0]) == 6
    assert r.device_time() == pytest.approx(0.000360633, rel=1e-6)
    assert r.device_time(xplane.is_matmul) == pytest.approx(9.025e-05, rel=1e-4)
    assert r.device_time(xplane.is_matmul) < r.device_time() <= r.busy_s_mean
    # a host fit is all host: 21 ms of each 21 ms span with the device idle
    assert all(20e6 < t < 22e6 for t in r.host_wait_per_fit())
    b = r.breakdown()
    assert len(b["device_ops"]) == 10 and b["device_ops"][0][0].startswith("%fusion = f32[96,96]")
    assert "kind=kOutput" in b["device_ops"][0][0]
    assert b["idle_gaps"][0][0] == "fit" and b["idle_gaps"][0][1] == pytest.approx(0.06348, abs=1e-5)


def nested() -> Trace:
    """One fit of 100 ns whose stages lie inside it, then a model read.

    The device is busy 25-50 and 60-70, so it idles 0-25, 50-60 and 70-110.
    ``place`` holds a span of its own (``inner``, 22-24: two deep), and
    ``solve`` (40-95) is cut short by nothing while ``late`` starts inside
    ``model_read`` and outlasts it: it is cut at ``model_read``'s end.
    """
    ops = [Op("%fusion.1 = fusion(), kind=kOutput", 25, 25), Op("%fusion.2 = fusion(), kind=kLoop", 60, 10)]
    spans = [
        ("fit", 0, 100), ("admit", 2, 5), ("densify", 5, 10), ("place", 20, 30), ("inner", 22, 24),
        ("solve", 40, 90), ("model_read", 100, 110), ("late", 105, 130),
    ]
    return Trace({0: ops}, spans)


def test_a_nanosecond_belongs_to_the_innermost_span_that_holds_it():
    own = dict(xplane.own_intervals(nested().spans))
    assert own["fit"] == [(0, 2), (10, 20), (30, 40), (90, 100)]
    assert own["place"] == [(20, 22), (24, 30)] and own["inner"] == [(22, 24)]
    assert own["solve"] == [(40, 90)] and own["admit"] == [(2, 5)]
    assert own["model_read"] == [(100, 105)] and own["late"] == [(105, 110)]
    # every nanosecond of the outermost spans once and only once
    assert sum(xplane.total(mine) for mine in own.values()) == 110
    # the order of the list does not matter, and a span alone is its own
    assert dict(xplane.own_intervals(reversed(nested().spans))) == own
    assert xplane.own_intervals([("fit", 3, 9)]) == [("fit", [(3, 9)])]


def test_idle_gaps_split_a_fit_by_stage_and_ingest_reads_as_before():
    trace = nested()
    trace.spans = [s for s in trace.spans if s[0] != "late"]
    r = xplane.reduce(trace, chips=1)
    assert r.window == (0, 110)
    gaps = dict(r.breakdown()["idle_gaps"])
    want = {"admit": 3, "densify": 5, "place": 3, "inner": 2, "solve": 30, "fit": 22, "model_read": 10}
    assert gaps == {name: pytest.approx(ns / 1e9) for name, ns in want.items()}
    # the stages and what is left of ``fit`` add up to what ``fit`` alone read
    # before the stages were kept, which is what ingest_ms reads
    plain = xplane.reduce(Trace(trace.devices, [("fit", 0, 100), ("model_read", 100, 110)]), chips=1)
    assert dict(plain.breakdown()["idle_gaps"]) == {"fit": pytest.approx(65e-9),
                                                    "model_read": pytest.approx(10e-9)}
    assert sum(want.values()) - want["model_read"] == 65
    assert r.host_wait_per_fit() == plain.host_wait_per_fit() == [65]


def test_measure_agrees_with_clip():
    import random

    rng = random.Random(28)
    cuts = sorted(rng.sample(range(1000), 60))
    intervals = list(zip(cuts[0::2], cuts[1::2]))
    inside = xplane.measure(intervals)
    for _ in range(300):
        lo, hi = sorted(rng.sample(range(-5, 1005), 2))
        assert inside(lo, hi) == xplane.total(xplane.clip(intervals, lo, hi))
    assert inside(cuts[1], cuts[2]) == 0 and xplane.measure([])(0, 10) == 0
