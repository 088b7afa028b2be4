"""trace_reduce on hand-made events and on two recorded traces kept in
benchmarks/testdata: ``tiny_tpu.xplane.pb`` (one v5e, four calls of a
jitted chain of three 2048^3 bf16 matmuls under a ``bench.step``
annotation; my chip run, PR 27) and ``host_only.xplane.pb`` (the same
kind of capture on the CPU: no device plane)."""
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(5, 7), (0, 3), (2, 4), (9, 9), (7, 8)]) == \
        [[0, 4], [5, 8]]


def test_busy_and_idle_share_from_events():
    ev = [("%a = x", 0, 10), ("%b = x", 5, 10), ("%c = x", 30, 10)]
    assert tr.busy_ns(ev, (0, 40)) == 25
    assert tr.busy_ns(ev, (10, 35)) == 10      # clipped to the window
    trace = {"devices": {0: ev, 1: [("%a = x", 0, 40)]}, "host": []}
    summ = tr.summary(trace)
    assert summ["window"] == (0, 40)
    assert summ["busy_s"] == pytest.approx((25 + 40) / 2 / 1e9)
    assert 1 - summ["busy_s"] / summ["window_s"] == pytest.approx(0.1875)


def test_exposed_collective_is_the_part_no_compute_hides():
    ev = [("%all-reduce.1 = f32[8] all-reduce(...)", 0, 100),
          ("%fusion.3 = f32[8] fusion(...)", 20, 30),
          ("%convolution.2 = ...", 40, 20),         # 40..60, overlaps fusion
          ("%all-gather-start.4 = ...", 200, 50),   # nothing under it
          ("%fusion.9 = ...", 300, 10)]
    # all-reduce 0..100 with compute union 20..60 under it: 60 exposed
    assert tr.exposed_collective_ns(ev, (0, 400)) == 60 + 50
    assert tr.exposed_collective_ns(ev[1:3], (0, 400)) == 0


def test_idle_gaps_go_to_the_host_span_that_overlaps_most():
    ev = [("%a = x", 0, 10), ("%b = x", 50, 10), ("%c = x", 90, 10)]
    host = [("bench.feed_next", 8, 30), ("bench.loss_readback", 38, 15),
            ("bench.step_dispatch", 62, 5)]
    gaps = dict(tr.idle_gaps(ev, (0, 100), host))
    assert gaps == {"bench.feed_next": pytest.approx(40e-9),
                    "bench.step_dispatch": pytest.approx(30e-9)}


def test_op_family_and_time_of():
    assert tr.op_family("%fusion.123 = bf16[2] fusion(...)") == "fusion"
    assert tr.op_family("%jvp__.8 = bf16[64,2048,64]{2,1,0} custom-call(") \
        == "jvp__"
    ev = [("%jvp__.8 = bf16[64,2048,64]{2,1,0:T(8,128)} custom-call(x)", 0, 7),
          ("%jvp__.9 = bf16[64,2048,64]{2,1,0:T(8,128)} custom-call(x)", 9, 7),
          ("%x.1 = bf16[64,2048,128]{2,1,0} custom-call(x)", 20, 7)]
    s, n = tr.time_of(ev, (0, 100),
                      r"^%\S+ = \w+\[64,2048,64\]\S* custom-call\(")
    assert (s, n) == (pytest.approx(14e-9), 2)


def test_recorded_tpu_trace():
    trace = tr.load(os.path.join(DATA, "tiny_tpu.xplane.pb"))
    assert list(trace["devices"]) == [0]
    assert len(trace["devices"][0]) == 20
    assert [n for n, _, _ in trace["host"]] == ["bench.step"] * 4
    summ = tr.summary(trace)
    assert summ["window"] == (46338400, 56086721)
    assert summ["busy_s"] == pytest.approx(0.001084854)
    idle = 1 - summ["busy_s"] / summ["window_s"]
    assert idle == pytest.approx(0.88871, abs=1e-5)
    ops = dict(summ["device_ops"])
    assert ops["convolution_tanh_fusion"] == pytest.approx(0.000723246)
    assert tr.exposed_collective_ns(trace["devices"][0], summ["window"]) == 0
    assert summ["idle_gaps"][0][0] == "bench.step"


def test_no_device_lane_is_an_error_not_a_fallback():
    with pytest.raises(tr.NoDeviceLane):
        tr.load(os.path.join(DATA, "host_only.xplane.pb"))
    with pytest.raises(tr.NoDeviceLane):
        tr.summary({"devices": {0: []}, "host": []})
