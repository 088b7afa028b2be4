"""The readers of the program's own spans and of device ops found by
name: ``program_spans`` on hand-made events, ``op_time`` on hand-made
instructions and on ``testdata/tiny_spmd.xplane.pb`` (one v5e: two steps
of a 2-layer TransformerLM, hidden 128, 2 heads of 64, 2 sequences of
2048 tokens so that the backward's scan has four chunks, bf16, through
``SPMDTrainer`` with the named Pallas kernel, the profiler's host tracer
off; my chip run, PR 28), and a rehearsal of the cell through the
unedited harness with both readers in the line."""
import argparse
import json
import os
import types

import pytest

import run
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")
spans = run.load_module("metrics", "readers", "program_spans.py")
op_time = run.load_module("metrics", "readers", "op_time.py")

MS = 1_000_000
T0 = 1_790_000_000 * 1_000_000_000      # a unix time in ns


def _ev(name, start_ms, dur_ms, tid=1, **args):
    return (name, T0 + int(start_ms * MS), int(dur_ms * MS), tid, args)


# set-up, then three steps of which the traced slice holds the last two
EVENTS = [
    _ev("mxnet_tpu.import", -900, 800),
    _ev("gluon.param_init", 10, 40), _ev("gluon.param_init", 60, 20),
    _ev("compile.trace", 100, 5), _ev("compile.trace", 108, 2),
    _ev("compile.trace", 98, 30),            # holds the two before it
    _ev("compile.lower", 130, 10),
    _ev("compile.backend", 140, 50), _ev("compile.backend", 200, 25),
    _ev("spmd.step", 300, 3, span_id=1),
    _ev("spmd.step.place", 300, 1, span_id=2, parent=1),
    _ev("spmd.step.launch", 301, 2, span_id=3, parent=1),
    _ev("spmd.step", 1000, 4, span_id=4),
    _ev("spmd.step.place", 1000, 1, span_id=5, parent=4),
    _ev("spmd.step.launch", 1001, 3, span_id=6, parent=4),
    _ev("pipeline.prefetch_stage", 1002, 0.5, tid=2),
    _ev("spmd.step", 1300, 6, span_id=7),
    _ev("spmd.step.place", 1300, 1, span_id=8, parent=7),
    _ev("spmd.step.launch", 1301, 5, span_id=9, parent=7),
    _ev("pipeline.prefetch_stage", 1302, 1.5, tid=2),
    _ev("compile.backend", 2000, 999),       # the reference, after the slice
]
BENCH = [("bench.feed_next", T0 + 999 * MS, MS // 2),
         ("bench.step_dispatch", T0 + 1000 * MS, 5 * MS),
         ("bench.loss_readback", T0 + 1005 * MS, 290 * MS),
         ("bench.step_dispatch", T0 + 1300 * MS, 7 * MS),
         ("bench.loss_readback", T0 + 1307 * MS, 290 * MS)]


def _ctx(events=EVENTS, bench=BENCH, dropped=0, monkeypatch=None):
    monkeypatch.setattr(spans, "program_events",
                        lambda: None if dropped else list(events))
    return types.SimpleNamespace(
        tracer=types.SimpleNamespace(spans=list(bench)), trace=None,
        measured={})


def test_window_phase_keeps_the_slice_and_divides_by_its_steps(monkeypatch):
    ctx = _ctx(monkeypatch=monkeypatch)
    assert spans.slice_of(BENCH) == (T0 + 999 * MS, T0 + 1597 * MS)
    read = spans.read
    assert read(ctx, ["spmd.step.place"], "window") == pytest.approx(1.0)
    assert read(ctx, ["spmd.step.launch"], "window") == pytest.approx(4.0)
    assert read(ctx, ["pipeline.prefetch_stage"], "window") == \
        pytest.approx(1.0)
    assert read(ctx, ["no.such.span"], "window") is None


def test_setup_phase_keeps_what_ended_before_the_slice(monkeypatch):
    ctx = _ctx(monkeypatch=monkeypatch)
    read = spans.read
    assert read(ctx, ["mxnet_tpu.import"], "setup") == pytest.approx(0.8)
    assert read(ctx, ["gluon.param_init"], "setup") == pytest.approx(0.06)
    # the nested traces count once: 30 ms of tracing, 10 of lowering
    assert read(ctx, ["compile.trace", "compile.lower"], "setup") == \
        pytest.approx(0.040)
    # the reference's compile after the slice is no set-up
    assert read(ctx, ["compile.backend"], "setup") == pytest.approx(0.075)
    assert read(ctx, ["compile.backend"], "setup", count=True) == 2
    with pytest.raises(ValueError):
        read(ctx, ["compile.backend"], "teardown")


def test_a_dropped_ring_an_empty_ring_and_an_untraced_run_read_nothing(
        monkeypatch):
    args = (["spmd.step.launch"], "window")
    assert spans.read(_ctx(dropped=3, monkeypatch=monkeypatch), *args) is None
    assert spans.read(_ctx(events=[], monkeypatch=monkeypatch), *args) is None
    assert spans.read(_ctx(bench=[], monkeypatch=monkeypatch), *args) is None


def test_the_programs_ring_is_read_on_unix_ns():
    """The real ring: default-on, anchored, read back on the wall clock."""
    import time

    from mxnet_tpu.telemetry import tracer

    tracer.reset()
    with tracer.span("spmd.step.launch", cat="test"):
        wall = time.time_ns()
    (ev,) = [e for e in spans.program_events()
             if e[0] == "spmd.step.launch"]
    assert abs(ev[1] - wall) < 5 * MS and ev[2] >= 0
    tracer.reset(capacity=16)
    for _ in range(20):
        tracer.instant("x")
    assert spans.program_events() is None       # the ring dropped events
    tracer.reset()


def test_clock_offset_comes_from_a_span_both_lists_hold():
    started = T0 + 123_456_789             # the profile's start, unix ns
    host = [(n, s - started, d) for n, s, d in BENCH]
    assert spans.clock_offset(BENCH, host) == started
    assert spans.clock_offset(BENCH, []) is None
    assert spans.clock_offset(BENCH, [("bench.other", 5, 7)]) is None


def test_idle_gaps_are_named_by_the_programs_innermost_span(monkeypatch):
    started = T0 + 900 * MS
    ctx = _ctx(monkeypatch=monkeypatch)
    # the device: busy but for 1000..1004 ms and 1300..1306 ms (unix)
    dev = [("%fusion.1 = f32[8] fusion(x)", 99 * MS, 1 * MS),
           ("%fusion.2 = f32[8] fusion(x)", 104 * MS, 296 * MS),
           ("%fusion.3 = f32[8] fusion(x)", 406 * MS, 291 * MS)]
    ctx.trace = {"devices": {0: dev},
                 "host": [(n, s - started, d) for n, s, d in BENCH]}
    ctx.measured["trace_summary"] = tr.summary(ctx.trace)
    assert spans.read(ctx, ["spmd.step.place"], "window") == \
        pytest.approx(1.0)
    gaps = dict(ctx.measured["program_idle_gaps"])
    # launch overlaps each gap most; spmd.step, its parent, is left out
    assert gaps == {"spmd.step.launch": pytest.approx(0.010)}


# ---------------------------------------------------------------------------
# device ops by name

FLASH = (64, 2048, 64)
BWD = ("%while.7 = (s32[]{:T(128)}, f32[2,32,2048,64]{3,2,1,0:T(8,128)}, "
       "f32[2,32,2048,64]{3,2,1,0:T(8,128)}, f32[4,2,32,512,64]{4,3,2,1,0}) "
       "while((s32[], f32[2,32,2048,64]) %tuple.3), condition=%c, body=%b")
LAYERS = ("%while.9 = (s32[]{:T(128)}, bf16[2,2048,2048]{2,1,0:T(8,128)(2,1)}) "
          "while((s32[], bf16[2,2048,2048]) %tuple.4), condition=%c, body=%b")


def test_the_while_rule_wants_the_kernels_shape():
    assert op_time.carries(BWD, FLASH)
    assert op_time.carries(BWD.replace("2,32,2048,64", "64,2048,64"), FLASH)
    # a scan over layers carries (B, S, E): not attention
    assert not op_time.carries(LAYERS, FLASH)
    assert not op_time.carries(BWD.replace("2,32,2048,64", "2,16,2048,64"),
                               FLASH)
    # only a while's own result counts, not an op that reads such arrays
    assert not op_time.carries(
        "%fusion.3 = f32[8] fusion(f32[2,32,2048,64] %x)", FLASH)


def test_ops_are_found_by_instruction_name_inside_the_window():
    ev = [("%flash_fwd.1 = bf16[64,2048,64]{2,1,0} custom-call(x)", 0, 7),
          ("%flash_fwd.2 = bf16[64,2048,64]{2,1,0} custom-call(x)", 10, 7),
          ("%flash_fwd_helper = bf16[8] fusion(x)", 20, 5),
          (BWD, 30, 40), (LAYERS, 80, 15),
          ("%flash_fwd.3 = bf16[64,2048,64]{2,1,0} custom-call(x)", 200, 7)]
    fwd = r"^%flash_fwd[.\d]* = "
    assert op_time.device_seconds(ev, (0, 100), fwd) == \
        (pytest.approx(14e-9), 2)
    assert op_time.device_seconds(ev, (0, 100), r"^%flash_bwd[.\d]* = ",
                                  FLASH) == (pytest.approx(40e-9), 1)
    assert op_time.device_seconds(ev, (0, 100), r"^%flash_bwd[.\d]* = ") == \
        (0.0, 0)


def _metric(name):
    spec = run.load_json("metrics", name + ".json")
    return spec["reader"], spec["args"]


def test_recorded_spmd_trace_names_the_kernel_and_the_backward_loop():
    trace = tr.load(os.path.join(DATA, "tiny_spmd.xplane.pb"))
    ev = trace["devices"][0]
    window = tr.window_of(ev)
    shape = (2 * 2, 2048, 64)               # batch*heads, seq, head_dim
    reader, args = _metric("attn_fwd_ms.tokens")
    assert reader == "op_time"
    fwd_s, fwd_n = op_time.device_seconds(ev, window, args["match"])
    # two steps of two layers; found by name exactly where the accepted
    # roofline reader finds the kernel by its result's shape
    by_shape = tr.time_of(
        ev, window, r"^%\S+ = \w+\[4,2048,64\]\S* custom-call\(")
    assert fwd_n == 4 and (fwd_s, fwd_n) == by_shape
    assert fwd_s == pytest.approx(0.001128768)
    reader, args = _metric("attn_bwd_ms.tokens")
    assert reader == "op_time" and args["while_carrying"] == "flash_fwd_shape"
    bwd_s, bwd_n = op_time.device_seconds(ev, window, args["match"], shape)
    assert (bwd_s, bwd_n) == (pytest.approx(0.000633182), 4)
    # the loops found are the trace's only whiles: none is left over,
    # and without the shape none is found
    whiles = [n for n, _, _ in ev if " while(" in n.split(", condition=")[0]]
    assert len(whiles) == bwd_n
    assert op_time.device_seconds(ev, window, args["match"],
                                  (8, 2048, 64))[1] == 0
    assert dict(tr.summary(trace)["device_ops"]).keys() >= \
        {"flash_fwd", "while"}


def test_rehearsal_walks_both_readers_through_the_unedited_harness():
    """``run.py --rehearse --trace 1`` on the CPU: the program's spans are
    in the line; the device readers find no device lane and leave their
    metrics out without raising."""
    from mxnet_tpu.telemetry import tracer

    tracer.reset()      # earlier tests' events must not wrap the ring;
    # the import's span goes with them (a run's own process has it)
    cell = "opt1.3b-train-s2048"
    args = argparse.Namespace(workload=cell, seed=4_100_000_007,
                              seconds=1.5, trace=1, rehearse=True)
    got = run.execute(args)["metrics"]
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in run.metrics_of(
            json.load(f), "per_layer", cell, set())}
    from_spans = {"step_place_ms.tokens", "step_launch_ms.tokens",
                  "feed_stage_ms.tokens", "setup_param_init_s", "setup_init_forward_s",
                  "setup_trace_lower_s", "setup_compile_s",
                  "setup_programs"}
    assert from_spans | {"setup_import_s", "attn_fwd_ms.tokens",
                         "attn_bwd_ms.tokens"} <= listed
    assert from_spans <= set(got), sorted(from_spans - set(got))
    assert "attn_fwd_ms.tokens" not in got
    assert got["step_place_ms.tokens"]["value"] + \
        got["step_launch_ms.tokens"]["value"] <= \
        got["dispatch_ms.tokens"]["value"] * 1.05
    assert got["setup_programs"]["value"] == int(
        got["setup_programs"]["value"]) > 0
