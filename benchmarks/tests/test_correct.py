"""`correct` has to be able to fail.

Each test skips the harness's look for a chip (a rehearsal: tiny sizes on
the CPU, kernels not compiled) and drives the rest of a run. The program
computes in float32 here, so a sound run agrees with the float32
reference to rounding and passes the cell's own limits (set on the chip
for bf16, so far wider than float32 needs); then

* the control, the reference one precision below the configuration's put
  in the program's place, has to come out as not correct, and
* the timed path broken underneath (a step that leaves its state
  unchanged; half of the batch left out; a served answer altered) has to
  make the run's ``correct`` false.

The cells of ``BENCHMARK.json`` are held to their own limits files. The
cells that PERF.md holds back (section 7) have no limits yet; their
files are driven here all the same, held to what float32 on the CPU
keeps, and one of them shows the program's fault that holds it back.
"""
import argparse
import json
import os

import pytest

import correct
import run

with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
LISTED = [w["name"] for w in BENCH["workloads"]]

FLOAT32_KEEPS = 1e-3
HELD_BACK = {
    "resnet50-train-b256": {
        "config": "resnet50_v1", "traffic": "train-b256", "chips": 1,
        "numbers": ["loss_gap", "grad_gap", "grad_diff", "delta_gap"]},
    "resnet50-score-b1024": {
        "config": "resnet50_v1", "traffic": "score-b1024", "chips": 1,
        "numbers": ["logit_gap"]},
}


def _bench():
    """BENCHMARK.json with the held-back cells beside its own."""
    held = [{"name": n, "config": c["config"], "traffic": c["traffic"],
             "chips": c["chips"], "why": "held back"}
            for n, c in HELD_BACK.items()]
    return dict(BENCH, workloads=BENCH["workloads"] + held)


def _float32(ctx):
    if "train" in ctx.cfg:
        ctx.cfg["train"]["compute_dtype"] = "float32"
    if "image_size" in ctx.cfg:
        ctx.cfg["image_size"] = 64      # BatchNorm over more than 8 values
    if "compare_calls" in ctx.traffic:  # every call of the short window,
        ctx.traffic["compare_calls"] = 1 << 20      # not a sample of four
    if ctx.name in HELD_BACK:
        ctx.limits = dict.fromkeys(HELD_BACK[ctx.name]["numbers"],
                                   FLOAT32_KEEPS)


def _run(cell, fault=None, tweak=_float32):
    # at the rehearsal's widths (4 channels in the first block) one seed
    # in a few is ill-conditioned for the ResNet: this one is not
    seed = 4_100_000_099 if cell == "resnet50-train-b256" else 4_100_000_007
    args = argparse.Namespace(workload=cell, seed=seed, seconds=0.5,
                              trace=0, rehearse=True)
    return run.execute(args, fault=fault, tweak=tweak, bench=_bench())


@pytest.mark.parametrize("cell", LISTED + list(HELD_BACK))
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("cell,fault", [
    ("opt1.3b-train-s2048", "state_unchanged"),
    ("opt1.3b-train-s2048", "half_batch"),
    ("resnet50-train-b256", "state_unchanged"),
    ("resnet50-train-b256", "half_batch"),
    ("resnet50-score-b1024", "answer_altered"),
])
def test_broken_timed_path_is_not_correct(cell, fault):
    res = _run(cell, fault)
    assert res["correct"] is False, res["compared"]
    assert any(v["value"] > v["limit"] for v in res["compared"].values())


@pytest.mark.parametrize("cell", LISTED)
def test_control_comes_out_not_correct(cell):
    """The reference in the control's precision (fp8 products for a bf16
    training cell) against the float32 reference, held to the cell's
    limits."""
    bench = _bench()
    args = argparse.Namespace(workload=cell, seed=4_100_000_011, seconds=0.5,
                              trace=0, rehearse=True)
    ctx = run.Ctx(bench, run.find_cell(bench, cell), args)
    _float32(ctx)
    run.look_for_chip(ctx)
    loop = run.load_module("loops", ctx.traffic["kind"] + ".py").Loop(ctx)
    loop.setup()
    if ctx.traffic["kind"] != "train":
        loop.window(0.5)
    loop.release()
    sound = loop.verify()
    assert all(v["value"] <= v["limit"] for v in sound.values()), sound
    control = correct.with_limits(loop.control(), ctx.limits)
    assert any(v["value"] > v["limit"] for v in control.values()), control


def test_running_statistics_show_the_programs_fault():
    """Why ``resnet50-train-b256`` is held back (PERF.md, section 7):
    ``SPMDTrainer`` updates BatchNorm's running statistics once on a
    zeros batch before its first step. In float32 every trained leaf
    agrees with the reference, and the statistics themselves do not."""
    def with_stats(ctx):
        _float32(ctx)
        ctx.limits["stat_gap"] = FLOAT32_KEEPS

    res = _run("resnet50-train-b256", tweak=with_stats)
    bad = {k for k, v in res["compared"].items() if v["value"] > v["limit"]}
    assert bad == {"stat_gap"}, res["compared"]
    assert res["compared"]["stat_gap"]["value"] > 0.1
