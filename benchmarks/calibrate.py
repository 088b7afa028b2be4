"""Readings from which the limits of ``correct`` are set, taken on the
chip at a cell's own size, in one process:

    python benchmarks/calibrate.py --workload <cell> --seeds 12 \
        --control-seeds 3 --faults half_batch --fault-seeds 3

For each seed the program is built and driven as a run drives it (the
checked steps of a training cell need no window; a scoring cell gets
``--seconds`` of its own load), then the plain reference follows, and the
numbers that a run compares are printed, with a training cell's first
gradient's difference leaf by leaf. The control (the reference one
precision below the configuration's, in the program's place; ``--also``
reads further precisions beside it) and each fault planted in the program
are read on the first seeds. Nothing here is
run by the benchmark's own runs. Results go to
``chiprun_out/calibrate/<cell>.json`` and to standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import correct
import run


def one(bench, cell, seed, seconds, rehearse, fault=None, control=False,
        also=()):
    args = argparse.Namespace(workload=cell["name"], seed=seed,
                              seconds=seconds, trace=0, rehearse=rehearse)
    ctx = run.Ctx(bench, cell, args, fault)
    run.look_for_chip(ctx)
    # every number is read, whatever limits the cell has by now
    ctx.limits = dict.fromkeys(
        ("loss1_gap", "loss_gap", "grad_gap", "grad_med_gap", "grad_diff",
         "grad_diff_med", "grad_diff_least", "delta_gap", "delta_med_gap",
         "stat_gap", "stat_med_gap", "logit_gap"), 1e30)
    loop = run.load_module("loops", ctx.traffic["kind"] + ".py").Loop(ctx)
    loop.setup()
    if ctx.traffic["kind"] != "train":
        loop.window(seconds)
    loop.release()
    gc.collect()
    loop.verify()
    got = dict(ctx.measured["all_numbers"])
    out = {"seed": seed, "fault": fault, "numbers": got,
           "at": ctx.measured.get("compared_at")}
    by_leaf = ctx.traffic["kind"] == "train"

    def leaves(readings):           # the first gradient's difference, leaf
        ref = loop.ref_readings     # by leaf, to choose a number from
        return correct.leaf_diffs(readings["grad_sample"],
                                  ref["grad_sample"], ref["grad_sample"])

    if by_leaf:
        out["grad_diff_by_leaf"] = leaves(loop.prog)
    if control:
        out["control"] = loop.control()
        if by_leaf:
            out["control_grad_diff_by_leaf"] = leaves(loop.control_readings)
        for precision in also:      # read beside the control, for PERF.md
            out["control." + precision] = loop.control(precision)
    del loop, ctx
    gc.collect()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--also", default="", help="further precisions of the "
                    "reference to read beside the control, comma-separated")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    if a.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    cell = run.find_cell(bench, a.workload)
    run.place_caches()
    rows = []
    for i in range(a.seeds):
        seed = a.first_seed + 7919 * i
        rows.append(one(bench, cell, seed, a.seconds, a.rehearse,
                        control=i < a.control_seeds,
                        also=[p for p in a.also.split(",") if p]))
        print(json.dumps(rows[-1]), flush=True)
    for fault in [f for f in a.faults.split(",") if f]:
        for i in range(a.fault_seeds):
            seed = a.first_seed + 7919 * i
            rows.append(one(bench, cell, seed, a.seconds, a.rehearse, fault))
            print(json.dumps(rows[-1]), flush=True)
    names = sorted({k for r in rows for k in r["numbers"]})
    summary = {}
    for k in names:
        sound = [r["numbers"][k] for r in rows if not r["fault"]]
        ctl = [r["control"][k] for r in rows if "control" in r]
        summary[k] = {"lower": max(sound), "sound": sound,
                      "control_min": min(ctl) if ctl else None,
                      "control": ctl}
        for p in {c for r in rows for c in r if c.startswith("control.")}:
            summary[k][p] = [r[p][k] for r in rows if p in r]
        for fault in {r["fault"] for r in rows if r["fault"]}:
            summary[k]["fault." + fault] = [
                r["numbers"][k] for r in rows if r["fault"] == fault]
    print(json.dumps({"cell": a.workload, "summary": summary}), flush=True)
    if not a.rehearse:
        os.makedirs("chiprun_out/calibrate", exist_ok=True)
        with open(f"chiprun_out/calibrate/{a.workload}.json", "w") as f:
            json.dump({"cell": a.workload, "rows": rows,
                       "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
