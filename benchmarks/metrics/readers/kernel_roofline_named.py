"""A kernel's share of its roofline, the kernel found by its HLO
instruction's name (a Pallas kernel's ``name=``) as ``op_time`` finds
it: the least time the chip could take for the work of the traced
window's steps (the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s) over the summed device time of the matching ops in the window.

``kernel`` names the function in ``flops/<config>.py`` that gives
(flops, bytes) of one STEP's calls, from the shapes and whatever
implements them. The window's steps are the traced run's rate over the
items of a step, times the window's seconds. Returns nothing when no
such op ran, or when the configuration counts no such kernel."""
import re
import sys

import trace_reduce


def read(ctx, kernel, match):
    m = ctx.measured
    summ = m.get("trace_summary")
    work = getattr(ctx.flops, kernel, None)
    if ctx.trace is None or not summ or not ctx.peak or work is None \
            or not m.get("traced_rate") or not m.get("items_per_step"):
        return None
    devs = ctx.trace["devices"]
    rx = re.compile(match)
    picked = [e for e in devs[min(devs)] if rx.search(e[0])]
    seconds, calls = trace_reduce.time_of(picked, summ["window"], "")
    if not calls or seconds <= 0:
        return None
    steps = m["traced_rate"] / m["items_per_step"] * summ["window_s"]
    flops, nbytes = work(ctx.cfg, ctx.traffic)
    t_flops = flops / ctx.peak["bf16_flops"]
    t_bytes = nbytes / ctx.peak["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    print(f"[bench] {kernel}: {calls} calls in {steps:.2f} steps, "
          f"{seconds / steps * 1e3:.3f} ms a step, {bound}-bound roof "
          f"{max(t_flops, t_bytes) * 1e3:.3f} ms a step", file=sys.stderr)
    return 100.0 * steps * max(t_flops, t_bytes) / seconds
