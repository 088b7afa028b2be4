"""A kernel's share of its roofline: the least time the chip could take
for the calls seen in the traced window (the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s, from benchmarks/flops/<config>.py)
over the summed device time of those calls. The kernel is found on device
0's op line as the custom call whose result has the kernel's shape.
Returns nothing when no such op ran."""
import re
import sys

import trace_reduce


def read(ctx, kernel, match):
    if ctx.trace is None or not ctx.peak:
        return None
    shape = getattr(ctx.flops, kernel + "_shape")(ctx.cfg, ctx.traffic)
    dims = ",".join(str(d) for d in shape)
    pattern = r"^%\S+ = \w+\[" + dims + r"\]\S* " + re.escape(match)
    devs = ctx.trace["devices"]
    seconds, calls = trace_reduce.time_of(
        devs[min(devs)], ctx.measured["trace_summary"]["window"], pattern)
    if not calls or seconds <= 0:
        return None
    flops, nbytes = getattr(ctx.flops, kernel)(ctx.cfg, ctx.traffic)
    t_flops = flops / ctx.peak["bf16_flops"]
    t_bytes = nbytes / ctx.peak["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    ctx.measured[kernel + "_bound"] = bound
    print(f"[bench] {kernel}: {calls} calls, {seconds / calls * 1e3:.3f} ms "
          f"each, {bound}-bound roof {max(t_flops, t_bytes) * 1e3:.3f} ms",
          file=sys.stderr)
    return 100.0 * calls * max(t_flops, t_bytes) / seconds
