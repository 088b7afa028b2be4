"""Whole-step share of the chips' bf16 peak: model FLOPs per item times
the traced run's rate (profiler start/stop stalls taken out of the time),
over chips times peak. Nothing to read without a peak or a rate."""


def read(ctx, flops_fn):
    rate = ctx.measured.get("traced_rate")
    if not rate or not ctx.peak:
        return None
    per_item = getattr(ctx.flops, flops_fn)(ctx.cfg, ctx.traffic)
    return 100.0 * per_item * rate / (ctx.chips * ctx.peak["bf16_flops"])
