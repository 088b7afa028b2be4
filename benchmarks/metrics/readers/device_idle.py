"""Share of the traced window in which no op ran, averaged over chips."""


def read(ctx):
    summ = ctx.measured.get("trace_summary")
    if not summ or summ["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - summ["busy_s"] / summ["window_s"])
