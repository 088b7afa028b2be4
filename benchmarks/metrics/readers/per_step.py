"""A quantity the loop summed over the window, per step."""


def read(ctx, key, scale=1.0):
    m = ctx.measured
    if key not in m or not m.get("steps"):
        return None
    return m[key] * scale / m["steps"]
