"""The expert layer's load, from the program's ``moe`` telemetry
counters: what the compiled step itself counted, published by
``SPMDTrainer`` as each step's arrays came ready.

``what`` is ``rows_per_expert`` (``moe/assignments_held`` over steps,
layers and held experts: how many rows a held expert sees a step) or
``max_over_mean`` (``moe/max_expert_rows``, the largest group of the
last step read, over that mean). Returns nothing when the program has
no such counters or published no step."""


def counters():
    try:
        from mxnet_tpu.telemetry import metrics

        return metrics.family_snapshot("moe")
    except Exception:       # no such module or family in this program
        return None


def read(ctx, what):
    c = counters()
    if not c or not c.get("steps"):
        return None
    groups = c["steps"] * ctx.cfg["num_hidden_layers"] \
        * ctx.cfg["num_experts"]
    mean = c.get("assignments_held", 0) / groups
    if what == "rows_per_expert":
        return mean
    if what == "max_over_mean":
        return c.get("max_expert_rows", 0) / mean if mean else None
    raise ValueError(f"what {what!r}")
