"""The arithmetic of ``correct``: how two sets of readings are compared.

Training. Each side gives three losses, the norm of the first gradient
of every trained leaf, the norm of every leaf's change over the three
steps, and for state leaves (running statistics) the norm of their change
from the seed's state after each step. A leaf's gap is the gap between
the two norms (not the norm of the difference), measured against the
reference's norm of that leaf or of the median leaf, whichever is larger:
some gradients are all but zero. Each family gives the worst leaf's gap
(``*_gap``) and the median leaf's (``*_med_gap``); the cell's limits file
says which of them are compared. Leaves whose gradient is nought to
rounding in the reference (under a thousandth of the median leaf's: a
bias in front of a BatchNorm) move by round-off alone and are left out of
the change.

Norms average rounding away: a reference whose products take fp8 operands
reads like the bf16 program on every gap of norms (PERF.md, PR 27). So
each side also gives the first gradient itself on a lattice of up to
65,536 elements of every leaf (``refcommon.sample``). A leaf's difference
is the norm of the difference of the two samples over the norm of the
reference's, with the leaves whose gradient is nought to rounding left
out as above: ``grad_diff`` is the worst leaf's, ``grad_diff_med`` the
median leaf's, and ``grad_diff_least`` the least among the weights of
products (leaves with two axes or more). Rounding that a gradient
inherits on its way down grows from layer to layer on both sides alike;
the weight whose gradient has come the shortest way shows what the
products themselves add, since its gradient is one.

Scoring. The widest gap between a served logit and the reference's, over
every compared call, against the largest reference logit of that call.
"""
from __future__ import annotations

import statistics

NOUGHT = 1e-3     # of the median leaf's gradient norm


def _gaps(prog, ref, leaves):
    """(worst gap, its leaf, median gap) over ``leaves``."""
    if not leaves:
        return None, None, None
    med = statistics.median(ref[k] for k in leaves)
    worst, at, gaps = 0.0, None, []
    for k in leaves:
        if k not in prog:
            return float("inf"), k, float("inf")
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        gaps.append(gap if gap == gap else float("inf"))
        if not gap <= worst:        # also catches nan
            worst, at = gap, k
    return float(worst), at, float(statistics.median(gaps))


def leaf_diffs(prog, ref, leaves):
    """{leaf: |prog - ref| / |ref| on the sampled elements of the leaf};
    a leaf the program lacks reads inf."""
    import numpy as onp

    out = {}
    for k in leaves:
        p, r = prog.get(k), onp.asarray(ref[k], onp.float64)
        if p is None or p.shape != r.shape:
            out[k] = float("inf")
            continue
        d = float(onp.linalg.norm(onp.asarray(p, onp.float64) - r)
                  / max(onp.linalg.norm(r), 1e-30))
        out[k] = d if d == d else float("inf")      # nan never passes
    return out


def train_numbers(prog, ref):
    """{name: value} and {name: leaf} from two readings, each
    {"loss": [l1, l2, l3], "grad": {leaf: norm}, "delta": {leaf: norm},
    "stat": {state leaf: norm of its changes from the seed's state},
    "grad_sample": {leaf: sampled elements of the first gradient}}; the
    reference adds "axes": {leaf: number of axes}."""
    loss_gap = max(abs(p - r) / max(abs(r), 1e-30)
                   for p, r in zip(prog["loss"], ref["loss"]))
    if len(prog["loss"]) != len(ref["loss"]):
        loss_gap = float("inf")
    loss1_gap = abs(prog["loss"][0] - ref["loss"][0]) \
        / max(abs(ref["loss"][0]), 1e-30)
    grad_leaves = list(ref["grad"])
    grad_gap, grad_at, grad_med = _gaps(prog["grad"], ref["grad"],
                                        grad_leaves)
    med = statistics.median(ref["grad"].values())
    moved = [k for k in ref["delta"]
             if k not in ref["grad"] or ref["grad"][k] >= NOUGHT * med]
    delta_gap, delta_at, delta_med = _gaps(prog["delta"], ref["delta"], moved)
    values = {"loss1_gap": float(loss1_gap), "loss_gap": float(loss_gap),
              "grad_gap": grad_gap, "grad_med_gap": grad_med,
              "delta_gap": delta_gap, "delta_med_gap": delta_med}
    at = {"grad_gap": grad_at, "delta_gap": delta_at,
          "left_out_of_delta": len(ref["delta"]) - len(moved)}
    if ref.get("grad_sample"):
        diffs = leaf_diffs(prog.get("grad_sample", {}), ref["grad_sample"],
                           [k for k in ref["grad_sample"]
                            if ref["grad"][k] >= NOUGHT * med])
        at["grad_diff"] = max(diffs, key=diffs.get)
        values["grad_diff"] = diffs[at["grad_diff"]]
        values["grad_diff_med"] = float(statistics.median(diffs.values()))
        weights = [k for k in diffs if ref.get("axes", {}).get(k, 0) >= 2]
        if weights:
            at["grad_diff_least"] = min(weights, key=diffs.get)
            values["grad_diff_least"] = diffs[at["grad_diff_least"]]
            if values["grad_diff"] == float("inf"):     # a leaf is missing
                values["grad_diff_least"] = float("inf")
    if ref.get("stat"):
        values["stat_gap"], at["stat_gap"], values["stat_med_gap"] = _gaps(
            prog.get("stat", {}), ref["stat"], list(ref["stat"]))
    return values, at


def logit_gap(served, reference):
    """Widest |served - reference| over one call's logits, against the
    call's largest |reference| logit."""
    import numpy as onp

    served = onp.asarray(served, onp.float64)
    reference = onp.asarray(reference, onp.float64)
    if served.shape != reference.shape or not onp.isfinite(served).all():
        return float("inf")
    return float(onp.abs(served - reference).max()
                 / max(onp.abs(reference).max(), 1e-30))


def with_limits(values, limits):
    """{name: {"value", "limit"}} for the numbers that have a limit; a
    number without one is not compared (PERF.md names it)."""
    out = {}
    for name, limit in limits.items():
        v = values.get(name)
        if v is not None and not abs(v) < 1e30:     # nan, inf: never pass
            v = 1e30                                # (and stay valid JSON)
        out[name] = {"value": v, "limit": limit}
    return out
