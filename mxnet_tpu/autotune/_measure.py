"""Paired-median measurement: how the tuner prices a candidate.

Measure back-to-back (test, base) pairs and take the MEDIAN of the
per-pair ratios. CPU-frequency/scheduler drift moves on a scale of
seconds, so it hits both halves of an adjacent pair equally and
cancels in the ratio — where best-of-independent-runs would credit
whichever side happened to land on the quiet interval. Pair order
alternates so within-pair drift cancels in the median too; each half
takes the min of ``reps`` windows, which filters one-sided preemption
spikes (a slow patch landing on one half of a pair skews that ratio by
far more than the effect being measured). Callers own per-window
hygiene (``gc.collect()``, ring resets) inside their measure
callables — the helper only schedules and aggregates.
"""
from __future__ import annotations

import statistics

__all__ = ["paired_speedup"]


def paired_speedup(measure_base, measure_test, pairs, reps=1):
    """Median of per-pair (base / test) cost ratios over adjacent
    alternating pairs; each half is the min of ``reps`` windows. Both
    callables return a seconds-like cost (lower is better). Returns
    ``(best_base, best_test, speedup)`` — ``speedup`` > 1 means the
    test config beats the base config."""
    best = {"base": float("inf"), "test": float("inf")}
    ratios = []
    for i in range(pairs):
        order = ("test", "base") if i % 2 == 0 else ("base", "test")
        got = {}
        for side in order:
            fn = measure_base if side == "base" else measure_test
            got[side] = min(fn() for _ in range(reps))
            best[side] = min(best[side], got[side])
        ratios.append(got["test"] / got["base"])
    return best["base"], best["test"], 1.0 / statistics.median(ratios)
