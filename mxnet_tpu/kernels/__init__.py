"""mxnet_tpu.kernels — fused cluster kernels the fusion pass lowers to.

The round-17 fusion-clustering pass (``analysis/fusion.py``) groups
fusable subgraphs — elementwise chains, norm+activation, attention
score→softmax→weighted-sum — into single cluster ops registered HERE.
Each cluster op carries two implementations:

- a **Pallas kernel** where the backend supports it (TPU; the round-8
  flash-attention kernel moved here as ``kernels/flash_attention.py``),
- a **lax-level fused fallback** everywhere else: the cluster replays
  the member ops' registered bodies inside ONE dispatch, so eager and
  serving paths pay one compiled-executable call instead of N and the
  math stays bit-identical to the unfused graph (same primitives, same
  order — XLA does not reassociate).

The per-cluster choice is made by ``cost_model.decide`` and recorded in
the counters below (cluster hits, fallbacks by reason, per-pattern
rewrite counts) — surfaced through ``profiler.dump()`` and the serving
``/metrics`` endpoint. This package is also the only place allowed to
import Pallas (graft_lint L801).

Knobs: ``MXNET_FUSION=0`` kill switch, ``MXNET_FUSION_PATTERNS``
(comma list of ``elementwise,norm_act,attention,serving``),
``MXNET_FUSION_COST_MODEL`` (``heuristic`` | ``always`` | ``never``).
"""
from __future__ import annotations

from .. import env
from ..telemetry import metrics as _telemetry

# registry-owned since round 18: the family keys grow on first use
# (clusters_<pattern>, fallback_<reason>...), so no zero template
_COUNTERS = _telemetry.counter_family("fusion")

#: every pattern the clustering pass + serving specialization know
ALL_PATTERNS = ("elementwise", "norm_act", "attention", "serving")


def _count(name, n=1):
    _COUNTERS.add(name, n)


def counters():
    """Snapshot of the fusion counters: ``clusters_<pattern>`` rewrite
    counts, ``nodes_absorbed``, ``impl_<lax|pallas>`` selections,
    ``fallback_<reason>`` rejections, the serving
    ``serving_pad_fused`` / ``serving_slice_fused`` call counts, and
    what each kernel file counts of its own lowerings at trace time
    (``flash_*``, ``moe_gmm_*``; ``gdn_pallas`` / ``gdn_plain``: which
    lowering a trace of the gated delta rule took, ``gdn_chunks``: the
    chunks a head its passes walk; ``ssm_scan_pallas`` /
    ``ssm_scan_plain``: which lowering a trace of the selective scan
    took, ``ssm_scan_chunks``: the chunks a channel tile its passes walk,
    ``kernels/selective_scan.py``; ``qk_prologue_pallas`` /
    ``qk_prologue_plain``: which lowering each attention layer's q/k
    norms, RoPE and layout took, ``kernels/qk_prologue.py``;
    ``delta_prologue_pallas`` / ``delta_prologue_plain``: which lowering
    each Gated DeltaNet layer's convolution, l2 norms and layout took,
    ``kernels/delta_prologue.py``; ``short_conv_pallas`` /
    ``short_conv_plain``: which lowering each gated short convolution
    took, ``kernels/short_conv.py``; ``pick_masked``: the ``pick`` ops a
    trace lowered as a masked sum, ``ndarray/ops_index.py``)."""
    return _COUNTERS.snapshot()


def reset_counters():
    _COUNTERS.clear()


# ------------------------------------------------------------- knobs ------

def fusion_enabled():
    """``MXNET_FUSION`` kill switch (default on — the clustering pass
    itself only runs under ``MXNET_GRAPH_OPT>=1``)."""
    return env.get_bool("MXNET_FUSION", True)


def enabled_patterns():
    """Patterns armed via ``MXNET_FUSION_PATTERNS`` (comma list;
    unknown names are ignored so a typo degrades, never crashes)."""
    raw = env.get_str("MXNET_FUSION_PATTERNS",
                      "elementwise,norm_act,attention,serving")
    pats = tuple(p.strip() for p in raw.split(",") if p.strip())
    return tuple(p for p in pats if p in ALL_PATTERNS)


def cost_model_mode():
    """``MXNET_FUSION_COST_MODEL``: ``heuristic`` (default) applies the
    per-pattern profitability rules, ``always`` fuses every match,
    ``never`` rejects every match (pass still runs, counters still
    record the candidates)."""
    mode = env.get_str("MXNET_FUSION_COST_MODEL", "heuristic")
    return mode if mode in ("heuristic", "always", "never") else "heuristic"


def fusion_salt():
    """Fingerprint/cache-key component for the fusion configuration:
    flipping any fusion knob must never collide optimized artifacts
    (the round-14 graph-opt salt rule extended to round 17)."""
    if not fusion_enabled():
        return ("fusion", 0)
    return ("fusion", 1, enabled_patterns(), cost_model_mode())


# registering the cluster ops is an import side effect, matching how
# ndarray/ops_*.py populate the registry
from . import elementwise  # noqa: E402,F401
from . import norm_act  # noqa: E402,F401
from . import attention  # noqa: E402,F401
from .cost_model import decide  # noqa: E402,F401

__all__ = [
    "ALL_PATTERNS", "counters", "reset_counters", "fusion_enabled",
    "enabled_patterns", "cost_model_mode", "fusion_salt", "decide",
]
