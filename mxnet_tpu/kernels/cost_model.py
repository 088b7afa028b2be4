"""Per-cluster cost model: fuse or keep the 1:1 lowering, and with
which implementation.

The decision is dispatch-oriented, per the round-14 measurement that
eager/serving hot paths are dominated by per-node dispatch (128→29
nodes bought 3.73x): a cluster of N ops saves N-1 dispatches whatever
the backend, so the lax fallback is profitable as soon as a cluster is
non-trivial. Pallas is only ever *selected* on TPU and only when the
shapes meet the fp32 tile floor — everywhere else the kernel would run
interpreted (orders of magnitude slower), so the model never picks it
off-TPU (tests force it via ``impl=`` for parity checks).

Round 24: the policy THRESHOLDS here are declared autotune decision
points — ``declare_decision`` returns the heuristic default, so the
constant and its candidate space live on one line, and ``decide``
consults ``autotune.lookup`` before each threshold (a measured record
beats the hand-written value; a miss falls back to it). graft_lint
L1201 enforces the shape: a bare numeric policy literal in this file
is a lint error unless it went through ``declare_decision`` or carries
an ``allow(L1201)`` pragma (the tile floor below is hardware geometry,
not tunable policy).
"""
from __future__ import annotations

from dataclasses import dataclass

from ..autotune import declare_decision, lookup as _lookup

#: fp32 minimum tile (sublane, lane) a Pallas TPU kernel wants aligned
#: — hardware geometry, not a tunable policy
_TILE_ROWS = 8  # graft-lint: allow(L1201)
_TILE_COLS = 128  # graft-lint: allow(L1201)

#: a fused elementwise cluster must absorb at least this many ops —
#: below it there is no dispatch to save
MIN_CLUSTER = declare_decision(
    "fusion.min_cluster", candidates=(2, 3, 4), default=2,
    key_doc="(backend,)")


@dataclass(frozen=True)
class Decision:
    """Outcome of one cluster decision. ``fuse=False`` keeps the 1:1
    lowering; ``reason`` names why (the fallbacks-by-reason counter
    family); ``impl`` is ``lax`` or ``pallas`` when fusing."""
    fuse: bool
    impl: str = "lax"
    reason: str = "ok"


#: VMEM one Pallas TPU kernel may take: 7/8 of the 16 MiB the TPU
#: compiler scopes to a kernel by default — hardware geometry. The 1/8
#: margin is what the estimate below was seen to miss against the v5e
#: compiler (tests/test_chip_compile.py holds both sides of the line)
_VMEM_BUDGET_BYTES = 14 << 20  # graft-lint: allow(L1201)
_BLOCK_ROWS = 128  # graft-lint: allow(L1201)

#: activations whose bodies use a primitive the Pallas TPU lowering
#: does not implement (expm1, erfc) — the norm_act kernel cannot take
#: them, the lax replay can
_PALLAS_UNLOWERABLE_ACTS = frozenset({"elu", "selu", "gelu"})


def pallas_vmem_bytes(pattern, shape, itemsize=4):
    """Upper estimate of the VMEM one grid step of ``pattern``'s TPU
    kernel holds, from shape and dtype width alone: every operand and
    result block double-buffered by the pipeline, the fp32 scratch, and
    one fp32 working copy of the largest block. ``shape`` is the
    cluster output shape — for ``attention_decode`` the ``(S, D)`` of
    one cache row, which that kernel streams in whole; for
    ``attention`` the ``(S, D)`` of one head, priced at the tiles the
    flash kernels' own chooser gives its two passes."""
    # lanes pad to the tile; graft-lint: allow(L1201)
    lanes = -(-int(shape[-1]) // _TILE_COLS) * _TILE_COLS
    if pattern == "norm_act":
        rows = 1
        for d in shape[:-1]:
            rows *= int(d)
        tile = max(_TILE_ROWS, min(_BLOCK_ROWS, rows)) * lanes
        blocks, scratch, largest = 2 * tile, 0, tile  # x in, y out
    elif pattern == "attention":
        # the kernel's own chooser and footprints, forward and backward
        # (imported here: flash_attention imports Pallas)
        from .flash_attention import vmem_bytes
        return vmem_bytes(int(shape[-2]), int(shape[-2]), int(shape[-1]),
                          itemsize)
    elif pattern == "attention_decode":
        row = int(shape[-2]) * lanes
        blocks, scratch, largest = 2 * row, 0, row  # K row, V row
    else:
        raise ValueError(f"no TPU kernel for pattern {pattern!r}")
    # x2: double-buffered; x4: fp32 working copy
    return 2 * blocks * itemsize + scratch + 4 * largest  # graft-lint: allow(L1201)


def pallas_fits_vmem(pattern, shape, itemsize=4):
    """Does one grid step of the pattern's TPU kernel fit the budget?"""
    return pallas_vmem_bytes(pattern, shape, itemsize) <= _VMEM_BUDGET_BYTES


def _pallas_refusal(pattern, out_shape, itemsize=4, act_type=None):
    """Why the pattern's TPU kernel cannot take this cluster, or None
    when it is viable: no kernel / unknown shape, output off the tile floor
    (misaligned shapes pay relayout more than the kernel wins), an
    absorbed activation the Pallas TPU lowering does not implement, or
    blocks over the VMEM budget at that shape and dtype width (the
    kernels hold whole rows — an oversize row would be refused by the
    chip's compiler at bind time)."""
    if pattern not in ("norm_act", "attention", "attention_decode"):
        return "no_kernel"
    if not out_shape or len(out_shape) < 2:
        return "no_shape"
    if out_shape[-1] % _TILE_COLS or out_shape[-2] % _TILE_ROWS:
        return "tile_misaligned"
    if act_type in _PALLAS_UNLOWERABLE_ACTS:
        return "act_unlowerable"
    if not pallas_fits_vmem(pattern, out_shape, itemsize):
        return "vmem_bound"
    return None


#: sequence length at which a lax attention cluster goes compute-bound:
#: round 17 measured (CPU, toy width) the fused lax replay at 0.92x of
#: the 1:1 lowering once both score dims reach 64 — the QK^T/PV matmuls
#: dominate and the fused executable only denies XLA its own gemm
#: scheduling — and at 1.74x at seq 16: the crossover is really a function
#: of feature width (narrow heads stay dispatch-dominated far past
#: seq 64), which is why the consult key carries a feat bucket — the
#: candidate 4096 effectively means "never compute-bound".
_ATTN_COMPUTE_BOUND_SEQ = declare_decision(
    "fusion.attn_compute_bound_seq",
    candidates=(16, 32, 64, 128, 4096), default=64,
    key_doc="(backend, pow2-bucket of cluster output feature dim)")

#: past 2**this elements an elementwise chain is bandwidth-bound and
#: XLA's own loop fusion already covers it; the fused dispatch saves
#: nothing but costs a fresh executable
_ELEMENTWISE_BANDWIDTH_LOG2 = declare_decision(
    "fusion.elementwise_bandwidth_log2",
    candidates=(20, 22, 24), default=22,
    key_doc="(backend,)")


def _bucket_pow2(n):
    """Power-of-two ceiling bucket for a consult-key dimension (0 for
    unknown): records generalize across nearby widths instead of
    fragmenting per exact shape."""
    n = int(n)
    if n <= 0:
        return 0
    return 1 << (n - 1).bit_length()


def decide(pattern, n_nodes, out_shape=None, backend="cpu",
           mode="heuristic", score_shape=None, act_type=None):
    """Decide one cluster: ``Decision(fuse, impl, reason)``.

    ``pattern`` is the cluster kind, ``n_nodes`` the member-op count,
    ``out_shape`` the cluster output shape when the shape fact resolved
    it (None otherwise), ``backend`` the jax default backend, ``mode``
    the ``MXNET_FUSION_COST_MODEL`` knob. For ``attention`` clusters,
    ``score_shape`` is the (..., seq_q, seq_k) shape of the QK^T score
    tensor when known; for ``norm_act``, ``act_type`` is the absorbed
    activation. The graph carries no per-node dtype, so the VMEM bound
    prices blocks at fp32 width (the widest the kernels take).

    Each threshold consults the autotune record store first
    (``MXNET_AUTOTUNE=0`` turns that into a constant-time no-op) and
    falls back to the declared heuristic default on miss.
    """
    if mode == "never":
        return Decision(False, reason="cost_model_never")
    refusal = (_pallas_refusal(pattern, out_shape, act_type=act_type)
               if backend == "tpu" else "off_tpu")
    impl = "lax" if refusal else "pallas"
    # a kernel refused for what only the chip's compiler would have
    # caught still fuses, priced as lax — the reason rides the decision
    # so the pass can count it (fallback_vmem_bound, ...)
    fused = Decision(True, impl=impl,
                     reason=refusal if refusal in (
                         "vmem_bound", "act_unlowerable") else "ok")
    if mode == "always":
        return fused
    min_cluster = _lookup("fusion.min_cluster", (backend,))
    if min_cluster is None:
        min_cluster = MIN_CLUSTER
    if n_nodes < min_cluster:
        # a 1-op "cluster" saves zero dispatches and costs a retrace
        return Decision(False, reason="too_small")
    if (pattern == "attention" and impl == "lax"
            and score_shape is not None and len(score_shape) >= 2):
        feat = out_shape[-1] if out_shape else 0
        bound = _lookup("fusion.attn_compute_bound_seq",
                        (backend, _bucket_pow2(feat)))
        if bound is None:
            bound = _ATTN_COMPUTE_BOUND_SEQ
        if score_shape[-2] >= bound and score_shape[-1] >= bound:
            return Decision(False, reason="compute_bound_attention")
    if pattern == "elementwise" and out_shape is not None:
        size = 1
        for d in out_shape:
            size *= int(d)
        log2_cap = _lookup("fusion.elementwise_bandwidth_log2",
                           (backend,))
        if log2_cap is None:
            log2_cap = _ELEMENTWISE_BANDWIDTH_LOG2
        if size > (1 << log2_cap):
            return Decision(False, reason="bandwidth_bound")
    return fused
