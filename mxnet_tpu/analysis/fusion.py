"""Fusion clustering: group fusable subgraphs into kernels-package ops.

The round-17 rewrite pass. XLA fuses well *inside* one compiled
program, but every graph node costs one dispatch on the eager /
serving paths, and XLA's automatic fusion still splits around
reductions ("Operator Fusion in XLA: Analysis and Evaluation",
PAPERS.md). This pass pattern-matches three cluster kinds over the
``_Graph`` work list —

- **elementwise** maximal chains/trees of pure, single-consumer
  elementwise ops (``kernels.elementwise.ELEMENTWISE_OPS``),
- **norm_act** ``layer_norm`` feeding one activation node
  (BatchNorm→act is matched but always rejected: ``batch_norm`` is
  effectful through the aux-state machinery — counted as
  ``fallback_effectful``),
- **attention** ``batch_dot(softmax(batch_dot(q, k, T) [*/ scale]),
  v)`` score→softmax→weighted-sum,

— and replaces each profitable cluster with ONE fused op from
``mxnet_tpu.kernels``. Profitability and implementation (``lax``
replay everywhere, ``pallas`` on TPU when shapes meet the tile floor)
are decided per-cluster by ``kernels.cost_model.decide``; rejected
candidates keep their 1:1 lowering and the reason lands in the
fusion counters. A bad fused kernel is caught by ``optimize_symbol``'s
post-verify, which falls the whole graph back to the original (the
round-14 rejection safety net, counted as ``fallback_post_verify``).

Pattern classification and per-node shapes are memoized ``PassContext``
facts (``fusion_patterns``, ``node_shapes``) — verify-then-optimize
and fixpoint iterations classify each original node once.
"""
from __future__ import annotations

from .graph_opt import (REWRITE_PASSES, AnalysisPass, RewritePass,
                        _fresh_like, _key, _use_counts, op_is_pure)
from .passes import FactError

#: activation-op defaults, needed to resolve the effective act_type of
#: a matched activation node (replay passes the node kwargs verbatim,
#: so defaults only matter for *matching*)
_ACT_DEFAULTS = {"activation": "relu", "leaky_relu": "leaky"}

_SCALE_OPS = {"broadcast_mul_scalar": "mul", "broadcast_div_scalar": "div"}


class _Unfreezable(Exception):
    pass


def _freeze(v):
    """Kwarg value -> hashable, repr-stable form (tuples for lists);
    raises _Unfreezable for anything a static jit kwarg can't carry."""
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, (str, int, float, bool, type(None))):
        return v
    try:
        hash(v)
    except TypeError:
        raise _Unfreezable from None
    return v


def _frozen_kwargs(node):
    """``node._kwargs`` as a sorted, hashable items tuple, or None when
    any value resists freezing (such a node is never absorbed)."""
    try:
        return tuple((k, _freeze(v))
                     for k, v in sorted(node._kwargs.items()))
    except _Unfreezable:
        return None


# ---------------------------------------------------------------------------
# memoized facts

def _classify(node):
    """Pattern role of one node, or None. Pure classification — no
    use-count/head checks here (those are graph-state, not node-state)."""
    from ..kernels.elementwise import ELEMENTWISE_OPS
    from ..kernels.norm_act import FUSABLE_ACTS

    op = node._op
    if op is None or node._num_outputs != 1 or not op_is_pure(op):
        return "bn_act_candidate" if op == "batch_norm" else None
    roles = []
    if op in ELEMENTWISE_OPS:
        roles.append("elementwise")
    if op in FUSABLE_ACTS:
        eff = node._kwargs.get("act_type", _ACT_DEFAULTS.get(op))
        if eff in FUSABLE_ACTS[op]:
            roles.append("act")
    if op == "layer_norm" and not node._kwargs.get("output_mean_var"):
        roles.append("norm")
    if op == "batch_dot":
        roles.append("batch_dot")
    if op == "softmax":
        roles.append("softmax")
    if op in _SCALE_OPS and not node._kwargs.get("reverse"):
        roles.append("scale")
    return tuple(roles) or None


def _fusion_patterns_fact(ctx):
    """node key -> role tuple over the original graph (memoized; the
    rewrite re-classifies only nodes other passes created later)."""
    out = {}
    for n in ctx.nodes():
        out[_key(n)] = _classify(n)
    return out


def _node_shapes_fact(ctx):
    """node key -> inferred output shape (memoized). Rides the same
    walk as ``infer_shapes`` with the per-node table kept, so the cost
    model can price clusters; unknown shapes simply price as None."""
    from ..symbol.infer import infer_shapes

    known = dict(ctx.declared_shapes())
    known.update(ctx.known_shapes)
    try:
        _, _, node_out = infer_shapes(ctx.symbol, known,
                                      allow_unknown=True,
                                      return_node_shapes=True)
    except Exception:
        return FactError("node shape inference failed")
    by_id = {id(n): n for n in ctx.nodes()}
    return {_key(n): node_out[i] for i, n in by_id.items()
            if i in node_out}


fusion_pattern_analysis = AnalysisPass(
    "fusion_patterns", _fusion_patterns_fact,
    "node key -> fusion pattern roles")
node_shape_analysis = AnalysisPass(
    "node_shapes", _node_shapes_fact,
    "node key -> inferred output shape (for the fusion cost model)")


# ---------------------------------------------------------------------------
# the rewrite

def _roles(node, fact):
    k = _key(node)
    if k in fact:
        return fact[k] or ()
    return _classify(node) or ()  # node created by an earlier rewrite


def _shape_of(node, shapes):
    if isinstance(shapes, (FactError, type(None))):
        return None
    s = shapes.get(_key(node))
    if isinstance(s, list):
        s = s[node._output_index] if node._output_index < len(s) else None
    return s


def _plain_softmax(node):
    """True for softmax over the last axis with none of the masking /
    temperature / dtype extras (those change the replay contract)."""
    kw = node._kwargs
    return (len(node._inputs) == 1
            and kw.get("axis", -1) == -1
            and not kw.get("use_length")
            and kw.get("temperature") in (None, 1.0)
            and kw.get("dtype") is None)


def _fusion(graph, ctx):
    """The clustering rewrite body: match → cost-model → replace."""
    import jax

    from .. import kernels
    from ..kernels import cost_model

    if not kernels.fusion_enabled():
        kernels._count("pass_skipped_disabled")
        return 0
    patterns = kernels.enabled_patterns()
    mode = kernels.cost_model_mode()
    backend = jax.default_backend()
    fact = ctx.fact("fusion_patterns")
    shapes = ctx.fact("node_shapes")
    use_counts = _use_counts(graph)
    head_keys = {_key(h) for h in graph.heads}
    order = {_key(n): i for i, n in enumerate(graph.nodes)}

    consumed = set()
    mapping = {}
    clusters = 0

    def interior_ok(node):
        """May ``node`` be absorbed as a cluster interior? Single
        consumer, not a graph output, single-output, in the work
        list."""
        k = _key(node)
        return (k in order and k not in consumed and k not in head_keys
                and use_counts.get(k, 0) == 1 and node._num_outputs == 1
                and node._output_index == 0)

    def decide(pattern, members, root, score_shape=None, act_type=None):
        d = cost_model.decide(pattern, len(members),
                              out_shape=_shape_of(root, shapes),
                              backend=backend, mode=mode,
                              score_shape=score_shape, act_type=act_type)
        if d.fuse:
            kernels._count(f"clusters_{pattern}")
            kernels._count(f"impl_{d.impl}")
            kernels._count("nodes_absorbed", len(members) - 1)
        if d.reason != "ok":
            kernels._count(f"fallback_{d.reason}")
        return d

    def claim(members, root_key, fused):
        nonlocal clusters
        consumed.update(_key(m) for m in members)
        mapping[root_key] = fused
        clusters += 1

    # -- attention: most specific first ---------------------------------
    if "attention" in patterns:
        for n in reversed(graph.nodes):
            k = _key(n)
            if k in consumed or "batch_dot" not in _roles(n, fact):
                continue
            if n._kwargs.get("transpose_a") or \
                    n._kwargs.get("transpose_b") or len(n._inputs) != 2:
                continue
            p, v = n._inputs
            if "softmax" not in _roles(p, fact) or not interior_ok(p) \
                    or not _plain_softmax(p):
                continue
            s = p._inputs[0]
            scale_op, scale = "none", 1.0
            if s._op in _SCALE_OPS and interior_ok(s) \
                    and "scale" in _roles(s, fact):
                scale_op = _SCALE_OPS[s._op]
                scale = float(s._kwargs.get("scalar", 0.0))
                score = s._inputs[0]
            else:
                s, score = None, s
            if "batch_dot" not in _roles(score, fact) \
                    or not interior_ok(score):
                continue
            if score._kwargs.get("transpose_a") \
                    or not score._kwargs.get("transpose_b") \
                    or len(score._inputs) != 2:
                continue
            members = [score, p, n] + ([s] if s is not None else [])
            softmax_kw = _frozen_kwargs(p)
            if softmax_kw is None:
                continue
            d = decide("attention", members, n,
                       score_shape=_shape_of(score, shapes))
            if not d.fuse:
                continue
            q, kk = score._inputs
            claim(members, k, _fresh_like(n, "_fused_attention",
                                          [q, kk, v],
                                          {"scale_op": scale_op,
                                           "scale": scale,
                                           "softmax_kw": softmax_kw,
                                           "impl": d.impl}))

    # -- norm + activation ----------------------------------------------
    if "norm_act" in patterns:
        for n in reversed(graph.nodes):
            k = _key(n)
            if k in consumed or "act" not in _roles(n, fact):
                continue
            if len(n._inputs) != 1:
                continue  # prelu-style parameterized acts stay out
            ln = n._inputs[0]
            if "bn_act_candidate" in _roles(ln, fact):
                # the pattern the issue names, rejected by design:
                # batch_norm's running-stat write-back must survive
                kernels._count("fallback_effectful")
                continue
            if "norm" not in _roles(ln, fact) or not interior_ok(ln):
                continue
            if len(ln._inputs) != 3:
                continue
            members = [ln, n]
            norm_kw = _frozen_kwargs(ln)
            act_kw = _frozen_kwargs(n)
            if norm_kw is None or act_kw is None:
                continue
            d = decide("norm_act", members, n,
                       act_type=n._kwargs.get("act_type"))
            if not d.fuse:
                continue
            claim(members, k, _fresh_like(n, "_fused_norm_act",
                                          list(ln._inputs),
                                          {"norm_kw": norm_kw,
                                           "act_op": n._op,
                                           "act_kw": act_kw,
                                           "impl": d.impl}))

    # -- elementwise chains/trees ---------------------------------------
    if "elementwise" in patterns:
        for n in reversed(graph.nodes):
            k = _key(n)
            if k in consumed or "elementwise" not in _roles(n, fact):
                continue
            if _frozen_kwargs(n) is None:
                continue
            members, frontier = [n], list(n._inputs)
            member_keys = {k}
            while frontier:
                cand = frontier.pop()
                ck = _key(cand)
                if ck in member_keys:
                    continue
                if "elementwise" in _roles(cand, fact) \
                        and interior_ok(cand) \
                        and _frozen_kwargs(cand) is not None:
                    member_keys.add(ck)
                    members.append(cand)
                    frontier.extend(cand._inputs)
            if len(members) < 2:
                kernels._count("fallback_too_small")
                continue
            d = decide("elementwise", members, n)
            if not d.fuse:
                continue
            fused = _build_elementwise(members, member_keys, n, order)
            if fused is None:
                continue
            claim(members, k, fused)

    graph.apply(mapping)
    return clusters


def _build_elementwise(members, member_keys, root, order):
    """Emit the ``_fused_elementwise`` replacement for one chain/tree:
    topo-sort the members, collect external inputs (first-seen order),
    and serialize each member as a ``(op, arg_slots, kw_items)`` step
    over the slot file."""
    members = sorted(members, key=lambda m: order.get(_key(m), 1 << 30))
    ext, ext_slot = [], {}
    # slot of each member's result, assigned as steps are emitted
    member_slot = {}
    steps = []
    for m in members:
        arg_slots = []
        for i in m._inputs:
            ik = _key(i)
            if ik in member_keys and i._output_index == 0:
                arg_slots.append(("m", ik))
            else:
                ek = (ik, i._output_index)
                if ek not in ext_slot:
                    ext_slot[ek] = len(ext)
                    ext.append(i)
                arg_slots.append(("e", ext_slot[ek]))
        steps.append((m, arg_slots))
    n_ext = len(ext)
    program = []
    for j, (m, arg_slots) in enumerate(steps):
        resolved = []
        for tag, val in arg_slots:
            if tag == "m":
                if val not in member_slot:
                    return None  # member used before computed: bail
                resolved.append(member_slot[val])
            else:
                resolved.append(val)
        kw = _frozen_kwargs(m)
        program.append((m._op, tuple(resolved), kw))
        member_slot[_key(m)] = n_ext + j
    return _fresh_like(root, "_fused_elementwise", ext,
                       {"program": tuple(program)})


fusion_pass = RewritePass(
    "fusion", _fusion,
    "cluster fusable subgraphs into kernels-package fused ops")
REWRITE_PASSES["fusion"] = fusion_pass
