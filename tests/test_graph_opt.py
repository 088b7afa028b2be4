"""Graph-optimization pass manager (analysis/graph_opt.py): golden
before/after snapshots per rewrite pass, idempotence, negative cases
(PRNG/effectful never merged, heads never eliminated), the shared
verify/optimize fact cache, and bitwise parity of optimized graphs
through all three lowering entry points (Executor bind, SymbolBlock
hybridize, serving InferenceSession)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
import mxnet_tpu.symbol as sym
from mxnet_tpu import autograd, nd
from mxnet_tpu.analysis import graph_opt
from mxnet_tpu.analysis.graph_opt import (RewritePass, _Graph,
                                          optimize_symbol)


def _ops(s):
    """Sorted op-name multiset of the graph's work list (vars excluded)
    — the golden-snapshot representation."""
    return sorted(n._op for n in _Graph(s).nodes if n._op is not None)


def _nodes(s):
    return len(_Graph(s).nodes)


@pytest.fixture(autouse=True)
def _fresh_counters():
    graph_opt.reset_counters()
    yield
    graph_opt.reset_counters()


# ---------------------------------------------------------------------------
# golden before/after snapshots, one per rewrite pass

def test_fold_golden():
    x = sym.var("x")
    c = sym.ones((2, 2)) + sym.zeros((2, 2))
    out = x + c
    assert _ops(out) == ["_sym_ones", "_sym_zeros", "broadcast_add",
                         "broadcast_add"]
    # fold alone replaces the const root in place; the orphaned
    # literals stay on the WORK LIST until dce drops them — the two
    # passes are separately observable in the per-pass node counts
    clean, st = optimize_symbol(out, level=1, passes=("fold", "dce"))
    fold_st, dce_st = st["passes"]
    assert (fold_st["rewrites"], dce_st["rewrites"]) == (1, 2)
    assert fold_st["nodes_before"] == fold_st["nodes_after"] == 5
    assert dce_st["nodes_after"] == 3
    assert not st["rejected"]
    assert _ops(clean) == ["_sym_constant", "broadcast_add"]
    feed = {"x": nd.array(onp.arange(4, dtype="f").reshape(2, 2))}
    assert onp.array_equal(out.eval_with(dict(feed)).asnumpy(),
                           clean.eval_with(dict(feed)).asnumpy())


def test_cse_golden():
    x, w = sym.var("x"), sym.var("w")
    out = (x * w) + (x * w)
    assert _ops(out) == ["broadcast_add", "broadcast_mul",
                         "broadcast_mul"]
    opt, st = optimize_symbol(out, level=1, passes=("cse",))
    assert st["rewrites"] == 1
    assert _ops(opt) == ["broadcast_add", "broadcast_mul"]
    feed = {"x": nd.array(onp.arange(4, dtype="f").reshape(2, 2)),
            "w": nd.array(onp.full((2, 2), 3.0, "f"))}
    assert onp.array_equal(out.eval_with(dict(feed)).asnumpy(),
                           opt.eval_with(dict(feed)).asnumpy())


def test_transpose_elision_golden():
    x, w = sym.var("x"), sym.var("w")
    out = x.transpose((1, 0)).transpose((1, 0)) + w
    assert _ops(out) == ["broadcast_add", "transpose", "transpose"]
    opt, st = optimize_symbol(out, level=1,
                              passes=("transpose_elision", "dce"))
    assert st["rewrites"] >= 1
    assert _ops(opt) == ["broadcast_add"]
    feed = {"x": nd.array(onp.arange(6, dtype="f").reshape(2, 3)),
            "w": nd.array(onp.ones((2, 3), "f"))}
    assert onp.array_equal(out.eval_with(dict(feed)).asnumpy(),
                           opt.eval_with(dict(feed)).asnumpy())


def test_transpose_pair_composes_to_net_permutation():
    x = sym.var("x")
    out = x.transpose((1, 2, 0)).transpose((1, 2, 0))
    opt, _ = optimize_symbol(out, level=1,
                             passes=("transpose_elision", "dce"))
    ts = [n for n in _Graph(opt).nodes if n._op == "transpose"]
    assert len(ts) == 1
    assert tuple(ts[0]._kwargs["axes"]) == (2, 0, 1)
    feed = {"x": nd.array(onp.arange(24, dtype="f").reshape(2, 3, 4))}
    assert onp.array_equal(out.eval_with(dict(feed)).asnumpy(),
                           opt.eval_with(dict(feed)).asnumpy())


def test_default_transpose_pair_is_identity():
    # axes=None is the full reversal; two of them cancel at any rank
    x = sym.var("x")
    out = x.transpose().transpose() + sym.var("w")
    opt, _ = optimize_symbol(out, level=1,
                             passes=("transpose_elision", "dce"))
    assert _ops(opt) == ["broadcast_add"]


def test_reshape_chain_collapses():
    x, w = sym.var("x"), sym.var("w")
    out = x.reshape((16,)).reshape((2, 8)) + w
    opt, _ = optimize_symbol(out, level=1,
                             passes=("transpose_elision", "dce"))
    rs = [n for n in _Graph(opt).nodes if n._op == "reshape"]
    assert len(rs) == 1
    assert tuple(rs[0]._kwargs["shape"]) == (2, 8)
    feed = {"x": nd.array(onp.arange(16, dtype="f").reshape(4, 4)),
            "w": nd.array(onp.ones((2, 8), "f"))}
    assert onp.array_equal(out.eval_with(dict(feed)).asnumpy(),
                           opt.eval_with(dict(feed)).asnumpy())


def test_identity_reshape_elided_under_known_shape():
    x, w = sym.var("x"), sym.var("w")
    out = x.reshape((4, 4)) + w
    opt, _ = optimize_symbol(out, shapes={"x": (4, 4)}, level=1,
                             passes=("transpose_elision", "dce"))
    assert _ops(opt) == ["broadcast_add"]
    # without the shape fact the reshape must stay (it may not be the
    # identity for some other binding)
    kept, st = optimize_symbol(out, level=1,
                               passes=("transpose_elision", "dce"))
    assert st["rewrites"] == 0 and kept is out


def test_dce_golden():
    x = sym.var("x")
    dead = x * sym.var("unused_w")
    out = sym.Group([x + x])
    # splice the dead producer into the walk via a group head, then
    # take only the live head: build a graph where the work list holds
    # an orphan by construction — fold's replacement does this in real
    # pipelines; here the simplest observable case is post-CSE orphans
    a, b = x * x, x * x
    g = a + b
    opt, st = optimize_symbol(g, level=1, passes=("cse", "dce"))
    assert _ops(opt) == ["broadcast_add", "broadcast_mul"]
    assert st["rewrites"] >= 1
    del dead, out


# ---------------------------------------------------------------------------
# pipeline behavior

def test_level2_fixpoint_and_idempotence(monkeypatch):
    # fusion off: this golden pins the round-14 fold/cse/elision/dce
    # behavior (with fusion on, the surviving mul+add cluster becomes
    # one _fused_elementwise — covered by tests/test_fusion.py)
    monkeypatch.setenv("MXNET_FUSION", "0")
    x, w = sym.var("x"), sym.var("w")
    t = x.transpose((1, 0)).transpose((1, 0))
    out = (t * w) + (x * w) + (sym.ones((4, 4)) + sym.ones((4, 4)))
    opt, st = optimize_symbol(out, shapes={"x": (4, 4), "w": (4, 4)},
                              level=2)
    assert st["nodes_after"] < st["nodes_before"]
    # elision exposes t*w == x*w only on the second iteration; the
    # fixpoint (level 2) must reach it
    muls = [n for n in _Graph(opt).nodes if n._op == "broadcast_mul"]
    assert len(muls) == 1
    # idempotence: a second run over the optimized graph is a no-op
    again, st2 = optimize_symbol(opt, level=2)
    assert st2["rewrites"] == 0
    assert again is opt
    feed = {"x": nd.array(onp.arange(16, dtype="f").reshape(4, 4)),
            "w": nd.array(onp.full((4, 4), 2.0, "f"))}
    assert onp.array_equal(out.eval_with(dict(feed)).asnumpy(),
                           opt.eval_with(dict(feed)).asnumpy())


def test_per_pass_stats_and_counters():
    x = sym.var("x")
    out = (x * x) + (x * x)
    _, st = optimize_symbol(out, level=1)
    names = [p["pass"] for p in st["passes"]]
    assert names == ["fold", "cse", "transpose_elision", "fusion",
                     "dce"]
    for p in st["passes"]:
        assert p["nodes_before"] >= p["nodes_after"]
        assert p["time_ms"] >= 0
    c = graph_opt.counters()
    assert c["graphs_optimized"] == 1
    assert c["cse_rewrites"] == 1
    assert c["nodes_before_total"] > c["nodes_after_total"]
    from mxnet_tpu import profiler
    assert profiler.graph_opt_counters()["graphs_optimized"] == 1


def test_redundant_graph_gives_every_pass_work_and_none_is_rejected():
    """A graph with a transpose pair, three identical ``t*t + x``
    chains, a chain of literal ones and a reshape round trip: the node
    count falls, fold, cse, transpose_elision and dce each rewrite
    something, and post-verify rejects no pass."""
    x = sym.var("x")
    t = x.transpose((1, 0)).transpose((1, 0))
    body = c = None
    for _ in range(3):
        v = t * t + x
        body = v if body is None else body + v
        c = sym.ones((4, 4)) if c is None else c + sym.ones((4, 4))
    r = x.reshape((-1,)).reshape((16,)).reshape((4, 4))
    out = (body + c) + r
    opt, st = optimize_symbol(out, shapes={"x": (4, 4)}, level=2)
    assert st["nodes_after"] < st["nodes_before"]
    assert not st["rejected"]
    rewrites = {}
    for row in st["passes"]:
        rewrites[row["pass"]] = rewrites.get(row["pass"], 0) \
            + row["rewrites"]
    assert all(rewrites[p] > 0 for p in
               ("fold", "cse", "transpose_elision", "dce")), rewrites
    assert graph_opt.counters()["graphs_rejected"] == 0
    feed = {"x": nd.array(onp.arange(16, dtype="f").reshape(4, 4))}
    assert onp.array_equal(out.eval_with(dict(feed)).asnumpy(),
                           opt.eval_with(dict(feed)).asnumpy())


def test_level0_is_passthrough():
    x = sym.var("x")
    out = (x * x) + (x * x)
    opt, st = optimize_symbol(out, level=0)
    assert opt is out and st["rewrites"] == 0
    assert graph_opt.counters()["graphs_seen"] == 0


def test_opt_level_reads_env(monkeypatch):
    monkeypatch.delenv("MXNET_GRAPH_OPT", raising=False)
    assert graph_opt.opt_level() == 0
    monkeypatch.setenv("MXNET_GRAPH_OPT", "2")
    assert graph_opt.opt_level() == 2
    assert graph_opt.graph_opt_enabled()
    monkeypatch.setenv("MXNET_GRAPH_OPT", "7")
    assert graph_opt.opt_level() == 2  # clamped
    from mxnet_tpu import runtime
    assert runtime._detect()["GRAPH_OPT"] is True


def test_fingerprint_salt_versions_artifacts(monkeypatch):
    monkeypatch.setenv("MXNET_GRAPH_OPT", "0")
    s0 = graph_opt.fingerprint_salt()
    monkeypatch.setenv("MXNET_GRAPH_OPT", "2")
    s2 = graph_opt.fingerprint_salt()
    assert s0 != s2
    assert graph_opt.PIPELINE_VERSION in s2
    assert graph_opt.PIPELINE_VERSION not in s0


# ---------------------------------------------------------------------------
# negative cases: what must NOT be rewritten

def test_prng_ops_never_cse():
    x = sym.var("x")
    d1 = sym.dropout(x, p=0.5)
    d2 = sym.dropout(x, p=0.5)
    out = sym.Group([d1, d2])
    opt, st = optimize_symbol(out, level=2)
    assert st["rewrites"] == 0 and opt is out
    assert not graph_opt.op_is_pure("dropout")


def test_effectful_ops_never_merged():
    x = sym.var("x")
    args = [sym.var(n) for n in ("g", "b", "mm", "mv")]
    b1 = sym.batch_norm(x, *args)
    b2 = sym.batch_norm(x, *args)
    out = sym.Group([b1, b2])
    opt, st = optimize_symbol(out, level=2)
    assert st["rewrites"] == 0 and opt is out
    assert not graph_opt.op_is_pure("batch_norm")


def test_prng_ops_never_folded():
    # a PRNG op over constant inputs must NOT be frozen to one draw
    c = sym.ones((2, 2))
    d = sym.dropout(c, p=0.5)
    opt, st = optimize_symbol(d, level=2)
    assert "dropout" in _ops(opt)


def test_group_heads_survive_dce():
    # every head is a DCE root: a Group output consumed by nothing
    # else (a grad_req output, an aux head) must never be eliminated
    x = sym.var("x")
    side = x * sym.var("w_side")
    main = x + x
    out = sym.Group([main, side])
    opt, _ = optimize_symbol(out, level=2)
    assert len(_Graph(opt).heads) == 2
    assert "broadcast_mul" in _ops(opt)


def test_positional_reshape_codes_not_collapsed():
    # 0 / -2 / -3 / -4 reshape codes depend on the INPUT shape; the
    # outer spec here is position-dependent, so the chain must stay
    x = sym.var("x")
    out = x.reshape((2, 8)).reshape((0, -1))
    opt, st = optimize_symbol(out, level=2)
    assert st["rewrites"] == 0 and opt is out


def test_bad_rewrite_is_rejected_by_post_verify():
    from mxnet_tpu.symbol import Symbol

    def breaker(graph, ctx):
        head = graph.heads[0]
        bad = Symbol(op="zz_unregistered_op", name=head._name,
                     inputs=list(head._inputs), kwargs={})
        graph.apply({graph_opt._key(head): bad})
        return 1

    x = sym.var("x")
    out = x + x
    opt, st = optimize_symbol(
        out, level=1, passes=[RewritePass("breaker", breaker)])
    assert opt is out
    assert st["rejected"] is True
    assert graph_opt.counters()["graphs_rejected"] == 1


def test_oversized_fold_is_skipped(monkeypatch):
    monkeypatch.setattr(graph_opt, "_FOLD_MAX_ELEMENTS", 8)
    c = sym.ones((4, 4)) + sym.ones((4, 4))  # 16 elements > cap
    opt, st = optimize_symbol(c + sym.var("x"), level=1,
                              passes=("fold", "dce"))
    assert "_sym_constant" not in _ops(opt)


# ---------------------------------------------------------------------------
# satellite: one fact cache across verify-then-optimize

def test_verify_then_optimize_infers_shapes_once(monkeypatch):
    monkeypatch.setenv("MXNET_GRAPH_VERIFY", "error")
    monkeypatch.setenv("MXNET_GRAPH_OPT", "1")
    graph_opt.reset_counters()
    x, w = sym.var("x"), sym.var("w")
    s = (x * w) + (x * w)
    ex = s.simple_bind(x=(4, 4), w=(4, 4))
    c = graph_opt.counters()
    # exactly two inference runs: ONE shared by the verifier pipeline
    # and the rewrite passes (the bind-time PassContext fact cache),
    # plus ONE for the post-pass re-verification of the optimized graph
    assert c["shape_analysis_runs"] == 2, c
    assert c["dtype_analysis_runs"] == 2, c
    assert c["fact_cache_hits"] >= 1, c
    assert c["graphs_optimized"] == 1
    assert _ops(ex._symbol) == ["broadcast_add", "broadcast_mul"]


def test_fact_cache_memoizes_within_context():
    from mxnet_tpu.analysis import PassContext

    x = sym.var("x")
    ctx = PassContext(x + x, shapes={"x": (2, 2)})
    graph_opt.reset_counters()
    first = ctx.fact("shapes")
    again = ctx.fact("shapes")
    assert first is again
    c = graph_opt.counters()
    assert c["shape_analysis_runs"] == 1
    assert c["fact_cache_hits"] == 1
    # analysis passes are typed objects over the same cache
    assert graph_opt.purity_analysis.run(ctx) == {"broadcast_add": True}
    assert ("var", "x") in graph_opt.reachability_analysis.run(ctx)


# ---------------------------------------------------------------------------
# entry point 1: Executor bind

def _dup_graph():
    data, w = sym.var("data"), sym.var("w")
    t = data.transpose((1, 0)).transpose((1, 0))
    c = sym.ones((4, 4)) + sym.ones((4, 4))
    return (t * w) + (data * w) + c


def _bind_forward_backward(monkeypatch, level):
    monkeypatch.setenv("MXNET_GRAPH_OPT", str(level))
    ex = _dup_graph().simple_bind(data=(4, 4), w=(4, 4))
    ex.arg_dict["data"]._data = nd.array(
        onp.arange(16, dtype="f").reshape(4, 4)).data
    ex.arg_dict["w"]._data = nd.array(
        onp.full((4, 4), 2.0, "f")).data
    outs = ex.forward(is_train=True)
    ex.backward()
    return (ex, outs[0].asnumpy(),
            {k: v.asnumpy() for k, v in ex.grad_dict.items()})


def test_bind_parity_and_node_reduction(monkeypatch):
    ex0, y0, g0 = _bind_forward_backward(monkeypatch, 0)
    ex2, y2, g2 = _bind_forward_backward(monkeypatch, 2)
    assert onp.array_equal(y0, y2)  # bitwise, integer-exact values
    assert set(g0) == set(g2)
    for k in g0:
        assert onp.array_equal(g0[k], g2[k]), k
    assert _nodes(ex2._symbol) < _nodes(ex0._symbol)
    assert _nodes(ex0._symbol) == _nodes(_dup_graph())


# ---------------------------------------------------------------------------
# entry point 2: SymbolBlock forward / hybridize (CachedOp)

def _paramless_block():
    x = sym.var("x")
    g = (x * x) + (x * x) + (sym.ones((1, 8)) + sym.ones((1, 8)))
    return mx.gluon.SymbolBlock(g, [sym.var("x")])


def test_symbolblock_hybridize_parity(monkeypatch):
    xval = nd.array(onp.arange(16, dtype="f").reshape(2, 8))
    monkeypatch.setenv("MXNET_GRAPH_OPT", "0")
    net0 = _paramless_block()
    with autograd.pause(train_mode=False):
        y_eager0 = net0(xval).asnumpy()
    monkeypatch.setenv("MXNET_GRAPH_OPT", "2")
    net2 = _paramless_block()
    net2.hybridize()
    with autograd.pause(train_mode=False):
        y_opt = net2(xval).asnumpy()
    assert onp.array_equal(y_eager0, y_opt)
    # the rewrite actually reached the evaluated graph
    assert _nodes(net2._optimized_outputs()) < _nodes(net2._outputs)
    assert graph_opt.counters()["graphs_optimized"] >= 1


def test_symbolblock_opt_cache_tracks_level(monkeypatch):
    net = _paramless_block()
    monkeypatch.setenv("MXNET_GRAPH_OPT", "0")
    assert net._optimized_outputs() is net._outputs
    monkeypatch.setenv("MXNET_GRAPH_OPT", "2")
    opt_a = net._optimized_outputs()
    opt_b = net._optimized_outputs()
    assert opt_a is opt_b  # cached per (level, pipeline version)
    assert opt_a is not net._outputs
    c = graph_opt.counters()["graphs_seen"]
    net._optimized_outputs()
    assert graph_opt.counters()["graphs_seen"] == c  # no re-run


# ---------------------------------------------------------------------------
# entry point 3: serving InferenceSession

def test_serving_session_parity_and_fingerprint(monkeypatch):
    from mxnet_tpu import serving

    x = onp.arange(16, dtype="f").reshape(2, 8)

    monkeypatch.setenv("MXNET_GRAPH_OPT", "0")
    sess0 = serving.InferenceSession(
        _paramless_block(), input_shapes=[(1, 8)], buckets=[1, 2],
        warm=False)
    y0 = sess0.predict(nd.array(x)).asnumpy()

    monkeypatch.setenv("MXNET_GRAPH_OPT", "2")
    sess2 = serving.InferenceSession(
        _paramless_block(), input_shapes=[(1, 8)], buckets=[1, 2],
        warm=False)
    y2 = sess2.predict(nd.array(x)).asnumpy()

    assert onp.array_equal(y0, y2)
    assert graph_opt.counters()["graphs_optimized"] >= 1

    # the compile-cache fingerprint must key on the pass-pipeline
    # version so optimized and unoptimized AOT artifacts never collide
    fp2 = sess2._fingerprint(2, 0)
    monkeypatch.setenv("MXNET_GRAPH_OPT", "0")
    fp0 = sess2._fingerprint(2, 0)
    assert fp0 is not None and fp2 is not None
    assert fp0 != fp2
    assert sess2._fingerprint(2, 0) == fp0  # deterministic
