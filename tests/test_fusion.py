"""Round-17 fusion clustering: cost-model goldens, per-pattern rewrite
goldens, bitwise parity across the eager / hybridized / serving paths,
the MXNET_FUSION kill switch and MXNET_FUSION_PATTERNS selection,
post-verify rejection falling back to the 1:1 lowering, interpret-mode
Pallas kernel parity, and the fused serving pad/slice."""
import numpy as onp
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, kernels, serving
from mxnet_tpu.analysis import graph_opt
from mxnet_tpu.analysis.graph_opt import _Graph, optimize_symbol
from mxnet_tpu.gluon import SymbolBlock
from mxnet_tpu.kernels import cost_model
from mxnet_tpu.ndarray import registry

nd = mx.nd
sym = mx.sym


@pytest.fixture(autouse=True)
def _armed(monkeypatch):
    monkeypatch.setenv("MXNET_GRAPH_OPT", "2")
    monkeypatch.delenv("MXNET_FUSION", raising=False)
    monkeypatch.delenv("MXNET_FUSION_PATTERNS", raising=False)
    monkeypatch.delenv("MXNET_FUSION_COST_MODEL", raising=False)
    kernels.reset_counters()
    graph_opt.reset_counters()
    yield
    kernels.reset_counters()
    graph_opt.reset_counters()


def _ops(s):
    return sorted(n._op for n in _Graph(s).nodes if n._op is not None)


def _chain(x=None):
    x = x if x is not None else sym.var("x")
    return sym.sqrt(sym.broadcast_add(sym.exp(x), sym.square(x)))


def _norm_act():
    d, g, b = sym.var("data"), sym.var("gamma"), sym.var("beta")
    return sym.leaky_relu(sym.layer_norm(d, g, b), act_type="gelu")


def _attention(scale_op="mul"):
    q, k, v = sym.var("q"), sym.var("k"), sym.var("v")
    s = sym.batch_dot(q, k, transpose_b=True)
    if scale_op == "mul":
        s = sym.broadcast_mul_scalar(s, scalar=0.125)
    elif scale_op == "div":
        s = sym.broadcast_div_scalar(s, scalar=8.0)
    return sym.batch_dot(sym.softmax(s), v)


def _feed(**shapes):
    rs = onp.random.RandomState(7)
    return {k: rs.randn(*v).astype("float32") for k, v in shapes.items()}


def _eval(s, feed):
    return s.eval_with({k: nd.array(v)
                        for k, v in feed.items()}).asnumpy()


# ---------------------------------------------------------------------------
# cost model goldens

def test_cost_model_goldens():
    d = cost_model.decide("elementwise", 1)
    assert (d.fuse, d.reason) == (False, "too_small")
    d = cost_model.decide("elementwise", 3)
    assert (d.fuse, d.impl) == (True, "lax")
    d = cost_model.decide("elementwise", 3, out_shape=(1 << 23,))
    assert (d.fuse, d.reason) == (False, "bandwidth_bound")
    # pallas only on TPU, only at tile-aligned shapes
    d = cost_model.decide("norm_act", 2, out_shape=(256, 512),
                          backend="tpu")
    assert (d.fuse, d.impl) == (True, "pallas")
    d = cost_model.decide("norm_act", 2, out_shape=(256, 100),
                          backend="tpu")
    assert (d.fuse, d.impl) == (True, "lax")
    d = cost_model.decide("norm_act", 2, out_shape=(256, 512),
                          backend="cpu")
    assert (d.fuse, d.impl) == (True, "lax")
    # elementwise has no TPU kernel: lax even on TPU
    d = cost_model.decide("elementwise", 4, out_shape=(256, 512),
                          backend="tpu")
    assert (d.fuse, d.impl) == (True, "lax")
    d = cost_model.decide("attention", 3, mode="never")
    assert (d.fuse, d.reason) == (False, "cost_model_never")
    d = cost_model.decide("attention", 1, mode="always")
    assert d.fuse
    # lax attention at seq>=64 is compute-bound (0.92x: CPU, toy
    # width, round 17) — reject; below the floor or on either short axis, fuse
    d = cost_model.decide("attention", 3, score_shape=(2, 64, 64))
    assert (d.fuse, d.reason) == (False, "compute_bound_attention")
    d = cost_model.decide("attention", 3, score_shape=(2, 63, 64))
    assert d.fuse
    d = cost_model.decide("attention", 3, score_shape=(2, 6, 6))
    assert d.fuse
    # the Pallas TPU kernel stays profitable at long sequence lengths
    d = cost_model.decide("attention", 3, out_shape=(256, 512),
                          backend="tpu", score_shape=(2, 128, 128))
    assert (d.fuse, d.impl) == (True, "pallas")


def test_cost_model_never_keeps_lowering(monkeypatch):
    monkeypatch.setenv("MXNET_FUSION_COST_MODEL", "never")
    out = _chain()
    opt, st = optimize_symbol(out, shapes={"x": (4, 5)}, subject="never")
    assert "_fused_elementwise" not in _ops(opt)
    assert kernels.counters()["fallback_cost_model_never"] >= 1


# ---------------------------------------------------------------------------
# per-pattern goldens + bitwise parity (lax replay)

def test_elementwise_chain_golden_and_bitwise():
    out = _chain()
    opt, st = optimize_symbol(out, shapes={"x": (4, 5)}, subject="ew")
    assert _ops(opt) == ["_fused_elementwise"]
    c = kernels.counters()
    assert c["clusters_elementwise"] == 1
    assert c["nodes_absorbed"] == 3
    assert c["impl_lax"] == 1
    feed = _feed(x=(4, 5))
    assert (_eval(out, feed) == _eval(opt, feed)).all()


def test_norm_act_golden_and_bitwise():
    out = _norm_act()
    opt, _ = optimize_symbol(
        out, shapes={"data": (8, 16), "gamma": (16,), "beta": (16,)},
        subject="na")
    assert _ops(opt) == ["_fused_norm_act"]
    assert kernels.counters()["clusters_norm_act"] == 1
    feed = _feed(data=(8, 16), gamma=(16,), beta=(16,))
    assert (_eval(out, feed) == _eval(opt, feed)).all()


@pytest.mark.parametrize("scale_op", ["mul", "div", "none"])
def test_attention_golden_and_bitwise(scale_op):
    out = _attention(scale_op)
    shapes = {k: (2, 6, 8) for k in ("q", "k", "v")}
    opt, _ = optimize_symbol(out, shapes=shapes, subject="att")
    assert _ops(opt) == ["_fused_attention"]
    assert kernels.counters()["clusters_attention"] == 1
    feed = _feed(q=(2, 6, 8), k=(2, 6, 8), v=(2, 6, 8))
    assert (_eval(out, feed) == _eval(opt, feed)).all()


def test_attention_compute_bound_seq_not_fused():
    """seq>=64 lax attention is compute-bound (CPU, toy width, round
    17: the fused replay at 0.92x): the shape-aware cost model must keep
    the 1:1 lowering and count the fallback."""
    out = _attention("mul")
    shapes = {k: (2, 64, 8) for k in ("q", "k", "v")}
    opt, _ = optimize_symbol(out, shapes=shapes, subject="att_cb")
    assert "_fused_attention" not in _ops(opt)
    c = kernels.counters()
    assert c["fallback_compute_bound_attention"] == 1
    assert c.get("clusters_attention", 0) == 0
    feed = _feed(q=(2, 64, 8), k=(2, 64, 8), v=(2, 64, 8))
    assert (_eval(out, feed) == _eval(opt, feed)).all()


def test_multi_consumer_interior_stays_external():
    # exp feeds two consumers: it must NOT be absorbed; the root
    # cluster fuses around it and reads it as an external input
    x = sym.var("x")
    e = sym.exp(x)
    out = sym.sqrt(e) + e
    opt, _ = optimize_symbol(out, shapes={"x": (4, 4)}, subject="mc")
    assert _ops(opt) == ["_fused_elementwise", "exp"]
    feed = _feed(x=(4, 4))
    assert (_eval(out, feed) == _eval(opt, feed)).all()


def test_batch_norm_act_rejected_as_effectful():
    d = sym.var("data")
    g, b = sym.var("gamma"), sym.var("beta")
    mm, mv = sym.var("moving_mean"), sym.var("moving_var")
    out = sym.activation(sym.batch_norm(d, g, b, mm, mv),
                         act_type="relu")
    opt, _ = optimize_symbol(
        out, shapes={"data": (4, 3), "gamma": (3,), "beta": (3,),
                     "moving_mean": (3,), "moving_var": (3,)},
        subject="bn")
    assert "batch_norm" in _ops(opt)
    assert "_fused_norm_act" not in _ops(opt)
    assert kernels.counters()["fallback_effectful"] >= 1


# ---------------------------------------------------------------------------
# knobs

def test_model_zoo_graph_forms_clusters_and_counts_its_fallbacks():
    """A traced model-zoo graph (resnet18_v1): the matchers form at
    least one cluster, every cost-model decision lands in a
    ``clusters_*`` or a ``fallback_*`` counter, and post-verify rejects
    nothing."""
    from mxnet_tpu.gluon.model_zoo.vision import get_model

    traced = get_model("resnet18_v1")(sym.var("data"))
    _, st = optimize_symbol(traced, shapes={"data": (1, 3, 32, 32)},
                            level=2)
    c = kernels.counters()
    clusters = sum(v for k, v in c.items() if k.startswith("clusters_"))
    fallbacks = sum(v for k, v in c.items() if k.startswith("fallback_"))
    assert clusters >= 1, c
    assert fallbacks >= 1, c  # batch_norm + act: effectful, counted
    assert not st["rejected"]
    assert st["nodes_after"] < st["nodes_before"]


def test_kill_switch_disables_all_patterns(monkeypatch):
    monkeypatch.setenv("MXNET_FUSION", "0")
    out = _chain()
    opt, _ = optimize_symbol(out, shapes={"x": (4, 5)}, subject="off")
    assert "_fused_elementwise" not in _ops(opt)
    assert kernels.counters()["pass_skipped_disabled"] >= 1
    from mxnet_tpu import runtime
    assert runtime._detect()["FUSION"] is False
    monkeypatch.setenv("MXNET_FUSION", "1")
    assert runtime._detect()["FUSION"] is True


def test_patterns_knob_selects_subset(monkeypatch):
    monkeypatch.setenv("MXNET_FUSION_PATTERNS", "norm_act")
    ew, _ = optimize_symbol(_chain(), shapes={"x": (4, 5)},
                            subject="ew-off")
    assert "_fused_elementwise" not in _ops(ew)
    na, _ = optimize_symbol(
        _norm_act(),
        shapes={"data": (8, 16), "gamma": (16,), "beta": (16,)},
        subject="na-on")
    assert "_fused_norm_act" in _ops(na)


def test_fusion_salt_tracks_knobs(monkeypatch):
    armed = graph_opt.fingerprint_salt()
    assert any("fusion" in str(part) for part in armed)
    monkeypatch.setenv("MXNET_FUSION", "0")
    assert kernels.fusion_salt() == ("fusion", 0)
    assert graph_opt.fingerprint_salt() != armed
    monkeypatch.setenv("MXNET_FUSION", "1")
    monkeypatch.setenv("MXNET_FUSION_PATTERNS", "elementwise")
    assert kernels.fusion_salt() != armed[-1]


# ---------------------------------------------------------------------------
# post-verify rejection: a bad fused kernel must not ship

def test_post_verify_rejection_serves_original(monkeypatch):
    good = registry.get_op("_fused_elementwise")

    def bad(*data, program=()):
        """Deliberately unshapeable fused body (test double)."""
        raise ValueError("broken fused kernel")

    monkeypatch.setitem(
        registry._OPS, "_fused_elementwise",
        registry.OpDef("_fused_elementwise", bad, good.differentiable,
                       bad.__doc__, good.namespaces))
    out = _chain()
    opt, st = optimize_symbol(out, shapes={"x": (4, 5)}, subject="bad")
    assert st["rejected"] is True
    assert opt is out  # the original graph is served
    c = kernels.counters()
    assert c["fallback_post_verify"] == 1
    assert graph_opt.counters()["graphs_rejected"] == 1
    feed = _feed(x=(4, 5))
    onp.testing.assert_allclose(_eval(out, feed),
                                onp.sqrt(onp.exp(feed["x"])
                                         + feed["x"] ** 2), rtol=1e-6)


# ---------------------------------------------------------------------------
# interpret-mode Pallas parity (documented-ulp, off-TPU)

def test_norm_act_interpret_matches_lax():
    rs = onp.random.RandomState(3)
    d = jnp.asarray(rs.randn(16, 32).astype("float32"))
    g = jnp.asarray(rs.randn(32).astype("float32"))
    b = jnp.asarray(rs.randn(32).astype("float32"))
    fn = registry.get_op("_fused_norm_act").fn
    kw = dict(norm_kw=(), act_op="leaky_relu",
              act_kw=(("act_type", "gelu"),))
    ref = fn(d, g, b, impl="lax", **kw)
    pal = fn(d, g, b, impl="interpret", **kw)
    assert float(jnp.abs(ref - pal).max()) < 1e-5


def test_attention_interpret_matches_lax():
    rs = onp.random.RandomState(4)
    q, k, v = (jnp.asarray(rs.randn(2, 16, 8).astype("float32"))
               for _ in range(3))
    fn = registry.get_op("_fused_attention").fn
    ref = fn(q, k, v, scale_op="mul", scale=0.125, impl="lax")
    pal = fn(q, k, v, scale_op="mul", scale=0.125, impl="interpret")
    assert float(jnp.abs(ref - pal).max()) < 1e-5


# ---------------------------------------------------------------------------
# hybridized + serving paths

def _chain_block():
    x = sym.var("data")
    blk = SymbolBlock(_chain(x), [x])
    with autograd.pause(train_mode=False):
        blk(nd.zeros((1, 8)))
    return blk


def test_symbolblock_forward_parity(monkeypatch):
    xv = onp.random.RandomState(11).randn(4, 8).astype("float32")
    monkeypatch.setenv("MXNET_FUSION", "0")
    blk = _chain_block()
    with autograd.pause(train_mode=False):
        ref = blk(nd.array(xv)).asnumpy()
    monkeypatch.setenv("MXNET_FUSION", "1")
    with autograd.pause(train_mode=False):
        fused = blk(nd.array(xv)).asnumpy()
    assert (ref == fused).all()
    # the optimized-graph cache re-keyed on the fusion salt
    assert "_fused_elementwise" in [
        n._op for n in blk._optimized_outputs()._walk()]


def test_serving_parity_and_fused_pad_slice():
    blk = _chain_block()
    xv = onp.random.RandomState(12).randn(3, 8).astype("float32")
    with autograd.pause(train_mode=False):
        ref = blk(nd.array(xv)).asnumpy()
    sess = serving.InferenceSession(blk, input_shapes=[(1, 8)],
                                    buckets=[1, 2, 4])
    out = sess.predict(nd.array(xv)).asnumpy()
    onp.testing.assert_array_equal(ref, out)
    c = kernels.counters()
    # batch 3 rides the 4-bucket: one fused pad, one fused slice
    assert c["serving_pad_fused"] >= 1
    assert c["serving_slice_fused"] >= 1


def test_serving_fused_pad_slice_off_is_bitwise_same(monkeypatch):
    blk = _chain_block()
    xv = onp.random.RandomState(13).randn(3, 8).astype("float32")
    sess = serving.InferenceSession(blk, input_shapes=[(1, 8)],
                                    buckets=[1, 2, 4])
    fused = sess.predict(nd.array(xv)).asnumpy()
    monkeypatch.setenv("MXNET_FUSION", "0")
    blk2 = _chain_block()
    sess2 = serving.InferenceSession(blk2, input_shapes=[(1, 8)],
                                     buckets=[1, 2, 4])
    plain = sess2.predict(nd.array(xv)).asnumpy()
    onp.testing.assert_array_equal(fused, plain)


# ---------------------------------------------------------------------------
# observability

def test_profiler_and_prometheus_surface():
    optimize_symbol(_chain(), shapes={"x": (4, 5)}, subject="obs")
    from mxnet_tpu import profiler
    fc = profiler.fusion_counters()
    assert fc["clusters_elementwise"] >= 1
    text = serving.prometheus_text()
    assert "mxnet_fusion_clusters_elementwise_total" in text
