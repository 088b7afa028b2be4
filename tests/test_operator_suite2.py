"""Op-spec suite, part 2: indexing, NN core, legacy ops, random
sampling — numpy oracles + gradient checks.

Reference coverage model: tests/python/unittest/test_operator.py
(test_take/test_pick/test_one_hot/test_order/test_convolution_*/
test_pooling_*/test_softmax/test_sequence_*, test_random.py).
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd
from mxnet_tpu.test_utils import (assert_almost_equal,
                                  check_numeric_gradient)

rs = onp.random.RandomState(13)


def _x(shape=(3, 4), lo=-2.0, hi=2.0):
    return (rs.rand(*shape) * (hi - lo) + lo).astype("f")


# -------------------------------------------------------------- indexing ---

def test_op_take_modes():
    x = _x((5, 3))
    idx = onp.array([0, 4, 2], "f")
    assert_almost_equal(nd.take(nd.array(x), nd.array(idx)).asnumpy(),
                        x[[0, 4, 2]], rtol=1e-6)
    big = onp.array([0, 7, -1], "f")
    out = nd.take(nd.array(x), nd.array(big), mode="clip")
    assert_almost_equal(out.asnumpy(), x[[0, 4, 0]], rtol=1e-6)
    wrap = nd.take(nd.array(x), nd.array(big), mode="wrap")
    assert_almost_equal(wrap.asnumpy(), x[[0, 2, 4]], rtol=1e-6)


def test_op_take_axis1_and_grad():
    x = _x((4, 6))
    idx = onp.array([1, 3], "f")
    out = nd.take(nd.array(x), nd.array(idx), axis=1)
    assert_almost_equal(out.asnumpy(), x[:, [1, 3]], rtol=1e-6)
    a = nd.array(x)
    a.attach_grad()
    with autograd.record():
        y = nd.sum(nd.take(a, nd.array(idx), axis=1))
    y.backward()
    expect = onp.zeros_like(x)
    expect[:, [1, 3]] = 1
    assert_almost_equal(a.grad.asnumpy(), expect, rtol=1e-6)


def test_op_pick():
    x = _x((3, 5))
    idx = onp.array([0, 2, 4], "f")
    out = nd.pick(nd.array(x), nd.array(idx), axis=1)
    assert_almost_equal(out.asnumpy(), x[onp.arange(3), [0, 2, 4]],
                        rtol=1e-6)
    outk = nd.pick(nd.array(x), nd.array(idx), axis=1, keepdims=True)
    assert outk.shape == (3, 1)


def test_op_gather_scatter_nd():
    x = _x((3, 4))
    indices = onp.array([[0, 2], [1, 3]], "f")  # 2 points (row, col)
    out = nd.gather_nd(nd.array(x), nd.array(indices))
    assert_almost_equal(out.asnumpy(), x[[0, 2], [1, 3]], rtol=1e-6)
    scat = nd.scatter_nd(out, nd.array(indices), shape=(3, 4))
    expect = onp.zeros((3, 4), "f")
    expect[0, 1] = x[0, 1]
    expect[2, 3] = x[2, 3]
    assert_almost_equal(scat.asnumpy(), expect, rtol=1e-6)


def test_op_one_hot():
    idx = onp.array([0, 2, 1], "f")
    out = nd.one_hot(nd.array(idx), depth=4)
    expect = onp.eye(4, dtype="f")[[0, 2, 1]]
    assert_almost_equal(out.asnumpy(), expect, rtol=1e-6)
    out2 = nd.one_hot(nd.array(idx), depth=4, on_value=2.0,
                      off_value=-1.0)
    assert_almost_equal(out2.asnumpy(), expect * 3 - 1, rtol=1e-6)


def test_op_topk_ret_types():
    x = _x((2, 6))
    v = nd.topk(nd.array(x), k=2, ret_typ="value")
    expect_v = -onp.sort(-x, axis=1)[:, :2]
    assert_almost_equal(v.asnumpy(), expect_v, rtol=1e-5)
    i = nd.topk(nd.array(x), k=2)
    expect_i = onp.argsort(-x, axis=1)[:, :2]
    assert_almost_equal(i.asnumpy(), expect_i.astype("f"), rtol=1e-6)
    both = nd.topk(nd.array(x), k=2, ret_typ="both")
    assert len(both) == 2
    asc = nd.topk(nd.array(x), k=1, is_ascend=True, ret_typ="value")
    assert_almost_equal(asc.asnumpy(), x.min(1, keepdims=True),
                        rtol=1e-5)


def test_op_sort_argsort():
    x = _x((3, 5))
    assert_almost_equal(nd.sort(nd.array(x), axis=1).asnumpy(),
                        onp.sort(x, 1), rtol=1e-6)
    assert_almost_equal(
        nd.sort(nd.array(x), axis=1, is_ascend=False).asnumpy(),
        -onp.sort(-x, 1), rtol=1e-6)
    assert_almost_equal(nd.argsort(nd.array(x), axis=1).asnumpy(),
                        onp.argsort(x, 1).astype("f"), rtol=1e-6)


def test_op_boolean_mask():
    x = _x((4, 3))
    m = onp.array([1, 0, 1, 0], "f")
    out = nd.contrib.boolean_mask(nd.array(x), nd.array(m))
    assert_almost_equal(out.asnumpy(), x[[0, 2]], rtol=1e-6)


def test_op_ravel_unravel():
    shape = (3, 4)
    flat = onp.array([0, 5, 11], "f")
    un = nd.unravel(nd.array(flat), shape=shape)
    expect = onp.stack(onp.unravel_index(flat.astype(int), shape))
    assert_almost_equal(un.asnumpy(), expect.astype("f"), rtol=1e-6)
    back = nd.ravel_multi_index(un, shape=shape)
    assert_almost_equal(back.asnumpy(), flat, rtol=1e-6)


def test_op_histogram():
    x = _x((50,), lo=0, hi=10)
    cnt, edges = nd.histogram(nd.array(x), bins=5, range=(0, 10))
    ec, ee = onp.histogram(x, bins=5, range=(0, 10))
    assert_almost_equal(cnt.asnumpy(), ec.astype("f"), rtol=1e-6)
    assert_almost_equal(edges.asnumpy(), ee.astype("f"), rtol=1e-5)


def test_op_index_array_copy():
    x = _x((2, 3))
    ia = nd.contrib.index_array(nd.array(x))
    assert ia.shape == (2, 3, 2)
    assert ia.asnumpy()[1, 2].tolist() == [1, 2]
    old = nd.array(_x((4, 3)))
    new = nd.array(_x((2, 3)))
    out = nd.contrib.index_copy(old, nd.array(onp.array([0, 2], "f")),
                                new)
    assert_almost_equal(out.asnumpy()[[0, 2]], new.asnumpy(), rtol=1e-6)
    assert_almost_equal(out.asnumpy()[1], old.asnumpy()[1], rtol=1e-6)


# --------------------------------------------------------------- NN core ---

def _naive_conv2d(x, w, stride, pad):
    B, C, H, W = x.shape
    F, _, kh, kw = w.shape
    sh, sw = stride
    ph, pw = pad
    xp = onp.pad(x, ((0, 0), (0, 0), (ph, ph), (pw, pw)))
    Ho = (H + 2 * ph - kh) // sh + 1
    Wo = (W + 2 * pw - kw) // sw + 1
    out = onp.zeros((B, F, Ho, Wo), "f")
    for b in range(B):
        for f in range(F):
            for i in range(Ho):
                for j in range(Wo):
                    patch = xp[b, :, i * sh:i * sh + kh,
                               j * sw:j * sw + kw]
                    out[b, f, i, j] = (patch * w[f]).sum()
    return out


def test_op_convolution_vs_naive():
    x = _x((2, 3, 7, 7))
    w = _x((4, 3, 3, 3))
    out = nd.convolution(nd.array(x), nd.array(w), kernel=(3, 3),
                         stride=(2, 2), pad=(1, 1), num_filter=4,
                         no_bias=True)
    assert_almost_equal(out.asnumpy(),
                        _naive_conv2d(x, w, (2, 2), (1, 1)),
                        rtol=1e-3, atol=1e-4)


def test_op_convolution_groups_and_bias():
    x = _x((1, 4, 5, 5))
    w = _x((4, 2, 3, 3))
    b = _x((4,))
    out = nd.convolution(nd.array(x), nd.array(w), nd.array(b),
                         kernel=(3, 3), pad=(1, 1), num_filter=4,
                         num_group=2)
    # group conv == two independent half convs
    o1 = _naive_conv2d(x[:, :2], w[:2], (1, 1), (1, 1))
    o2 = _naive_conv2d(x[:, 2:], w[2:], (1, 1), (1, 1))
    expect = onp.concatenate([o1, o2], 1) + b.reshape(1, -1, 1, 1)
    assert_almost_equal(out.asnumpy(), expect, rtol=1e-3, atol=1e-4)


def test_op_convolution_gradients():
    x = _x((1, 2, 5, 5))
    w = _x((2, 2, 3, 3))
    check_numeric_gradient(
        lambda a, b: nd.convolution(a, b, kernel=(3, 3), pad=(1, 1),
                                    num_filter=2, no_bias=True),
        [x, w], rtol=3e-2, atol=1e-3)


def test_op_deconvolution_shape_inverse():
    x = _x((1, 3, 4, 4))
    w = _x((3, 5, 3, 3))
    out = nd.deconvolution(nd.array(x), nd.array(w), kernel=(3, 3),
                           stride=(2, 2), pad=(1, 1), num_filter=5)
    assert out.shape == (1, 5, 7, 7)


def test_op_pooling_max_avg():
    x = _x((1, 2, 4, 4))
    mx_out = nd.pooling(nd.array(x), kernel=(2, 2), stride=(2, 2),
                        pool_type="max")
    expect = x.reshape(1, 2, 2, 2, 2, 2).max(axis=(3, 5))
    assert_almost_equal(mx_out.asnumpy(), expect, rtol=1e-5)
    avg = nd.pooling(nd.array(x), kernel=(2, 2), stride=(2, 2),
                     pool_type="avg")
    expecta = x.reshape(1, 2, 2, 2, 2, 2).mean(axis=(3, 5))
    assert_almost_equal(avg.asnumpy(), expecta, rtol=1e-5)


def test_op_pooling_global_and_full_convention():
    x = _x((2, 3, 5, 5))
    g = nd.pooling(nd.array(x), pool_type="avg", global_pool=True)
    assert_almost_equal(g.asnumpy().reshape(2, 3),
                        x.mean(axis=(2, 3)), rtol=1e-5)
    full = nd.pooling(nd.array(x), kernel=(2, 2), stride=(2, 2),
                      pool_type="max", pooling_convention="full")
    assert full.shape == (2, 3, 3, 3)


def test_op_avg_pool_count_include_pad():
    x = onp.ones((1, 1, 2, 2), "f")
    incl = nd.pooling(nd.array(x), kernel=(2, 2), stride=(2, 2),
                      pad=(1, 1), pool_type="avg",
                      count_include_pad=True)
    excl = nd.pooling(nd.array(x), kernel=(2, 2), stride=(2, 2),
                      pad=(1, 1), pool_type="avg",
                      count_include_pad=False)
    assert incl.asnumpy()[0, 0, 0, 0] == pytest.approx(0.25)
    assert excl.asnumpy()[0, 0, 0, 0] == pytest.approx(1.0)


def test_op_fully_connected_flatten():
    x = _x((2, 3, 4))
    w = _x((5, 12))
    b = _x((5,))
    out = nd.fully_connected(nd.array(x), nd.array(w), nd.array(b),
                             num_hidden=5)
    expect = x.reshape(2, 12) @ w.T + b
    assert_almost_equal(out.asnumpy(), expect, rtol=1e-4)
    nf = nd.fully_connected(nd.array(x), nd.array(_x((5, 4))),
                            nd.array(b), num_hidden=5, flatten=False)
    assert nf.shape == (2, 3, 5)


def test_op_softmax_properties():
    x = _x((3, 5))
    out = nd.softmax(nd.array(x), axis=1)
    e = onp.exp(x - x.max(1, keepdims=True))
    assert_almost_equal(out.asnumpy(), e / e.sum(1, keepdims=True),
                        rtol=1e-5)
    ls = nd.log_softmax(nd.array(x), axis=1)
    assert_almost_equal(ls.asnumpy(), onp.log(e / e.sum(1,
                                                        keepdims=True)),
                        rtol=1e-4, atol=1e-5)
    sm = nd.softmin(nd.array(x), axis=1)
    en = onp.exp(-(x - x.min(1, keepdims=True)))
    assert_almost_equal(sm.asnumpy(), en / en.sum(1, keepdims=True),
                        rtol=1e-4)


def test_op_softmax_gradient():
    x = _x((2, 4))
    w = nd.array(_x((2, 4)))  # fixed weights — the fn must be pure
    check_numeric_gradient(
        lambda a: nd.sum(nd.softmax(a, axis=1) * w),
        [x], rtol=3e-2, atol=1e-3)


def test_op_dropout_train_inference():
    x = onp.ones((200, 10), "f")
    with autograd.record(train_mode=True):
        out = nd.dropout(nd.array(x), p=0.5)
    kept = out.asnumpy()
    frac = (kept > 0).mean()
    assert 0.35 < frac < 0.65
    assert_almost_equal(kept[kept > 0], onp.full((kept > 0).sum(), 2.0),
                        rtol=1e-5)  # inverted scaling
    out_inf = nd.dropout(nd.array(x), p=0.5)
    assert_almost_equal(out_inf.asnumpy(), x, rtol=1e-6)


def test_op_embedding_and_grad():
    w = _x((10, 4))
    idx = onp.array([1, 3, 1], "f")
    out = nd.embedding(nd.array(idx), nd.array(w), input_dim=10,
                       output_dim=4)
    assert_almost_equal(out.asnumpy(), w[[1, 3, 1]], rtol=1e-6)
    wv = nd.array(w)
    wv.attach_grad()
    with autograd.record():
        y = nd.sum(nd.embedding(nd.array(idx), wv, input_dim=10,
                                output_dim=4))
    y.backward()
    expect = onp.zeros_like(w)
    expect[1] = 2  # index 1 used twice
    expect[3] = 1
    assert_almost_equal(wv.grad.asnumpy(), expect, rtol=1e-6)


def test_op_layer_norm_vs_numpy():
    x = _x((4, 6))
    g, b = _x((6,)), _x((6,))
    out = nd.layer_norm(nd.array(x), nd.array(g), nd.array(b), axis=-1,
                        eps=1e-5)
    mu = x.mean(1, keepdims=True)
    var = x.var(1, keepdims=True)
    expect = (x - mu) / onp.sqrt(var + 1e-5) * g + b
    assert_almost_equal(out.asnumpy(), expect, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype,rtol,atol", [
    ("float32", 1e-4, 1e-5),    # this file's layer_norm tolerance
    ("bfloat16", 6e-2, 2e-2),   # test_op_dtype_sweep.py's bfloat16 rung
])
def test_op_layer_norm_value_and_grad_vs_plain_formula(dtype, rtol, atol):
    """The op hands its output on as a stored residual under
    differentiation (``ops_nn.stored_residual``); value and every
    gradient are those of ``jax.grad`` of the formula written out."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.ndarray.registry import get_op

    op = get_op("layer_norm").fn

    def plain(x, g, b):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mean) * lax.rsqrt(var + 1e-5) * g + b

    x, g, b = (jnp.asarray(a, dtype) for a in (_x((4, 6)), _x((6,)),
                                                _x((6,))))
    w = jnp.asarray(_x((4, 6)), dtype)    # a cotangent that is not all ones

    def loss(fn):
        return lambda *a: jnp.sum((fn(*a) * w).astype(jnp.float32))

    got, got_grads = jax.value_and_grad(loss(op), (0, 1, 2))(x, g, b)
    ref, ref_grads = jax.value_and_grad(loss(plain), (0, 1, 2))(x, g, b)
    assert_almost_equal(onp.asarray(op(x, g, b), "f"),
                        onp.asarray(plain(x, g, b), "f"), rtol=rtol, atol=atol)
    assert_almost_equal(float(got), float(ref), rtol=rtol, atol=atol)
    for a, r in zip(got_grads, ref_grads):
        assert a.dtype == r.dtype
        r = onp.asarray(r, "f")
        assert_almost_equal(onp.asarray(a, "f"), r, rtol=rtol,
                            atol=atol * max(1.0, onp.abs(r).max()))


def test_op_layer_norm_counts_stored_outputs_only_when_differentiated():
    """``kernels.counters()["norm_out_stored"]``: nothing for a forward
    call, eager or jitted; one for each norm output inside one
    differentiated trace."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import kernels
    from mxnet_tpu.ndarray.registry import get_op

    op = get_op("layer_norm").fn
    x, g, b = jnp.asarray(_x((4, 6))), jnp.asarray(_x((6,))), \
        jnp.asarray(_x((6,)))
    before = kernels.counters().get("norm_out_stored", 0)
    nd.layer_norm(nd.array(_x((4, 6))), nd.array(_x((6,))),
                  nd.array(_x((6,)))).asnumpy()
    jax.jit(op)(x, g, b).block_until_ready()
    assert kernels.counters().get("norm_out_stored", 0) == before
    jax.grad(lambda *a: op(*a).sum())(x, g, b)
    assert kernels.counters()["norm_out_stored"] == before + 1
    xv = nd.array(_x((4, 6)))
    xv.attach_grad()
    with autograd.record():
        y = nd.sum(nd.layer_norm(xv, nd.array(_x((6,))), nd.array(_x((6,)))))
    y.backward()
    assert xv.grad.shape == (4, 6)
    assert kernels.counters()["norm_out_stored"] == before + 2


def test_op_instance_group_norm():
    x = _x((2, 4, 3, 3))
    g, b = _x((4,)), _x((4,))
    out = nd.instance_norm(nd.array(x), nd.array(g), nd.array(b),
                           eps=1e-5)
    mu = x.mean(axis=(2, 3), keepdims=True)
    var = x.var(axis=(2, 3), keepdims=True)
    expect = (x - mu) / onp.sqrt(var + 1e-5) * g.reshape(1, -1, 1, 1) \
        + b.reshape(1, -1, 1, 1)
    assert_almost_equal(out.asnumpy(), expect, rtol=1e-3, atol=1e-4)
    # group_norm: per-GROUP gamma/beta (reference group_norm-inl.h:163)
    gg, gb = _x((2,)), _x((2,))
    gn = nd.group_norm(nd.array(x), nd.array(gg), nd.array(gb),
                       num_groups=2)
    xg = x.reshape(2, 2, 2, 3, 3)
    mu = xg.mean(axis=(2, 3, 4), keepdims=True)
    var = xg.var(axis=(2, 3, 4), keepdims=True)
    expect_g = ((xg - mu) / onp.sqrt(var + 1e-5)
                * gg.reshape(1, 2, 1, 1, 1)
                + gb.reshape(1, 2, 1, 1, 1)).reshape(x.shape)
    assert_almost_equal(gn.asnumpy(), expect_g, rtol=1e-3, atol=1e-4)


def test_op_batch_norm_inference_stats():
    x = _x((3, 4, 2, 2))
    mean = _x((4,))
    var = onp.abs(_x((4,))) + 0.5
    out = nd.batch_norm(nd.array(x), nd.ones(4), nd.zeros(4),
                        nd.array(mean), nd.array(var),
                        use_global_stats=True, use_batch_stats=False,
                        eps=1e-3, fix_gamma=False)
    expect = (x - mean.reshape(1, -1, 1, 1)) / onp.sqrt(
        var.reshape(1, -1, 1, 1) + 1e-3)
    assert_almost_equal(out.asnumpy(), expect, rtol=1e-3, atol=1e-4)


def test_op_lrn():
    x = _x((1, 6, 3, 3), lo=0.1, hi=1.0)
    out = nd.lrn(nd.array(x), nsize=3, alpha=1e-3, beta=0.75, knorm=2.0)
    # oracle: across-channel normalization
    sq = onp.zeros_like(x)
    for c in range(6):
        lo, hi = max(0, c - 1), min(6, c + 2)
        sq[:, c] = (x[:, lo:hi] ** 2).sum(1)
    expect = x / (2.0 + 1e-3 / 3 * sq) ** 0.75
    assert_almost_equal(out.asnumpy(), expect, rtol=1e-3, atol=1e-4)


def test_op_l2_normalization():
    x = _x((2, 3, 4))
    out = nd.l2_normalization(nd.array(x), mode="instance")
    norm = onp.sqrt((x.reshape(2, -1) ** 2).sum(1) + 1e-10)
    assert_almost_equal(out.asnumpy(),
                        x / norm.reshape(2, 1, 1), rtol=1e-4)
    ch = nd.l2_normalization(nd.array(x), mode="channel")
    nc = onp.sqrt((x ** 2).sum(1, keepdims=True) + 1e-10)
    assert_almost_equal(ch.asnumpy(), x / nc, rtol=1e-4)


def test_op_sequence_family():
    x = _x((4, 2, 3))  # (T, N, C)
    lens = onp.array([2, 3], "f")
    m = nd.sequence_mask(nd.array(x), nd.array(lens),
                         use_sequence_length=True, value=-1.0)
    mn = m.asnumpy()
    assert (mn[2:, 0] == -1).all() and (mn[3:, 1] == -1).all()
    assert_almost_equal(mn[:2, 0], x[:2, 0], rtol=1e-6)
    last = nd.sequence_last(nd.array(x), nd.array(lens),
                            use_sequence_length=True)
    assert_almost_equal(last.asnumpy(),
                        onp.stack([x[1, 0], x[2, 1]]), rtol=1e-6)
    rev = nd.sequence_reverse(nd.array(x), nd.array(lens),
                              use_sequence_length=True)
    assert_almost_equal(rev.asnumpy()[0, 0], x[1, 0], rtol=1e-6)
    assert_almost_equal(rev.asnumpy()[0, 1], x[2, 1], rtol=1e-6)


def test_op_leaky_relu_variants():
    x = _x()
    leaky = nd.leaky_relu(nd.array(x), act_type="leaky", slope=0.1)
    assert_almost_equal(leaky.asnumpy(),
                        onp.where(x > 0, x, 0.1 * x), rtol=1e-5)
    elu = nd.leaky_relu(nd.array(x), act_type="elu", slope=1.0)
    assert_almost_equal(elu.asnumpy(),
                        onp.where(x > 0, x, onp.expm1(x)), rtol=1e-4,
                        atol=1e-5)
    g = _x((x.shape[-1],), lo=0.1, hi=0.3)
    pr = nd.leaky_relu(nd.array(x), nd.array(g), act_type="prelu")
    assert_almost_equal(pr.asnumpy(), onp.where(x > 0, x, g * x),
                        rtol=1e-5)


def test_op_upsampling_nearest():
    x = _x((1, 2, 3, 3))
    out = nd.upsampling(nd.array(x), scale=2, sample_type="nearest")
    assert out.shape == (1, 2, 6, 6)
    assert_almost_equal(out.asnumpy()[0, 0, ::2, ::2], x[0, 0],
                        rtol=1e-6)


def test_op_softmax_cross_entropy():
    x = _x((3, 5))
    lab = onp.array([0, 2, 4], "f")
    out = nd.softmax_cross_entropy(nd.array(x), nd.array(lab))
    e = onp.exp(x - x.max(1, keepdims=True))
    p = e / e.sum(1, keepdims=True)
    expect = -onp.log(p[onp.arange(3), lab.astype(int)]).sum()
    assert_almost_equal(out.asnumpy().reshape(()), expect, rtol=1e-4)


# ------------------------------------------------------------ legacy ops ---

def test_op_smooth_l1_piecewise():
    x = onp.array([-2.0, -0.3, 0.0, 0.3, 2.0], "f")
    out = nd.smooth_l1(nd.array(x), scalar=1.0)
    expect = onp.where(onp.abs(x) < 1, 0.5 * x * x, onp.abs(x) - 0.5)
    assert_almost_equal(out.asnumpy(), expect, rtol=1e-5)


def test_op_moments():
    x = _x((3, 4))
    mean, var = nd.moments(nd.array(x), axes=(1,))
    assert_almost_equal(mean.asnumpy(), x.mean(1), rtol=1e-5)
    assert_almost_equal(var.asnumpy(), x.var(1), rtol=1e-4)


def test_op_regression_outputs_backward():
    x = _x((4, 3))
    lab = _x((4, 3))
    a = nd.array(x)
    a.attach_grad()
    with autograd.record():
        out = nd.linear_regression_output(a, nd.array(lab))
    out.backward()
    # forward is identity; backward is (pred - label) * grad_scale /
    # num_output with num_output = per-sample feature count (reference
    # regression_output-inl.h:201)
    assert_almost_equal(out.asnumpy(), x, rtol=1e-6)
    assert_almost_equal(a.grad.asnumpy(), (x - lab) / 3, rtol=1e-4)


def test_op_roi_pooling():
    x = onp.arange(16, dtype="f").reshape(1, 1, 4, 4)
    rois = onp.array([[0, 0, 0, 3, 3]], "f")
    out = nd.roi_pooling(nd.array(x), nd.array(rois),
                         pooled_size=(2, 2), spatial_scale=1.0)
    assert_almost_equal(out.asnumpy().reshape(2, 2),
                        [[5, 7], [13, 15]], rtol=1e-5)


def test_op_grid_generator_bilinear_sampler_identity():
    x = _x((1, 2, 4, 4))
    # identity affine transform
    theta = onp.array([[1, 0, 0, 0, 1, 0]], "f")
    grid = nd.grid_generator(nd.array(theta), transform_type="affine",
                             target_shape=(4, 4))
    out = nd.bilinear_sampler(nd.array(x), grid)
    assert_almost_equal(out.asnumpy(), x, rtol=1e-4, atol=1e-4)


def test_op_spatial_transformer_identity():
    x = _x((1, 2, 4, 4))
    theta = onp.array([[1, 0, 0, 0, 1, 0]], "f")
    out = nd.spatial_transformer(nd.array(x), nd.array(theta),
                                 target_shape=(4, 4),
                                 transform_type="affine",
                                 sampler_type="bilinear")
    assert_almost_equal(out.asnumpy(), x, rtol=1e-4, atol=1e-4)


def test_op_correlation_self():
    x = _x((1, 2, 5, 5))
    out = nd.correlation(nd.array(x), nd.array(x), kernel_size=1,
                         max_displacement=0, stride1=1, stride2=1)
    expect = (x * x).mean(1, keepdims=True)
    assert_almost_equal(out.asnumpy(), expect, rtol=1e-4)


def test_op_crop():
    x = _x((1, 2, 6, 6))
    out = nd.crop(nd.array(x), offset=(1, 2), h_w=(3, 3))
    assert_almost_equal(out.asnumpy(), x[:, :, 1:4, 2:5], rtol=1e-6)


def test_op_make_loss_identity_grad():
    x = _x((3,))
    a = nd.array(x)
    a.attach_grad()
    with autograd.record():
        y = nd.make_loss(a * 2)
    y.backward()
    assert_almost_equal(a.grad.asnumpy(), onp.full(3, 2.0), rtol=1e-5)


# ---------------------------------------------------------------- random ---

def test_op_random_uniform_range():
    mx.random.seed(0)
    x = nd.random.uniform(low=2.0, high=5.0, shape=(2000,))
    v = x.asnumpy()
    assert v.min() >= 2.0 and v.max() <= 5.0
    assert abs(v.mean() - 3.5) < 0.1


def test_op_random_normal_moments():
    mx.random.seed(0)
    x = nd.random.normal(loc=1.0, scale=2.0, shape=(4000,))
    v = x.asnumpy()
    assert abs(v.mean() - 1.0) < 0.15
    assert abs(v.std() - 2.0) < 0.15


def test_op_random_poisson_gamma_exponential():
    mx.random.seed(0)
    p = nd.random.poisson(lam=4.0, shape=(3000,)).asnumpy()
    assert abs(p.mean() - 4.0) < 0.25
    g = nd.random.gamma(alpha=2.0, beta=3.0, shape=(3000,)).asnumpy()
    assert abs(g.mean() - 6.0) < 0.5
    e = nd.random.exponential(scale=2.0, shape=(3000,)).asnumpy()
    assert abs(e.mean() - 2.0) < 0.25


def test_op_random_randint_multinomial():
    mx.random.seed(0)
    r = nd.random.randint(low=0, high=5, shape=(2000,)).asnumpy()
    assert r.min() >= 0 and r.max() <= 4
    probs = nd.array(onp.array([[0.0, 0.0, 1.0]], "f"))
    m = nd.sample_multinomial(probs, shape=(10,))
    assert (m.asnumpy() == 2).all()


def test_op_random_seed_reproducible():
    mx.random.seed(123)
    a = nd.random.uniform(shape=(5,)).asnumpy()
    mx.random.seed(123)
    b = nd.random.uniform(shape=(5,)).asnumpy()
    assert_almost_equal(a, b, rtol=1e-7)
    c = nd.random.uniform(shape=(5,)).asnumpy()
    assert not onp.allclose(a, c)


def test_op_shuffle_is_permutation():
    x = onp.arange(20, dtype="f")
    out = nd.shuffle(nd.array(x)).asnumpy()
    assert sorted(out.tolist()) == x.tolist()


def test_op_gather_nd_grad_scatters():
    data = nd.array(onp.arange(12, dtype="f").reshape(3, 4))
    data.attach_grad()
    idx = nd.array(onp.array([[0, 2], [1, 3]], "f"))  # rows, cols pairs
    with autograd.record():
        out = nd.gather_nd(data, idx)
        loss = nd.sum(out * nd.array([2.0, 3.0]))
    loss.backward()
    g = data.grad.asnumpy()
    expect = onp.zeros((3, 4), "f")
    expect[0, 1] = 2.0
    expect[2, 3] = 3.0
    onp.testing.assert_allclose(g, expect)


def test_op_take_along_axis_grad():
    data = nd.array(onp.arange(6, dtype="f").reshape(2, 3))
    data.attach_grad()
    idx = nd.array(onp.array([[2], [0]], "f"))
    with autograd.record():
        out = nd.take_along_axis(data, idx, axis=1)
        loss = nd.sum(out)
    loss.backward()
    expect = onp.zeros((2, 3), "f")
    expect[0, 2] = 1.0
    expect[1, 0] = 1.0
    onp.testing.assert_allclose(data.grad.asnumpy(), expect)


def test_op_topk_value_grad_routes_to_argmax_slots():
    data = nd.array(onp.array([[1.0, 5.0, 3.0], [4.0, 2.0, 6.0]], "f"))
    data.attach_grad()
    with autograd.record():
        vals = nd.topk(data, k=1, ret_typ="value")
        loss = nd.sum(vals)
    loss.backward()
    expect = onp.array([[0, 1, 0], [0, 0, 1]], "f")
    onp.testing.assert_allclose(data.grad.asnumpy(), expect)
