"""Gluon blocks / training (reference suite:
tests/python/unittest/test_gluon.py)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, gluon
from mxnet_tpu.gluon import nn


def test_dense_forward():
    layer = nn.Dense(4, in_units=3)
    layer.initialize()
    x = nd.ones((2, 3))
    out = layer(x)
    assert out.shape == (2, 4)


def test_dense_deferred_init():
    layer = nn.Dense(4)
    layer.initialize()
    out = layer(nd.ones((2, 7)))
    assert out.shape == (2, 4)
    assert layer.weight.shape == (4, 7)


def test_sequential_mlp():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(8), nn.Dense(2))
    net.initialize()
    out = net(nd.ones((5, 10)))
    assert out.shape == (5, 2)


def test_collect_params_names():
    net = nn.HybridSequential(prefix="net_")
    with net.name_scope():
        net.add(nn.Dense(4, in_units=2))
    params = net.collect_params()
    names = list(params.keys())
    assert any("weight" in n for n in names)
    assert any("bias" in n for n in names)
    assert all(n.startswith("net_") for n in names)


def test_param_save_load(tmp_path):
    net = nn.HybridSequential()
    net.add(nn.Dense(3, in_units=2))
    net.initialize()
    f = str(tmp_path / "p.params")
    net.save_parameters(f)
    net2 = nn.HybridSequential()
    net2.add(nn.Dense(3, in_units=2))
    net2.load_parameters(f)
    x = nd.ones((1, 2))
    onp.testing.assert_allclose(net(x).asnumpy(), net2(x).asnumpy(),
                                rtol=1e-6)


def test_conv2d():
    layer = nn.Conv2D(8, kernel_size=3, padding=1)
    layer.initialize()
    out = layer(nd.ones((2, 3, 16, 16)))
    assert out.shape == (2, 8, 16, 16)
    assert layer.weight.shape == (8, 3, 3, 3)


def test_conv_stride_groups():
    layer = nn.Conv2D(8, kernel_size=3, strides=2, padding=1, groups=2,
                      in_channels=4)
    layer.initialize()
    out = layer(nd.ones((1, 4, 8, 8)))
    assert out.shape == (1, 8, 4, 4)


def test_conv2d_transpose():
    layer = nn.Conv2DTranspose(4, kernel_size=2, strides=2)
    layer.initialize()
    out = layer(nd.ones((1, 3, 8, 8)))
    assert out.shape == (1, 4, 16, 16)


def test_pooling_layers():
    x = nd.ones((1, 2, 8, 8))
    assert nn.MaxPool2D(2)(x).shape == (1, 2, 4, 4)
    assert nn.AvgPool2D(2)(x).shape == (1, 2, 4, 4)
    assert nn.GlobalAvgPool2D()(x).shape == (1, 2, 1, 1)
    assert nn.GlobalMaxPool2D()(x).shape == (1, 2, 1, 1)


def test_batchnorm_train_updates_stats():
    layer = nn.BatchNorm(in_channels=3)
    layer.initialize()
    x = nd.array(onp.random.rand(4, 3, 2, 2).astype("f") * 10)
    with autograd.record():
        layer(x)
    rm = layer.running_mean.data().asnumpy()
    assert (onp.abs(rm) > 0).any()  # moved off init
    # inference path uses running stats
    out = layer(nd.zeros((2, 3, 2, 2)))
    assert out.shape == (2, 3, 2, 2)


def test_layernorm():
    layer = nn.LayerNorm(in_channels=5)
    layer.initialize()
    out = layer(nd.array(onp.random.rand(2, 5).astype("f")))
    onp.testing.assert_allclose(out.asnumpy().mean(axis=-1), [0, 0],
                                atol=1e-5)


def _plain_norm(kind, x, gamma, beta, eps):
    """LayerNorm / RMSNorm written out: the statistics of RMSNorm in
    float32, those of LayerNorm in the input's type, as the blocks keep
    them."""
    import jax.numpy as jnp
    from jax import lax

    if kind == "rms":
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        return (xf * lax.rsqrt(ms + eps)
                * gamma.astype(jnp.float32)).astype(x.dtype)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * gamma + beta


@pytest.mark.parametrize("kind", ["layer", "rms"])
@pytest.mark.parametrize("dtype,rtol,atol", [
    ("float32", 1e-5, 1e-5), ("bfloat16", 6e-2, 2e-2)])
def test_norm_blocks_value_and_grad_vs_plain_formula(kind, dtype, rtol,
                                                     atol):
    """``nn.LayerNorm`` and ``nn.RMSNorm`` under ``autograd.record``:
    the output and the gradients of input and gamma against ``jax.grad``
    of the formula written out. ``LayerNorm``'s output is a stored
    residual under differentiation (``ops_nn.stored_residual``): counted
    once for the recorded call, not at all for the forward-only one.
    ``RMSNorm`` does not call the helper (the one cell that trains with
    it lost 0.3% by it on the chip, PERF.md section 6, PR 31) and counts
    nothing."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import kernels

    rs = onp.random.RandomState(7)
    eps = 1e-5 if kind == "layer" else 1e-6
    layer = (nn.LayerNorm(in_channels=6, epsilon=eps) if kind == "layer"
             else nn.RMSNorm(epsilon=eps, in_channels=6))
    layer.initialize()
    layer.cast(dtype)
    gamma = (rs.rand(6) + 0.5).astype("f")
    layer.gamma.set_data(nd.array(gamma, dtype=dtype))
    x = nd.array((rs.rand(3, 4, 6) * 4 - 2).astype("f"), dtype=dtype)
    w = jnp.asarray((rs.rand(3, 4, 6) * 2 - 1).astype("f"), dtype)

    before = kernels.counters().get("norm_out_stored", 0)
    out = layer(x)
    assert kernels.counters().get("norm_out_stored", 0) == before
    x.attach_grad()
    with autograd.record():
        y = nd.sum((layer(x) * nd.array(w, dtype=dtype)).astype("float32"))
    y.backward()
    assert kernels.counters().get("norm_out_stored", 0) == \
        before + (kind == "layer")

    xj, gj = jnp.asarray(x.asnumpy(), dtype), jnp.asarray(gamma, dtype)
    bj = jnp.zeros((6,), dtype)
    ref = _plain_norm(kind, xj, gj, bj, eps)
    dx, dg = jax.grad(lambda a, g: jnp.sum(
        (_plain_norm(kind, a, g, bj, eps) * w).astype(jnp.float32)),
        (0, 1))(xj, gj)
    for got, want in ((out, ref), (x.grad, dx), (layer.gamma.grad(), dg)):
        want = onp.asarray(want, "f")
        onp.testing.assert_allclose(
            got.asnumpy().astype("f"), want, rtol=rtol,
            atol=atol * max(1.0, onp.abs(want).max()))


def test_embedding():
    layer = nn.Embedding(10, 4)
    layer.initialize()
    out = layer(nd.array([1, 2, 5], dtype="int32"))
    assert out.shape == (3, 4)


def test_dropout_layer():
    layer = nn.Dropout(0.5)
    x = nd.ones((10, 10))
    assert (layer(x).asnumpy() == 1).all()  # not training
    with autograd.record():
        y = layer(x)
    assert (y.asnumpy() == 0).any()


def test_activations():
    x = nd.array([-1.0, 0.0, 1.0])
    assert (nn.LeakyReLU(0.1)(x).asnumpy()[0] + 0.1) < 1e-6
    assert nn.ELU()(x).shape == (3,)
    assert nn.SELU()(x).shape == (3,)
    assert nn.Swish()(x).shape == (3,)
    assert nn.GELU()(x).shape == (3,)
    prelu = nn.PReLU()
    prelu.initialize()
    assert prelu(x).shape == (3,)


def test_hybridize_matches_eager():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    x = nd.array(onp.random.rand(3, 8).astype("f"))
    eager = net(x).asnumpy()
    net.hybridize()
    compiled = net(x).asnumpy()
    onp.testing.assert_allclose(eager, compiled, rtol=1e-5, atol=1e-6)
    # second call hits the jit cache
    onp.testing.assert_allclose(net(x).asnumpy(), eager, rtol=1e-5,
                                atol=1e-6)


def test_hybridize_grad_matches_eager():
    def run(hybrid):
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="relu", in_units=4), nn.Dense(2,
                                                                     in_units=8))
        net.initialize(mx.init.Xavier())
        if hybrid:
            net.hybridize()
        x = nd.array(onp.arange(8).reshape(2, 4).astype("f"))
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        return {k: p.grad().asnumpy()
                for k, p in net._collect_params_with_prefix().items()}

    g1, g2 = run(False), run(True)
    assert g1.keys() == g2.keys()
    for k in g1:
        onp.testing.assert_allclose(g1[k], g2[k], rtol=1e-4, atol=1e-5)


def test_hybridized_batchnorm_updates_stats():
    layer = nn.BatchNorm(in_channels=3)
    layer.initialize()
    layer.hybridize()
    x = nd.array(onp.random.rand(4, 3, 2, 2).astype("f") * 5 + 3)
    with autograd.record():
        layer(x)
    rm = layer.running_mean.data().asnumpy()
    assert (onp.abs(rm) > 0.01).any()


def test_trainer_sgd_step():
    net = nn.Dense(1, in_units=2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = nd.array([[1.0, 2.0]])
    w_before = net.weight.data().asnumpy().copy()
    with autograd.record():
        loss = (net(x)).sum()
    loss.backward()
    trainer.step(1)
    w_after = net.weight.data().asnumpy()
    onp.testing.assert_allclose(w_after, w_before - 0.1 * x.asnumpy(),
                                rtol=1e-5)


def test_training_reduces_loss():
    mx.random.seed(42)
    onp.random.seed(42)
    w_true = onp.array([[2.0], [-3.0]], dtype="f")
    X = onp.random.rand(64, 2).astype("f")
    y = X @ w_true + 0.5

    net = nn.Dense(1, in_units=2)
    net.initialize()
    l2 = gluon.loss.L2Loss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.5})
    first = None
    for _ in range(50):
        with autograd.record():
            loss = l2(net(nd.array(X)), nd.array(y))
            total = loss.mean()
        total.backward()
        trainer.step(X.shape[0] / 64.0)
        if first is None:
            first = total.asscalar()
    assert total.asscalar() < first * 0.1


def test_losses():
    pred = nd.array(onp.random.rand(4, 5).astype("f"))
    label = nd.array([1, 2, 3, 0])
    l = gluon.loss.SoftmaxCrossEntropyLoss()(pred, label)
    assert l.shape == (4,)
    dense_label = nd.one_hot(label, 5)
    l2 = gluon.loss.SoftmaxCrossEntropyLoss(sparse_label=False)(pred,
                                                                dense_label)
    onp.testing.assert_allclose(l.asnumpy(), l2.asnumpy(), rtol=1e-5)
    assert gluon.loss.L1Loss()(pred, nd.zeros((4, 5))).shape == (4,)
    assert gluon.loss.L2Loss()(pred, nd.zeros((4, 5))).shape == (4,)
    assert gluon.loss.SigmoidBCELoss()(pred, nd.zeros((4, 5))).shape == (4,)
    assert gluon.loss.HuberLoss()(pred, nd.zeros((4, 5))).shape == (4,)
    assert gluon.loss.HingeLoss()(pred, nd.ones((4, 5))).shape == (4,)
    assert gluon.loss.KLDivLoss(from_logits=False)(
        pred, nd.softmax(pred)).shape == (4,)


def test_block_repr_and_name():
    d = nn.Dense(2)
    assert d.prefix.startswith("dense")
    assert "Dense" in repr(d)


def test_cast():
    net = nn.Dense(2, in_units=2)
    net.initialize()
    net.cast("bfloat16")
    out = net(nd.ones((1, 2)).astype("bfloat16"))
    assert "bfloat16" in str(out.data.dtype)


def test_ctc_loss_has_gradient():
    pred = nd.array(onp.random.rand(8, 2, 5).astype("f"))  # (T, N, C)
    pred.attach_grad()
    label = nd.array([[1, 2, 3], [2, 3, 4]])
    ctc = gluon.loss.CTCLoss(layout="TNC")
    with autograd.record():
        loss = ctc(pred, label)
    assert loss.shape == (2,)
    loss.backward()
    assert (onp.abs(pred.grad.asnumpy()) > 0).any(), "CTC grad must flow"


def test_inplace_raises_under_record():
    x = nd.array([1.0, 2.0])
    x.attach_grad()
    with autograd.record():
        y = x * 2
        try:
            y += x
            raised = False
        except mx.MXNetError:
            raised = True
    assert raised


def test_out_kwarg_keeps_gradient():
    x = nd.array([1.0, -2.0, 3.0])
    w = nd.array([2.0, 2.0, 2.0])
    x.attach_grad()
    y = nd.zeros((3,))
    with autograd.record():
        nd.relu(x, out=y)
        z = (y * w).sum()
    z.backward()
    onp.testing.assert_allclose(x.grad.asnumpy(), [2, 0, 2])
