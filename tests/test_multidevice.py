"""Multi-device (8-way virtual CPU mesh) tests for everything that
claims SPMD.

Reference model: tests/nightly/dist_sync_kvstore.py (exact-value asserts
across workers) + the multi-GPU tests in tests/python/gpu. The conftest
mesh plays the role of the reference's multi-process launcher.
"""
import os

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, gluon, kvstore, parallel
from mxnet_tpu.gluon import nn

rs = onp.random.RandomState(3)

pytestmark = pytest.mark.skipif(jax.device_count() < 8,
                                reason="needs the 8-device test mesh")


# ------------------------------------------------------- collectives ---

def test_group_all_reduce_exact():
    vals = [rs.rand(16, 8).astype("f") for _ in range(8)]
    devs = jax.devices()[:8]
    nds = [nd.NDArray(jax.device_put(v, d)) for v, d in zip(vals, devs)]
    out = parallel.group_all_reduce(nds)
    expect = onp.sum(vals, axis=0)
    assert len(out) == 8
    for i, o in enumerate(out):
        onp.testing.assert_allclose(o.asnumpy(), expect, rtol=1e-6)
        assert list(o.data.devices())[0] == devs[i]


def test_group_all_reduce_rejects_same_device():
    a = nd.array(rs.rand(4).astype("f"))
    b = nd.array(rs.rand(4).astype("f"))
    with pytest.raises(mx.base.MXNetError):
        parallel.group_all_reduce([a, b])


def test_kvstore_device_push_collective():
    kv = kvstore.create("device")
    shape = (8, 4)
    kv.init("w", nd.zeros(shape))
    devs = jax.devices()[:8]
    grads = [rs.rand(*shape).astype("f") for _ in range(8)]
    kv.push("w", [nd.NDArray(jax.device_put(g, d))
                  for g, d in zip(grads, devs)])
    out = nd.zeros(shape)
    kv.pull("w", out=out)
    onp.testing.assert_allclose(out.asnumpy(), onp.sum(grads, 0),
                                rtol=1e-5)


def test_kvstore_multi_key_multi_device():
    kv = kvstore.create("device")
    keys = ["a", "b", "c"]
    shapes = [(4, 4), (16,), (2, 3, 4)]
    for k, s in zip(keys, shapes):
        kv.init(k, nd.zeros(s))
    devs = jax.devices()[:4]
    expects = {}
    for k, s in zip(keys, shapes):
        grads = [rs.rand(*s).astype("f") for _ in range(4)]
        expects[k] = onp.sum(grads, 0)
        kv.push(k, [nd.NDArray(jax.device_put(g, d))
                    for g, d in zip(grads, devs)])
    for k, s in zip(keys, shapes):
        out = nd.zeros(s)
        kv.pull(k, out=out)
        onp.testing.assert_allclose(out.asnumpy(), expects[k], rtol=1e-5)


def test_kvstore_bigarray_sharded_storage(monkeypatch):
    monkeypatch.setenv("MXNET_KVSTORE_BIGARRAY_BOUND", "100")
    kv = kvstore.create("dist_sync")
    big = nd.array(rs.rand(16, 32).astype("f"))  # 512 > 100
    kv.init("big", big)
    stored = kv._store["big"]
    assert len(stored.data.sharding.device_set) == 8
    out = nd.zeros((16, 32))
    kv.pull("big", out=out)
    onp.testing.assert_allclose(out.asnumpy(), big.asnumpy(), rtol=1e-6)
    # pull must not leak the kvshard layout into the caller's array
    assert len(out.data.sharding.device_set) == 1
    small = nd.array(rs.rand(3, 3).astype("f"))
    kv.init("small", small)
    assert len(kv._store["small"].data.sharding.device_set) == 1


def test_kvstore_bigarray_push_pull_cycle(monkeypatch):
    """push/updater/pull all keep working after init shards a big key
    (regression: sharded store value used to clash with single-device
    gradients)."""
    monkeypatch.setenv("MXNET_KVSTORE_BIGARRAY_BOUND", "100")
    kv = kvstore.create("dist_sync")
    big = rs.rand(16, 32).astype("f")
    kv.init("big", nd.array(big))
    g = rs.rand(16, 32).astype("f")
    kv.push("big", nd.array(g))
    out = nd.zeros((16, 32))
    kv.pull("big", out=out)
    onp.testing.assert_allclose(out.asnumpy(), big + g, rtol=1e-5)
    # the stored value stays row-sharded across the device group
    assert len(kv._store["big"].data.sharding.device_set) == 8
    # updater path on the sharded key
    kv2 = kvstore.create("dist_sync")
    kv2.init("w", nd.array(big))
    def upd(key, grad, weight):
        weight._data = (weight - 0.5 * grad).data

    kv2.set_updater(upd)
    kv2.push("w", nd.array(g))
    out2 = nd.zeros((16, 32))
    kv2.pull("w", out=out2)
    onp.testing.assert_allclose(out2.asnumpy(), big - 0.5 * g, rtol=1e-5)


def test_group_all_reduce_rejects_multi_device_value():
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = parallel.make_mesh({"dp": 8})
    sharded = nd.NDArray(jax.device_put(rs.rand(8, 4).astype("f"),
                                        NamedSharding(mesh, P("dp"))))
    single = nd.NDArray(jax.device_put(rs.rand(8, 4).astype("f"),
                                       jax.devices()[1]))
    with pytest.raises(mx.base.MXNetError, match="single-device"):
        parallel.group_all_reduce([sharded, single])


# ------------------------------------------------ gradient compression ---

def _ref_quantize(grad, residual, th):
    """Reference quantize_2bit semantics, scalar python oracle
    (gradient_compression-inl.h:64-79)."""
    out = onp.zeros_like(grad)
    r = residual.copy()
    for i in range(grad.size):
        r[i] += grad[i]
        if r[i] >= th:
            out[i] = th
            r[i] -= th
        elif r[i] <= -th:
            out[i] = -th
            r[i] += th
    return out, r


def test_2bit_quantize_matches_reference_semantics():
    from mxnet_tpu.gradient_compression import GradientCompression

    gc = GradientCompression("2bit", threshold=0.4)
    g = (rs.rand(37).astype("f") - 0.5) * 2
    res = onp.zeros(37, "f")
    packed, new_res = gc.quantize(jnp.asarray(g), jnp.asarray(res))
    assert packed.dtype == jnp.uint32 and packed.shape == (3,)
    deq = gc.dequantize(packed, 37)
    exp_out, exp_res = _ref_quantize(g, res, 0.4)
    onp.testing.assert_allclose(onp.asarray(deq), exp_out, rtol=1e-6)
    onp.testing.assert_allclose(onp.asarray(new_res), exp_res, rtol=1e-5)


def test_2bit_error_feedback_converges():
    """Residual accumulation means the summed dequantized gradients
    approach the summed true gradients over steps."""
    from mxnet_tpu.gradient_compression import GradientCompression

    gc = GradientCompression("2bit", threshold=0.05)
    g = (rs.rand(64).astype("f") - 0.5) * 0.2
    res = jnp.zeros(64)
    total = onp.zeros(64, "f")
    for _ in range(50):
        packed, res = gc.quantize(jnp.asarray(g), res)
        total += onp.asarray(gc.dequantize(packed, 64))
    onp.testing.assert_allclose(total / 50, g, atol=0.06)


def test_kvstore_compressed_push_exact():
    kv = kvstore.create("device")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.3})
    shape = (24,)
    kv.init("w", nd.zeros(shape))
    devs = jax.devices()[:4]
    grads = [(rs.rand(*shape).astype("f") - 0.5) for _ in range(4)]
    kv.push("w", [nd.NDArray(jax.device_put(g, d))
                  for g, d in zip(grads, devs)])
    expect = onp.zeros(shape, "f")
    for g in grads:
        q, _ = _ref_quantize(g, onp.zeros(shape, "f"), 0.3)
        expect += q
    out = nd.zeros(shape)
    kv.pull("w", out=out)
    onp.testing.assert_allclose(out.asnumpy(), expect, rtol=1e-5)
    # second push uses the per-source residuals
    kv.push("w", [nd.NDArray(jax.device_put(g, d))
                  for g, d in zip(grads, devs)])
    for g in grads:
        _, r = _ref_quantize(g, onp.zeros(shape, "f"), 0.3)
        q2, _ = _ref_quantize(g, r, 0.3)
        expect += q2
    kv.pull("w", out=out)
    onp.testing.assert_allclose(out.asnumpy(), expect, rtol=1e-5)


def test_compression_rejects_unknown_type():
    kv = kvstore.create("device")
    with pytest.raises(mx.base.MXNetError):
        kv.set_gradient_compression({"type": "1bit"})
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.set_gradient_compression({"type": "none"})
    assert kv._compression is None


# -------------------------------------------------------- SPMDTrainer ---

def _make_net(seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(64, activation="relu"), nn.BatchNorm(),
            nn.Dense(8))
    net.initialize(mx.init.Xavier())
    return net


def _train(mesh_axes, opt, params, steps=6, cdt=None, seed=0):
    net = _make_net(seed)
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = parallel.make_mesh(mesh_axes)
    rules = {r"dense1_weight": ("mp", None)} if "mp" in mesh_axes else None
    tr = parallel.SPMDTrainer(net, loss, optimizer=opt,
                              optimizer_params=params, mesh=mesh,
                              param_rules=rules, compute_dtype=cdt)
    r = onp.random.RandomState(11)
    X = nd.array(r.randn(64, 16).astype("f"))
    y = nd.array(r.randint(0, 8, 64).astype("f"))
    losses = [float(tr.step(X, y).asscalar()) for _ in range(steps)]
    return losses


@pytest.mark.parametrize("opt,params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.01}),
    ("lamb", {"learning_rate": 0.05}),
])
def test_spmd_dp8_matches_single_device(opt, params):
    l8 = _train({"dp": 8}, opt, params)
    l1 = _train({"dp": 1}, opt, params)
    onp.testing.assert_allclose(l8, l1, rtol=2e-4, atol=2e-5)
    assert l8[-1] < l8[0]  # actually learning


def test_spmd_dp_x_mp_matches_single_device():
    lmp = _train({"dp": 4, "mp": 2}, "sgd", {"learning_rate": 0.1})
    l1 = _train({"dp": 1}, "sgd", {"learning_rate": 0.1})
    onp.testing.assert_allclose(lmp, l1, rtol=2e-4, atol=2e-5)


def test_spmd_bf16_on_mesh_learns():
    losses = _train({"dp": 8}, "adam", {"learning_rate": 0.01}, steps=10,
                    cdt="bfloat16")
    assert losses[-1] < losses[0] * 0.9


def test_spmd_adamw_weight_decay_on_mesh():
    l = _train({"dp": 8}, "adamw", {"learning_rate": 0.01, "wd": 0.01},
               steps=6)
    assert l[-1] < l[0]


def test_spmd_param_sync_back_to_gluon():
    net = _make_net()
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = parallel.make_mesh({"dp": 8})
    tr = parallel.SPMDTrainer(net, loss, optimizer="sgd",
                              optimizer_params={"learning_rate": 0.1},
                              mesh=mesh)
    r = onp.random.RandomState(1)
    X = nd.array(r.randn(32, 16).astype("f"))
    y = nd.array(r.randint(0, 8, 32).astype("f"))
    for _ in range(3):
        tr.step(X, y)
    tr.sync_params_to_gluon()
    out = net(X)  # eager forward with the synced params works
    assert out.shape == (32, 8)


# ----------------------------------------------- SyncBatchNorm / AMP ---

def test_sync_batch_norm_stats_match_global_batch():
    """pmean-reduced statistics == stats of the full (unsharded) batch."""
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.gluon.contrib import nn as contrib_nn

    sbn = contrib_nn.SyncBatchNorm(in_channels=4)
    sbn.initialize()
    X = rs.rand(16, 4, 3, 3).astype("f")

    mesh = parallel.make_mesh({"dp": 8})

    def step(x):
        with autograd.pause(train_mode=True):  # batch-stat mode
            out = sbn(nd.NDArray(x))
        return out.data

    sharded = jax.device_put(X, NamedSharding(mesh, P("dp")))
    with mesh:
        out = jax.jit(shard_map(step, mesh=mesh, in_specs=P("dp"),
                                out_specs=P("dp")))(
            sharded)
    # plain BN over the full batch gives the same normalized output
    # (use_batch_stats=True explicitly: outside autograd.record the op
    # now follows the reference and normalizes with the MOVING stats)
    bn_full = nd.batch_norm(
        nd.array(X), nd.ones(4), nd.zeros(4), nd.zeros(4), nd.ones(4),
        fix_gamma=False, eps=1e-5, use_batch_stats=True)
    onp.testing.assert_allclose(onp.asarray(out), bn_full.asnumpy(),
                                rtol=2e-3, atol=2e-3)


def test_amp_overflow_skip_under_dp():
    """LossScaler skips the update when ANY shard's gradient overflows —
    the all_finite check runs on gradients sharded over the dp mesh, so
    the reduction is distributed-safe."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.contrib.amp import LossScaler

    mesh = parallel.make_mesh({"dp": 8})

    class FakeParam:
        grad_req = "write"

        def __init__(self, g):
            self._g = nd.NDArray(
                jax.device_put(g, NamedSharding(mesh, P("dp"))))

        def grad(self):
            return self._g

    good = onp.ones((8, 4), "f")
    bad = good.copy()
    bad[5, 2] = onp.inf  # overflow on shard 5 only
    scaler = LossScaler(init_scale=2 ** 10)
    assert scaler.has_overflow([FakeParam(bad)])
    assert not scaler.has_overflow([FakeParam(good)])
    s0 = scaler.loss_scale
    scaler.update_scale(True)
    assert scaler.loss_scale == s0 / 2  # halved on overflow


def test_shard_batch_layout():
    mesh = parallel.make_mesh({"dp": 8})
    x = nd.array(rs.rand(16, 4).astype("f"))
    sx = parallel.shard_batch(x, mesh)
    assert len(sx.data.sharding.device_set) == 8
    onp.testing.assert_allclose(sx.asnumpy(), x.asnumpy(), rtol=1e-6)


def test_module_multi_context_data_parallel():
    """Module(context=[8 devices]) trains as ONE sharded computation:
    batch inputs split over 'dp', params replicated, gradients globally
    reduced by GSPMD — the Module-API analog of the reference's
    DataParallelExecutorGroup (executor_group.py:144)."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import sym, io
    from mxnet_tpu.module import Module

    ndev = min(8, jax.device_count())
    if ndev < 2:
        import pytest

        pytest.skip("needs multiple devices")
    rs = onp.random.RandomState(0)
    X = rs.randn(128, 6).astype("f")
    y = (X.sum(1) > 0).astype("f")

    def build(ctx):
        mx.random.seed(0)
        data = sym.Variable("data")
        fc1 = sym.FullyConnected(data, name="mc_fc1", num_hidden=16)
        out = sym.SoftmaxOutput(
            sym.FullyConnected(sym.Activation(fc1, act_type="relu"),
                               name="mc_fc2", num_hidden=2),
            sym.Variable("softmax_label"), name="softmax")
        m = Module(out, context=ctx)
        m.bind(data_shapes=[("data", (64, 6))],
               label_shapes=[("softmax_label", (64,))])
        m.init_params(mx.init.Uniform(0.1))
        m.init_optimizer(optimizer="sgd",
                         optimizer_params={"learning_rate": 0.05})
        return m

    def run_epochs(m, epochs=4):
        it = io.NDArrayIter(X, y, batch_size=64)
        for _ in range(epochs):
            it.reset()
            for batch in it:
                m.forward(batch, is_train=True)
                m.backward()
                m.update()
        return {k: v.asnumpy() for k, v in m.get_params()[0].items()}

    # identical graphs/params trained single- vs multi-context must agree
    single = run_epochs(build(mx.cpu(0)))
    multi_mod = build([mx.cpu(i) for i in range(ndev)])
    multi = run_epochs(multi_mod)
    assert single.keys() == multi.keys()
    for k in single:
        onp.testing.assert_allclose(multi[k], single[k], rtol=2e-4,
                                    atol=1e-5, err_msg=k)
    # and the bound computation really is sharded over dp
    m = multi_mod
    m.forward(io.DataBatch(data=[nd.array(X[:64])],
                           label=[nd.array(y[:64])]), is_train=False)
    assert m.get_outputs()[0].shape == (64, 2)
