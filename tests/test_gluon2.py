"""Gluon Block semantics, second suite (reference:
tests/python/unittest/test_gluon.py, 115 fns — parameter sharing and
scoping, hybridize caching, save/load edge cases, hooks, SymbolBlock,
grad_req, deferred init)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.test_utils import assert_almost_equal, with_seed


def _x(*shape):
    return nd.array(onp.random.RandomState(0).randn(*shape).astype("f"))


def test_parameter_sharing_via_params():
    """Reference: test_gluon.py test_parameter_sharing."""
    d1 = nn.Dense(4, in_units=3)
    d2 = nn.Dense(4, in_units=3, params=d1.collect_params())
    d1.initialize()
    x = _x(2, 3)
    assert_almost_equal(d2(x), d1(x).asnumpy())
    # updating through one handle is visible through the other
    for _, p in d1.collect_params().items():
        p.set_data(p.data() * 0 + 1.0)
    assert_almost_equal(d2(x), d1(x).asnumpy())


def test_name_scope_prefixes():
    class Net(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.fc = nn.Dense(2)

        def hybrid_forward(self, F, x):
            return self.fc(x)

    n = Net(prefix="outer_")
    names = list(n.collect_params().keys())
    assert all(k.startswith("outer_") for k in names), names


def test_hybridize_caches_and_matches_eager():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(3))
    net.initialize()
    x = _x(4, 5)
    eager = net(x).asnumpy()
    net.hybridize()
    jit1 = net(x).asnumpy()
    jit2 = net(x).asnumpy()
    assert_almost_equal(jit1, eager, rtol=1e-5)
    assert_almost_equal(jit2, eager, rtol=1e-5)


def test_save_load_parameters_roundtrip(tmp_path):
    net = nn.HybridSequential()
    net.add(nn.Dense(6, activation="tanh"), nn.BatchNorm(), nn.Dense(2))
    net.initialize()
    x = _x(3, 4)
    with autograd.pause(train_mode=False):
        want = net(x).asnumpy()
    p = str(tmp_path / "p.params")
    net.save_parameters(p)
    net2 = nn.HybridSequential()
    net2.add(nn.Dense(6, activation="tanh"), nn.BatchNorm(), nn.Dense(2))
    net2.load_parameters(p)
    with autograd.pause(train_mode=False):
        assert_almost_equal(net2(x).asnumpy(), want, rtol=1e-6)


def test_load_parameters_errors(tmp_path):
    net = nn.Dense(3, in_units=2)
    net.initialize()
    p = str(tmp_path / "d.params")
    net.save_parameters(p)
    other = nn.Dense(5, in_units=2)
    with pytest.raises(Exception):
        other.load_parameters(p)  # shape mismatch must not pass silently


def test_forward_hooks_fire():
    net = nn.Dense(2, in_units=3)
    net.initialize()
    calls = []
    h1 = net.register_forward_pre_hook(
        lambda blk, inp: calls.append("pre"))
    h2 = net.register_forward_hook(
        lambda blk, inp, out: calls.append("post"))
    net(_x(1, 3))
    assert calls == ["pre", "post"]
    h1.detach()
    h2.detach()
    calls.clear()
    net(_x(1, 3))
    assert calls == []


def test_grad_req_null_excludes_from_step():
    net = nn.Dense(2, in_units=3)
    net.initialize()
    for _, p in net.collect_params().items():
        if p.name.endswith("bias"):
            p.grad_req = "null"
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 1.0})
    before = {k: p.data().asnumpy().copy()
              for k, p in net.collect_params().items()}
    with autograd.record():
        loss = net(_x(4, 3)).sum()
    loss.backward()
    trainer.step(1)
    for k, p in net.collect_params().items():
        if k.endswith("bias"):
            assert_almost_equal(p.data(), before[k])  # untouched
        else:
            assert not onp.allclose(p.data().asnumpy(), before[k])


def test_deferred_init_infers_in_units():
    net = nn.Dense(4)  # in_units unknown
    net.initialize()
    out = net(_x(5, 7))
    assert out.shape == (5, 4)
    assert net.weight.shape == (4, 7)


def test_uninitialized_forward_raises():
    net = nn.Dense(4, in_units=3)
    with pytest.raises(Exception):
        net(_x(1, 3))


def test_constant_parameter():
    class Net(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.c = self.params.get_constant(
                "c", onp.array([2.0, 3.0], "f"))

        def hybrid_forward(self, F, x, c):
            return x * c

    n = Net()
    n.initialize()
    out = n(nd.array(onp.ones((2, 2), "f")))
    assert_almost_equal(out, onp.array([[2, 3], [2, 3]], "f"))
    # constants take no gradient step
    with autograd.record():
        loss = n(nd.array(onp.ones((1, 2), "f"))).sum()
    loss.backward()


def test_symbolblock_imports_exported(tmp_path):
    net = nn.HybridSequential()
    net.add(nn.Dense(5, activation="relu"), nn.Dense(2))
    net.initialize()
    net.hybridize()
    x = _x(2, 3)
    want = net(x).asnumpy()
    net.export(str(tmp_path / "m"), epoch=0)
    sb = gluon.SymbolBlock.imports(
        str(tmp_path / "m-symbol.json"), ["data"],
        str(tmp_path / "m-0000.params"))
    assert_almost_equal(sb(x), want, rtol=1e-5)


def test_children_and_named_iteration():
    net = nn.HybridSequential()
    net.add(nn.Dense(2), nn.Dense(3))
    kids = list(net._children.values())
    assert len(kids) == 2
    assert isinstance(kids[1], nn.Dense)


def test_block_repr_and_summary_run():
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=3))
    net.initialize()
    net(_x(1, 3))
    net.summary()  # prints; must not raise


def test_trainer_learning_rate_set():
    net = nn.Dense(2, in_units=2)
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.5})
    assert tr.learning_rate == 0.5
    tr.set_learning_rate(0.125)
    assert tr.learning_rate == 0.125


def test_trainer_save_load_states(tmp_path):
    net = nn.Dense(2, in_units=2)
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    for _ in range(3):
        with autograd.record():
            loss = net(_x(4, 2)).sum()
        loss.backward()
        tr.step(1)
    p = str(tmp_path / "tr.states")
    tr.save_states(p)
    tr2 = gluon.Trainer(net.collect_params(), "sgd",
                        {"learning_rate": 0.1, "momentum": 0.9})
    tr2.load_states(p)
    # momentum buffers restored: one step from each must agree
    with autograd.record():
        loss = net(_x(4, 2)).sum()
    loss.backward()
    tr2.step(1)


@with_seed(9)
def test_dropout_train_vs_eval():
    net = nn.Dropout(0.5)
    x = nd.array(onp.ones((200,), "f"))
    with autograd.pause(train_mode=False):
        assert_almost_equal(net(x), onp.ones(200))  # identity at eval
    with autograd.record(train_mode=True):
        y = net(x).asnumpy()
    assert (y == 0).any() and (y > 1.0).any()  # dropped + rescaled


def test_embedding_block_grad_sparse_rows():
    emb = nn.Embedding(10, 4)
    emb.initialize()
    idx = nd.array(onp.array([1.0, 3.0, 1.0], "f"))
    with autograd.record():
        out = emb(idx)
        loss = out.sum()
    loss.backward()
    g = emb.weight.grad().asnumpy()
    assert (g[1] == 2.0).all() and (g[3] == 1.0).all()
    assert (g[0] == 0).all()


def test_sequential_getitem_len():
    net = nn.HybridSequential()
    net.add(nn.Dense(2), nn.Dense(3), nn.Dense(4))
    assert len(net) == 3
    assert isinstance(net[1], nn.Dense)


def test_apply_and_cast():
    net = nn.HybridSequential()
    net.add(nn.Dense(2, in_units=2))
    net.initialize()
    seen = []
    net.apply(lambda b: seen.append(type(b).__name__))
    assert "Dense" in seen
    net.cast("float16")
    assert "float16" in str(net[0].weight.dtype)


def test_parameter_sharing_nested_prefixes(tmp_path):
    """The reference's own sharing scenario (test_gluon.py:227): blocks
    with DIFFERENT prefixes share via params=; the sharing net creates
    its params under the SHARED dict's prefix, and checkpoints load
    across prefixes by structure."""
    class Net(gluon.Block):
        def __init__(self, in_units=0, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.dense0 = nn.Dense(5, in_units=in_units)
                self.dense1 = nn.Dense(5, in_units=in_units)

        def forward(self, x):
            return self.dense1(self.dense0(x))

    net1 = Net(prefix="net1_", in_units=5)
    net2 = Net(prefix="net2_", params=net1.collect_params())
    net1.collect_params().initialize()
    x = _x(3, 5)
    out2 = net2(x)
    assert_almost_equal(out2, net1(x).asnumpy())
    # param names of net2 live under net1_'s prefix (true sharing)
    assert set(net2.collect_params().keys()) == \
        set(net1.collect_params().keys())
    # structure-based load across prefixes
    p = str(tmp_path / "net1.params")
    net1.save_parameters(p)
    net3 = Net(prefix="net3_", in_units=5)
    net3.load_parameters(p)
    assert_almost_equal(net3(x), net1(x).asnumpy())


def test_register_op_hook_taps_and_detaches():
    """Reference: block.py register_op_hook — per-op output taps in
    eager AND hybridized execution, detachable."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import nd, gluon

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(4, activation="relu"), gluon.nn.Dense(2))
    net.initialize(mx.init.Xavier())
    seen = []
    handle = net.register_op_hook(lambda name, arr: seen.append(name))
    x = nd.array(onp.ones((2, 3), "f"))
    net(x)
    assert any("dense" in s for s in seen), seen
    assert any(s.endswith("_output") for s in seen)
    n_eager = len(seen)
    net.hybridize()
    net(x)  # hooks force the eager path: taps fire...
    assert len(seen) > n_eager
    n1 = len(seen)
    net(x)  # ...on EVERY call, not just the trace
    assert len(seen) > n1
    handle.detach()
    before = len(seen)
    net(x)  # cached path resumes, tap-free
    net(x)
    assert len(seen) == before  # taps removed


def test_register_op_hook_nested_hybrid_and_order():
    """Hooks see concrete values through independently hybridized
    children on every call, and handles detach safely in any order."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import nd, gluon

    inner = gluon.nn.HybridSequential()
    inner.add(gluon.nn.Dense(4, activation="relu"))
    outer = gluon.nn.HybridSequential()
    outer.add(inner, gluon.nn.Dense(2))
    outer.initialize(mx.init.Xavier())
    inner.hybridize()  # child has its own cache
    x = nd.array(onp.ones((2, 3), "f"))
    outer(x)  # build caches
    values = []
    h1 = outer.register_op_hook(
        lambda name, arr: values.append(float(arr.asnumpy().max())))
    names2 = []
    h2 = outer.register_op_hook(lambda name, arr: names2.append(name))
    outer(x)
    outer(x)  # concrete values BOTH calls (no tracer leak via caches)
    assert len(values) >= 4 and all(
        isinstance(v, float) for v in values)
    n2 = len(names2)
    # out-of-order detach: h1 first, h2 keeps firing
    h1.detach()
    nv = len(values)
    outer(x)
    assert len(values) == nv  # h1 gone
    assert len(names2) > n2  # h2 alive
    h2.detach()
    n2 = len(names2)
    outer(x)
    assert len(names2) == n2  # fully detached, cache path restored


def test_tpu_context_needs_cpu_asked_for_by_name():
    """tpu(i) stands in for a CPU device only because the suite asked
    for JAX_PLATFORMS=cpu by name; without that request and without an
    accelerator it raises instead of silently falling back."""
    import jax

    from mxnet_tpu.base import MXNetError

    assert mx.tpu(0).jax_device.platform == "cpu"
    jax.config.update("jax_platforms", "")
    try:
        with pytest.raises(MXNetError, match="no accelerator"):
            mx.tpu(0).jax_device
        assert mx.cpu(0).jax_device.platform == "cpu"
    finally:
        jax.config.update("jax_platforms", "cpu")


def test_parameter_reset_ctx():
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import context, nd, gluon

    net = gluon.nn.Dense(3, in_units=2)
    net.initialize(mx.init.Xavier())
    out_before = net(nd.array(onp.ones((1, 2), "f"))).asnumpy()
    ctx = context.cpu(0)
    net.collect_params().reset_ctx(ctx)
    # the buffers really moved: committed to exactly the requested device
    for _, p in net.collect_params().items():
        devs = p.data().data.sharding.device_set
        assert devs == {ctx.jax_device}, devs
    out_after = net(nd.array(onp.ones((1, 2), "f"))).asnumpy()
    onp.testing.assert_allclose(out_after, out_before, rtol=1e-6)
    # uninitialized parameters refuse loudly instead of silently
    # materializing on the wrong device later
    lazy = gluon.nn.Dense(2)
    lazy.initialize()
    import pytest as _pytest

    with _pytest.raises(ValueError, match="not been initialized"):
        lazy.collect_params().reset_ctx(ctx)
