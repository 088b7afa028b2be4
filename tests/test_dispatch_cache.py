"""Compiled eager-dispatch cache (ndarray/registry.py).

Covers the cache contract: hits on repeated same-shape dispatch, misses on
shape/dtype/AMP-version changes, the LRU bound, the MXNET_EAGER_JIT=0
bypass, and byte-for-byte equivalence (values, gradients, out=, PRNG
streams, create_graph replay) between the cached and uncached paths.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, profiler
from mxnet_tpu.ndarray import registry

nd = mx.nd


@pytest.fixture(autouse=True)
def _fresh_cache(monkeypatch):
    monkeypatch.setenv("MXNET_EAGER_JIT", "1")
    registry.reset_dispatch_cache(maxsize=512)
    yield
    registry.reset_dispatch_cache(maxsize=512)


def test_hit_on_repeated_same_shape():
    x = nd.ones((4, 8))
    w = nd.ones((8, 8))
    r = [nd.dot(x, w) for _ in range(3)]
    s = registry.dispatch_cache_stats()
    assert s["misses"] == 1
    assert s["hits"] == 2
    for ri in r[1:]:
        assert onp.array_equal(ri.asnumpy(), r[0].asnumpy())


def test_miss_on_shape_dtype_and_amp_change():
    w32 = nd.ones((8, 8))
    nd.dot(nd.ones((4, 8)), w32)
    nd.dot(nd.ones((2, 8)), w32)                       # shape change
    nd.dot(nd.ones((4, 8), dtype="float16"),
           nd.ones((8, 8), dtype="float16"))           # dtype change
    assert registry.dispatch_cache_stats()["misses"] == 3
    registry.set_amp(None)                             # bumps AMP version
    nd.dot(nd.ones((4, 8)), w32)
    assert registry.dispatch_cache_stats()["misses"] == 4


def test_eviction_bound_respected():
    registry.reset_dispatch_cache(maxsize=2)
    for n in (2, 3, 4, 5):
        nd.tanh(nd.ones((n,)))
    s = registry.dispatch_cache_stats()
    assert s["size"] <= 2
    assert s["evictions"] >= 2
    # the most recent entry survived and still hits
    nd.tanh(nd.ones((5,)))
    assert registry.dispatch_cache_stats()["hits"] == 1


def test_eager_jit_env_bypass(monkeypatch):
    monkeypatch.setenv("MXNET_EAGER_JIT", "0")
    x = nd.ones((4, 4))
    for _ in range(3):
        nd.tanh(x)
    s = registry.dispatch_cache_stats()
    assert s["hits"] == 0 and s["misses"] == 0
    assert not registry.eager_jit_enabled()


def _grad_chain(a, w):
    with autograd.record():
        y = nd.dot(a, w)
        z = nd.sum(nd.tanh(y))
    z.backward()
    return a.grad.asnumpy().copy()


def test_gradient_bitwise_equivalence(monkeypatch):
    a = nd.array(onp.linspace(-1, 1, 32).reshape(4, 8).astype("float32"))
    w = nd.array(onp.linspace(0, 2, 64).reshape(8, 8).astype("float32"))
    a.attach_grad()
    monkeypatch.setenv("MXNET_EAGER_JIT", "0")
    g_un = _grad_chain(a, w)
    monkeypatch.setenv("MXNET_EAGER_JIT", "1")
    g_miss = _grad_chain(a, w)   # first pass populates the cache
    g_hit = _grad_chain(a, w)    # second pass runs compiled executables
    assert registry.dispatch_cache_stats()["hits"] > 0
    assert onp.array_equal(g_un, g_miss)
    assert onp.array_equal(g_un, g_hit)


@pytest.mark.parametrize("donate", ["0", "1"])
def test_out_equivalence(monkeypatch, donate):
    # donate=1 opts into out=-buffer donation (entry compiled with
    # donate_argnums; a no-op alias hint on the CPU backend)
    monkeypatch.setenv("MXNET_EAGER_JIT_DONATE", donate)

    def run():
        registry.reset_dispatch_cache()
        w = nd.array(onp.arange(8, dtype="float32"))
        g = nd.ones((8,))
        for _ in range(3):
            nd.sgd_update(w, g, 0.1, out=w)
        return w.asnumpy().copy()

    monkeypatch.setenv("MXNET_EAGER_JIT", "0")
    expect = run()
    monkeypatch.setenv("MXNET_EAGER_JIT", "1")
    got = run()
    assert registry.dispatch_cache_stats()["hits"] >= 2
    assert onp.array_equal(expect, got)
    # out= must return the same handle, updated in place
    w = nd.ones((8,))
    r = nd.sgd_update(w, nd.ones((8,)), 0.1, out=w)
    assert r is w


def test_prng_stream_equivalence(monkeypatch):
    def draw():
        mx.random.seed(11)
        return [nd.random_uniform(shape=(5,)).asnumpy() for _ in range(4)]

    monkeypatch.setenv("MXNET_EAGER_JIT", "0")
    expect = draw()
    monkeypatch.setenv("MXNET_EAGER_JIT", "1")
    got = draw()     # call 1 = miss, calls 2-4 = cached hits
    assert registry.dispatch_cache_stats()["hits"] >= 1
    for e, g in zip(expect, got):
        assert onp.array_equal(e, g)


def test_stochastic_op_grad_equivalence(monkeypatch):
    def run():
        mx.random.seed(3)
        x = nd.ones((16, 16))
        x.attach_grad()
        outs = []
        for _ in range(2):
            with autograd.record():
                y = nd.sum(nd.dropout(x, p=0.5))
            y.backward()
            outs.append((y.asnumpy().copy(), x.grad.asnumpy().copy()))
        return outs

    monkeypatch.setenv("MXNET_EAGER_JIT", "0")
    expect = run()
    monkeypatch.setenv("MXNET_EAGER_JIT", "1")
    got = run()
    for (ey, eg), (gy, gg) in zip(expect, got):
        assert onp.array_equal(ey, gy)
        assert onp.array_equal(eg, gg)


def test_create_graph_replay_equivalence(monkeypatch):
    def second_order():
        x = nd.array(onp.array([0.3, -0.7, 1.2], dtype="float32"))
        x.attach_grad()
        with autograd.record():
            y = nd.sum(nd.tanh(x) * nd.tanh(x))
        (g,) = autograd.grad(y, [x], create_graph=True)
        autograd.backward(nd.sum(g))
        return x.grad.asnumpy().copy()

    monkeypatch.setenv("MXNET_EAGER_JIT", "0")
    expect = second_order()
    monkeypatch.setenv("MXNET_EAGER_JIT", "1")
    second_order()                 # populate
    got = second_order()           # cached forward, replayed backward
    assert onp.array_equal(expect, got)


def test_profiler_cached_flag_and_counters(tmp_path):
    x = nd.ones((4, 4))
    nd.tanh(x)          # miss outside the profiled window
    profiler.set_config(filename="", profile_imperative=True)
    profiler.start()
    try:
        nd.tanh(x)      # hit
    finally:
        profiler.stop()
        profiler.set_config(filename="profile.json",
                            profile_imperative=False)
    evs = [e for e in profiler._events
           if e.get("name") == "tanh" and "cached" in e.get("args", {})]
    assert evs and evs[-1]["args"]["cached"] is True
    counters = profiler.dispatch_cache_counters()
    assert counters["hits"] >= 1
    # dump() carries the counters as chrome counter samples
    import json

    profiler.set_config(filename=str(tmp_path / "prof.json"))
    try:
        f = profiler.dump()
    finally:
        profiler.set_config(filename="profile.json")
    evts = json.load(open(f))["traceEvents"]
    assert any(e["name"] == "eager_jit_cache/hits" for e in evts)
    # dumps() keeps its empty-after-reset contract
    profiler.dumps(format="json", reset=True)
    assert profiler.dumps(format="json") == "[]"


def test_tracer_and_adhoc_bypass():
    # numpy frontend _call dispatches ad-hoc OpDefs: must bypass, and two
    # different closures under one name must not collide
    np = mx.np
    xi, yi = np.meshgrid(np.arange(3), np.arange(4), indexing="ij")
    xx, yy = np.meshgrid(np.arange(3), np.arange(4))
    assert xi.shape == (3, 4) and xx.shape == (4, 3)
    assert registry.dispatch_cache_stats()["bypasses"] >= 1
