"""Persistent compile cache + shape bucketing (utils/compile_cache.py).

Covers the disk second tier behind the eager-dispatch and fused-step
executable caches (warm start without recompiling, corrupt/mismatched
entries as misses, the MXNET_COMPILE_CACHE=0 knob), automatic shape
bucketing (MXNET_SHAPE_BUCKETS: retrace reduction + bitwise row
identity), the AOT warmup APIs (Trainer.warmup, Module.warmup,
BucketingModule.warmup_buckets), tier-1 hermeticity of the cache dir,
and thread-safety of the shared CountedLRUCache.
"""
import os
import pickle
import threading

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, profiler
from mxnet_tpu.gluon import fused_step as fs
from mxnet_tpu.gluon import nn
from mxnet_tpu.ndarray import registry
from mxnet_tpu.utils import compile_cache as cc
from mxnet_tpu.utils.lru import CountedLRUCache

nd = mx.nd


@pytest.fixture(autouse=True)
def _fresh(monkeypatch, tmp_path):
    """Per-test cache dir + zeroed counters + empty in-memory caches,
    so disk hits/retraces in one test can't leak into another."""
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path / "cc"))
    monkeypatch.setenv("MXNET_EAGER_JIT", "1")
    monkeypatch.delenv("MXNET_SHAPE_BUCKETS", raising=False)
    registry.reset_dispatch_cache(maxsize=512)
    fs.reset_fused_step_cache()
    cc.reset_compile_cache_counters()
    yield
    registry.reset_dispatch_cache(maxsize=512)
    fs.reset_fused_step_cache()
    cc.reset_compile_cache_counters()


def _mxc_files():
    d = cc.cache_dir()
    if not os.path.isdir(d):
        return []
    return [f for f in os.listdir(d) if f.endswith(".mxc")]


# ---------------------------------------------------------------------------
# hermeticity (conftest satellite)

def test_tier1_cache_dir_is_hermetic():
    """The session conftest pins MXNET_COMPILE_CACHE_DIR into pytest's
    tmpdir (this test's fixture narrows it further): nothing the suite
    compiles may land in — or be served from — the checkout's default
    cache."""
    d = cc.cache_dir()
    default = cc._DEFAULT_CACHE_ROOT
    assert not os.path.abspath(d).startswith(default)
    assert not os.path.abspath(cc.jax_cache_dir()).startswith(default)
    mxc = os.path.join(default, "mxc")
    before = set(os.listdir(mxc)) if os.path.isdir(mxc) else set()
    x = nd.ones((3, 5))
    nd.tanh(x)
    nd.tanh(x)  # first hit: AOT compile + disk write
    assert _mxc_files(), "executable was not persisted into the tmpdir"
    after = set(os.listdir(mxc)) if os.path.isdir(mxc) else set()
    assert after == before, "suite leaked cache entries into the checkout"


@pytest.mark.parametrize("placed", [True, False],
                         ids=["env_set", "env_unset"])
def test_jax_cache_placement_rule(placed, forced_device_subprocess,
                                  tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, importing and using the
    package leaves jax's cache directory at that value (the package
    sets no other); unset, it is the one fixed in-checkout path — and
    the .mxc tier's default is fixed inside the checkout too."""
    outside = str(tmp_path / "placed_from_outside")
    out = forced_device_subprocess(
        "import json, os, jax\n"
        "os.environ.pop('MXNET_COMPILE_CACHE_DIR', None)\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.utils import compile_cache as cc\n"
        "f = cc.counting_jit(lambda a: a + 1)\n"
        "mx.nd.tanh(mx.nd.ones((2, 3))).wait_to_read()\n"
        "print(json.dumps({'jax': jax.config.jax_compilation_cache_dir,\n"
        "                  'fn': cc.jax_cache_dir(), 'mxc': cc.cache_dir(),\n"
        "                  'root': cc._DEFAULT_CACHE_ROOT}))\n",
        env={"JAX_COMPILATION_CACHE_DIR": outside if placed else ""})
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert out["root"] == os.path.join(repo, ".jax_cache")
    assert out["mxc"] == os.path.join(repo, ".jax_cache", "mxc")
    want = outside if placed else out["root"]
    assert out["jax"] == want and out["fn"] == want, out


# ---------------------------------------------------------------------------
# dispatch-cache disk tier

def test_dispatch_warm_start_skips_retrace():
    x = nd.ones((4, 8))
    w = nd.ones((8, 8))
    r_cold = nd.dot(x, w)
    nd.dot(x, w)  # first hit: AOT compile, serialize, write
    s = cc.compile_cache_stats()
    assert s["disk_writes"] == 1 and s["retraces"] == 1, s

    # simulated restart: in-memory cache gone, disk survives
    registry.reset_dispatch_cache()
    cc.reset_compile_cache_counters()
    r_warm = nd.dot(x, w)
    s = cc.compile_cache_stats()
    assert s["disk_hits"] == 1, s
    assert s["retraces"] == 0, "warm start must not trace"
    assert onp.array_equal(r_cold.asnumpy(), r_warm.asnumpy())
    # and the promoted entry keeps serving hits
    r2 = nd.dot(x, w)
    assert onp.array_equal(r2.asnumpy(), r_cold.asnumpy())
    assert registry.dispatch_cache_stats()["hits"] >= 1


def test_dispatch_eager_persist_stores_at_compile_time(monkeypatch):
    """MXNET_DISPATCH_EAGER_PERSIST=1 (round 23, fleet replicas): the
    dispatch executable is AOT-compiled and written to the disk tier
    on the very first call — a one-shot construction op that never
    hits again in its process still leaves an artifact, so a
    bundle-warm replica truly starts at zero compiles."""
    x = nd.ones((4, 8))
    w = nd.ones((8, 8))
    cc.reset_compile_cache_counters()
    monkeypatch.setenv("MXNET_DISPATCH_EAGER_PERSIST", "1")
    r_cold = nd.dot(x, w)  # ONE call — no in-process hit ever happens
    s = cc.compile_cache_stats()
    assert s["disk_writes"] == 1, s
    assert _mxc_files(), "eager persist left no disk entry"
    # simulated restart: the single warm call serves from disk
    registry.reset_dispatch_cache()
    cc.reset_compile_cache_counters()
    r_warm = nd.dot(x, w)
    s = cc.compile_cache_stats()
    assert s["disk_hits"] == 1 and s["retraces"] == 0, s
    assert onp.array_equal(r_cold.asnumpy(), r_warm.asnumpy())
    # default (off): a single call persists nothing — eager AOT is an
    # exporting-replica tax the common path must not pay
    monkeypatch.delenv("MXNET_DISPATCH_EAGER_PERSIST")
    registry.reset_dispatch_cache()
    cc.reset_compile_cache_counters()
    nd.tanh(x)
    assert cc.compile_cache_stats()["disk_writes"] == 0


def test_recording_entries_are_not_persisted():
    """vjp pullbacks carry live functions in their output pytree — they
    cannot serialize and must count as serialize_skips, not break."""
    x = nd.ones((4, 8))
    x.attach_grad()
    for _ in range(3):
        with autograd.record():
            y = nd.tanh(x)
        y.backward()
    s = cc.compile_cache_stats()
    assert s["disk_writes"] == 0
    # grads still flow through the in-memory compiled path
    assert x.grad.shape == (4, 8)


def test_corrupt_entry_is_a_miss_and_removed():
    x = nd.ones((2, 3))
    nd.exp(x)
    nd.exp(x)
    files = _mxc_files()
    assert len(files) == 1
    path = os.path.join(cc.cache_dir(), files[0])
    with open(path, "wb") as f:
        f.write(b"not a pickle")
    registry.reset_dispatch_cache()
    cc.reset_compile_cache_counters()
    r = nd.exp(x)
    s = cc.compile_cache_stats()
    assert s["disk_corrupt"] == 1 and s["disk_hits"] == 0, s
    assert not os.path.exists(path), "corrupt entry must be removed"
    assert onp.allclose(r.asnumpy(), onp.exp(onp.ones((2, 3))))


def test_version_mismatch_is_a_miss():
    x = nd.ones((2, 3))
    nd.log(x)
    nd.log(x)
    files = _mxc_files()
    assert len(files) == 1
    path = os.path.join(cc.cache_dir(), files[0])
    with open(path, "rb") as f:
        env = pickle.load(f)
    env["salt"] = ("different",)  # jax/jaxlib/backend/format drifted
    with open(path, "wb") as f:
        pickle.dump(env, f)
    registry.reset_dispatch_cache()
    cc.reset_compile_cache_counters()
    nd.log(x)
    s = cc.compile_cache_stats()
    assert s["disk_corrupt"] == 1 and s["disk_hits"] == 0, s


def test_knob_disables_disk_tier(monkeypatch):
    monkeypatch.setenv("MXNET_COMPILE_CACHE", "0")
    x = nd.ones((2, 3))
    nd.sqrt(x)
    nd.sqrt(x)
    assert _mxc_files() == []
    s = cc.compile_cache_stats()
    assert s["disk_writes"] == 0 and s["disk_misses"] == 0
    assert s["enabled"] is False
    # dispatch cache itself still works
    assert registry.dispatch_cache_stats()["hits"] >= 1


def test_fingerprint_stability_and_unstable_keys():
    k = ("dot", (("a", 0),), (), (), (((4, 8), "float32", False),), 0)
    assert cc.fingerprint("dispatch", k) == cc.fingerprint("dispatch", k)
    assert cc.fingerprint("dispatch", k) != cc.fingerprint("fused", k)
    k2 = ("dot", (("a", 0),), (), (), (((4, 9), "float32", False),), 0)
    assert cc.fingerprint("dispatch", k) != cc.fingerprint("dispatch", k2)
    # live functions have no process-stable form: no fingerprint, and
    # the entry simply stays memory-only
    assert cc.fingerprint("dispatch", (lambda: 1,)) is None
    # floats are type-tagged apart from ints, hex-exact
    assert cc.fingerprint("d", (1,)) != cc.fingerprint("d", (1.0,))


# ---------------------------------------------------------------------------
# shape bucketing

_STREAM = (5, 6, 7, 9, 11, 13, 15, 8)


def _stream_outputs():
    w = nd.ones((8, 8))
    outs = {}
    for _ in range(2):  # sizes repeat: unbucketed pays one trace per size
        for b in _STREAM:
            x = nd.array(onp.arange(b * 8, dtype="float32").reshape(b, 8)
                         / 100.0)
            outs[b] = nd.tanh(nd.broadcast_add(nd.dot(x, w),
                                               nd.ones((8,))))
    return outs


def test_bucketing_cuts_retraces_bitwise(monkeypatch):
    monkeypatch.setenv("MXNET_SHAPE_BUCKETS", "pow2")
    bucketed = _stream_outputs()
    s = cc.compile_cache_stats()
    retr_bucketed = s["retraces"]
    assert s["bucketed_calls"] > 0
    assert 0.0 < s["pad_ratio"] < 1.0

    monkeypatch.setenv("MXNET_SHAPE_BUCKETS", "0")
    registry.reset_dispatch_cache()
    cc.reset_compile_cache_counters()
    plain = _stream_outputs()
    retr_plain = cc.compile_cache_stats()["retraces"]

    assert retr_bucketed < retr_plain, (retr_bucketed, retr_plain)
    for b in plain:
        assert bucketed[b].shape == plain[b].shape
        assert onp.array_equal(bucketed[b].asnumpy(), plain[b].asnumpy()), \
            f"batch {b} not bitwise identical under bucketing"


def test_bucketing_mult_policy(monkeypatch):
    monkeypatch.setenv("MXNET_SHAPE_BUCKETS", "mult:4")
    assert cc.bucket_size(5, cc.bucket_spec()) == 8
    assert cc.bucket_size(8, cc.bucket_spec()) == 8
    assert cc.bucket_size(9, cc.bucket_spec()) == 12
    x5 = nd.array(onp.arange(5 * 4, dtype="float32").reshape(5, 4))
    x7 = nd.array(onp.arange(7 * 4, dtype="float32").reshape(7, 4))
    r5, r7 = nd.relu(x5), nd.relu(x7)
    assert r5.shape == (5, 4) and r7.shape == (7, 4)
    assert cc.compile_cache_stats()["bucketed_calls"] == 2
    assert onp.array_equal(r5.asnumpy(), onp.maximum(x5.asnumpy(), 0))


def test_non_whitelisted_ops_never_bucketed(monkeypatch):
    monkeypatch.setenv("MXNET_SHAPE_BUCKETS", "pow2")
    x = nd.array(onp.arange(5 * 4, dtype="float32").reshape(5, 4))
    # sum reduces over the batch axis: padding would be silently wrong
    s = nd.sum(x, axis=0)
    assert onp.array_equal(s.asnumpy(), x.asnumpy().sum(axis=0))
    # softmax over axis 0 mixes rows: the guard must veto it
    sm = nd.softmax(x, axis=0)
    ref = onp.exp(x.asnumpy()) / onp.exp(x.asnumpy()).sum(0)
    assert onp.allclose(sm.asnumpy(), ref, atol=1e-6)
    assert cc.compile_cache_stats()["bucketed_calls"] == 0


def test_bucketing_resolves_negative_and_positional_axis(monkeypatch):
    """Regression: the softmax guard must resolve the axis against the
    operand rank (axis=-2 on 2-D aliases axis 0) and must see
    POSITIONALLY-passed config — both previously bucketed a
    normalization over the batch axis and returned wrong values."""
    monkeypatch.setenv("MXNET_SHAPE_BUCKETS", "pow2")
    x = nd.array(onp.arange(3 * 2, dtype="float32").reshape(3, 2) / 10.0)
    ref = onp.exp(x.asnumpy()) / onp.exp(x.asnumpy()).sum(0)
    assert onp.allclose(nd.softmax(x, axis=-2).asnumpy(), ref, atol=1e-6)
    # axis passed positionally: softmax(data, length, axis)
    assert onp.allclose(nd.softmax(x, None, 0).asnumpy(), ref, atol=1e-6)
    # dot with transpose_a positional: rows mix; must not be bucketed
    a = nd.array(onp.arange(3 * 2, dtype="float32").reshape(3, 2))
    b = nd.array(onp.arange(3 * 2, dtype="float32").reshape(3, 2))
    got = nd.dot(a, b, True)
    assert onp.array_equal(got.asnumpy(),
                           a.asnumpy().T @ b.asnumpy())
    assert cc.compile_cache_stats()["bucketed_calls"] == 0


def test_bucketing_skips_rank1_row_operands(monkeypatch):
    """Regression: on a 1-D dot lhs (or softmax vector) axis 0 is the
    contraction/data axis — padding it raised a dot_general shape
    TypeError before the rank>=2 precondition."""
    monkeypatch.setenv("MXNET_SHAPE_BUCKETS", "pow2")
    v = nd.array(onp.array([0.0, 1.0, 2.0], dtype="float32"))
    m = nd.ones((3, 2))
    r = nd.dot(v, m)
    assert onp.array_equal(r.asnumpy(), v.asnumpy() @ m.asnumpy())
    sm = nd.softmax(v)
    assert onp.allclose(sm.asnumpy(),
                        onp.exp(v.asnumpy())
                        / onp.exp(v.asnumpy()).sum(), atol=1e-6)
    assert cc.compile_cache_stats()["bucketed_calls"] == 0


def test_disk_cache_prunes_to_size_cap(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_COMPILE_CACHE_MAX_MB", "1")
    monkeypatch.setattr(cc, "_PRUNE_EVERY", 1)
    d = cc.cache_dir()
    os.makedirs(d, exist_ok=True)
    # simulate an overgrown cache from previous runs: ~1.5 MB of stale
    # entries, distinct mtimes so eviction order is deterministic
    for i in range(12):
        p = os.path.join(d, f"stale{i:02d}.mxc")
        with open(p, "wb") as f:
            f.write(b"x" * (128 * 1024))
        os.utime(p, (1000 + i, 1000 + i))
    x = nd.ones((2, 3))
    nd.exp(x)
    nd.exp(x)  # first hit: AOT compile + write -> prune pass
    files = _mxc_files()
    total = sum(os.path.getsize(os.path.join(d, f)) for f in files)
    assert total <= 1024 * 1024, (total, files)
    # oldest entries went first; the fresh real entry survived
    assert not os.path.exists(os.path.join(d, "stale00.mxc"))
    assert any(not f.startswith("stale") for f in files)


def test_prune_survives_concurrent_pruner(monkeypatch, tmp_path):
    """Regression: two replicas sharing one cache dir prune
    concurrently — entries the other pruner already deleted vanish
    between scandir/stat and stat/remove. The sweep must tolerate the
    per-entry races (not abort on the first ghost) and still enforce
    the cap on what remains."""
    import contextlib

    monkeypatch.setenv("MXNET_COMPILE_CACHE_MAX_MB", "1")
    monkeypatch.setattr(cc, "_PRUNE_EVERY", 1)
    d = str(tmp_path)
    for i in range(12):
        p = os.path.join(d, f"stale{i:02d}.mxc")
        with open(p, "wb") as f:
            f.write(b"x" * (256 * 1024))
        os.utime(p, (1000 + i, 1000 + i))

    real_scandir = os.scandir
    # the "other pruner" takes these mid-sweep: two before our stat,
    # one after our stat but before our remove
    vanish = {"stale00.mxc": "pre-stat", "stale01.mxc": "pre-stat",
              "stale02.mxc": "pre-remove"}

    class _RacyEntry:
        def __init__(self, e, race):
            self._e, self._race = e, race
            self.name, self.path = e.name, e.path

        def stat(self):
            if self._race == "pre-stat":
                os.remove(self.path)
                raise FileNotFoundError(self.path)
            st = self._e.stat()
            if self._race == "pre-remove":
                os.remove(self.path)
            return st

    @contextlib.contextmanager
    def racy_scandir(path):
        with real_scandir(path) as it:
            yield (_RacyEntry(e, vanish.get(e.name)) for e in it)

    monkeypatch.setattr(cc.os, "scandir", racy_scandir)
    before = cc.compile_cache_stats()
    cc._maybe_prune(d)  # must not raise
    monkeypatch.setattr(cc.os, "scandir", real_scandir)
    stats = cc.compile_cache_stats()
    assert stats["prunes"] - before["prunes"] == 1
    assert stats["disk_evicted"] > before["disk_evicted"]
    left = [f for f in os.listdir(d) if f.endswith(".mxc")]
    total = sum(os.path.getsize(os.path.join(d, f)) for f in left)
    assert total <= 1024 * 1024, (total, left)
    # newest entries survived the sweep
    assert "stale11.mxc" in left


def test_bucketing_skips_recording(monkeypatch):
    monkeypatch.setenv("MXNET_SHAPE_BUCKETS", "pow2")
    x = nd.array(onp.ones((5, 4), dtype="float32"))
    x.attach_grad()
    with autograd.record():
        y = nd.tanh(x)
    y.backward()
    assert cc.compile_cache_stats()["bucketed_calls"] == 0
    assert x.grad.shape == (5, 4)


# ---------------------------------------------------------------------------
# fused-step disk tier + Trainer.warmup

def _make_net(seed=7, materialize=True):
    mx.random.seed(seed)
    net = nn.Sequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    if materialize:
        with autograd.pause(train_mode=False):
            net(nd.zeros((8, 10)))
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    return net, tr


def _train(net, tr, steps=3):
    for i in range(steps):
        x = nd.array(onp.random.RandomState(i).rand(8, 10)
                     .astype("float32"))
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        tr.step(8)
    return [p.data().asnumpy()
            for _, p in sorted(net.collect_params().items())]


def test_fused_step_warm_start_bitwise():
    net, tr = _make_net()
    p_cold = _train(net, tr)
    s = cc.compile_cache_stats()
    assert s["disk_writes"] >= 1  # the fused-step executable persisted

    fs.reset_fused_step_cache()
    registry.reset_dispatch_cache()
    cc.reset_compile_cache_counters()
    net, tr = _make_net()
    p_warm = _train(net, tr)
    s = cc.compile_cache_stats()
    assert s["disk_hits"] >= 1, s
    for a, b in zip(p_cold, p_warm):
        assert onp.array_equal(a, b)


def test_trainer_warmup_resolves_before_first_step():
    net, tr = _make_net()
    assert tr.warmup() == 0  # no block/shapes: fused AOT resolve only
    r0 = cc.compile_cache_stats()["retraces"]
    assert r0 >= 1  # the fused step traced during warmup, not mid-epoch
    st = fs.fused_step_stats()
    assert st["size"] == 1
    _train(net, tr, steps=1)
    assert fs.fused_step_stats()["hits"] >= 1


def test_trainer_warmup_block_is_bitwise_neutral():
    net, tr = _make_net()
    p_cold = _train(net, tr)

    fs.reset_fused_step_cache()
    registry.reset_dispatch_cache()
    cc.reset_compile_cache_counters()
    net, tr = _make_net()
    before = [p.data().asnumpy()
              for _, p in sorted(net.collect_params().items())]
    assert tr.warmup(shapes=[(8, 10)], block=net) == 1
    after = [p.data().asnumpy()
             for _, p in sorted(net.collect_params().items())]
    for a, b in zip(before, after):
        assert onp.array_equal(a, b), "warmup mutated parameters"
    assert tr._optimizer.num_update == 0

    p_warm = _train(net, tr)
    for a, b in zip(p_cold, p_warm):
        assert onp.array_equal(a, b), "training after warmup diverged"
    # the warmed shapes step without new fused traces
    r0 = cc.compile_cache_stats()["retraces"]
    _train(net, tr, steps=1)
    assert cc.compile_cache_stats()["retraces"] == r0


def test_fingerprint_salts_function_bodies():
    """Editing an op body (or optimizer kernel) must invalidate its
    disk entries even though the cache key only carries the op NAME."""
    def body_a(x):
        return x + 1

    def body_b(x):
        return x + 2

    def body_a2(x):
        return x + 1

    key = ("someop", (((4,), "float32", False),))
    fa = cc.fingerprint("dispatch", key, code_of=(body_a,))
    fb = cc.fingerprint("dispatch", key, code_of=(body_b,))
    fa2 = cc.fingerprint("dispatch", key, code_of=(body_a2,))
    assert fa != fb, "changed body must change the fingerprint"
    assert fa == fa2, "identical source must fingerprint identically"


def test_knob_disables_fused_disk_layer(monkeypatch):
    """MXNET_COMPILE_CACHE=0 must mean the plain jit path on the fused
    step too — not a no-op GuardedCompiled layer."""
    monkeypatch.setenv("MXNET_COMPILE_CACHE", "0")
    net, tr = _make_net()
    _train(net, tr, steps=1)
    entry = next(iter(fs._CACHE._d.values()))
    assert entry._artifact is None
    assert not isinstance(entry._call, cc.GuardedCompiled)
    assert _mxc_files() == []


def test_warmup_half_specified_raises():
    net, tr = _make_net()
    with pytest.raises(ValueError, match="BOTH shapes and block"):
        tr.warmup(shapes=[(8, 10)])
    with pytest.raises(ValueError, match="BOTH shapes and block"):
        tr.warmup(block=net)


# ---------------------------------------------------------------------------
# BucketingModule: switch-back reuse + AOT precompile (satellite)

def _bucketing_module():
    from mxnet_tpu import io, symbol as sym
    from mxnet_tpu.module import BucketingModule

    def gen(bucket_key):
        data = sym.Variable("data")
        pooled = sym.mean(data, axis=1, keepdims=True)
        fc = sym.FullyConnected(pooled, name="bk_fc", num_hidden=2)
        out = sym.SoftmaxOutput(fc, sym.Variable("softmax_label"),
                                name="softmax")
        return out, ("data",), ("softmax_label",)

    bm = BucketingModule(gen, default_bucket_key=8, context=mx.cpu())
    bm.bind(data_shapes=[("data", (4, 8))],
            label_shapes=[("softmax_label", (4,))])
    bm.init_params()
    return bm, io


def _bucket_batch(io, width, rs):
    return io.DataBatch(
        data=[nd.array(rs.rand(4, width).astype("f"))],
        label=[nd.array(rs.randint(0, 2, 4).astype("f"))],
        bucket_key=width,
        provide_data=[io.DataDesc("data", (4, width))],
        provide_label=[io.DataDesc("softmax_label", (4,))])


def test_switch_bucket_reuses_compiled_executor():
    """Regression: switching BACK to a previously-seen bucket must reuse
    its bound module and compiled executor — no re-bind, no retrace —
    asserted through the profiler's compile-cache counters."""
    bm, io = _bucketing_module()
    rs = onp.random.RandomState(3)
    bm.forward(_bucket_batch(io, 8, rs), is_train=True)
    bm.forward(_bucket_batch(io, 4, rs), is_train=True)
    mod8 = bm._buckets[8]
    exec8 = mod8._exec
    fwd8 = exec8._fwd_jit
    retr = profiler.compile_cache_counters()["retraces"]
    bm.forward(_bucket_batch(io, 8, rs), is_train=True)  # back to 8
    assert bm._buckets[8] is mod8, "bucket module was re-created"
    assert mod8._exec is exec8, "executor was re-bound"
    assert mod8._exec._fwd_jit is fwd8, "forward jit was rebuilt"
    assert profiler.compile_cache_counters()["retraces"] == retr, \
        "switching back to a seen bucket retraced"


def test_warmup_buckets_precompiles_all_buckets():
    bm, io = _bucketing_module()
    buckets = [(8, [("data", (4, 8))], [("softmax_label", (4,))]),
               (4, [("data", (4, 4))], [("softmax_label", (4,))]),
               (6, [("data", (4, 6))], [("softmax_label", (4,))])]
    assert bm.warmup_buckets(buckets, is_train=True) == 3
    assert set(bm._buckets) == {8, 4, 6}
    assert bm._curr_bucket_key == 8  # switched back to the entry bucket
    retr = profiler.compile_cache_counters()["retraces"]
    assert retr >= 3
    rs = onp.random.RandomState(3)
    for width in (4, 8, 6, 4, 8):
        bm.forward(_bucket_batch(io, width, rs), is_train=True)
        bm.backward()
    assert profiler.compile_cache_counters()["retraces"] == retr, \
        "a warmed bucket retraced mid-epoch"


# ---------------------------------------------------------------------------
# observability

def test_profiler_and_runtime_surfaces():
    from mxnet_tpu import runtime

    x = nd.ones((2, 2))
    nd.tanh(x)
    nd.tanh(x)
    counters = profiler.compile_cache_counters()
    for k in ("disk_hits", "disk_misses", "disk_writes", "disk_corrupt",
              "serialize_skips", "retraces", "bucketed_calls",
              "pad_ratio", "enabled"):
        assert k in counters, k
    feats = runtime.Features()
    assert feats.is_enabled("COMPILE_CACHE")


def test_profiler_dump_includes_compile_cache_samples(tmp_path):
    profiler.set_config(filename=str(tmp_path / "prof.json"))
    profiler.start()
    nd.tanh(nd.ones((2, 2)))
    profiler.stop()
    out = profiler.dump()
    import json

    with open(out) as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]}
    assert any(n.startswith("compile_cache/") for n in names)
    profiler.set_config(filename="profile.json")


# ---------------------------------------------------------------------------
# CountedLRUCache thread-safety (satellite): three caches now share it

def test_lru_cache_thread_safety():
    cache = CountedLRUCache(maxsize=32)
    errors = []
    barrier = threading.Barrier(8)
    N = 400

    def worker(tid):
        try:
            barrier.wait()
            for i in range(N):
                k = (tid * 7 + i) % 48  # cross-thread key overlap + evict
                if cache.lookup(k) is None:
                    cache.insert(k, ("v", tid, i))
                if i % 97 == 0:
                    cache.remove((tid + i) % 48)
                if i % 131 == 0:
                    cache.stats()
        except Exception as e:  # pragma: no cover - failure surface
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    s = cache.stats()
    assert s["size"] <= 32
    assert s["hits"] + s["misses"] == 8 * N
    # the OrderedDict survived concurrent mutation: lookups still work
    cache.insert("probe", 1)
    assert cache.lookup("probe") == 1
