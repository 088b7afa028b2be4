"""Async training pipeline (mxnet_tpu/pipeline/): DeviceFeed prefetch,
dispatch-as-ready gradient all-reduce, async kvstore pushes, counters.

Exception/shutdown paths get explicit coverage: a prefetch worker that
raises mid-epoch must propagate to the training loop without deadlock,
close()/reset() must drain a blocked worker, and the pipeline must keep
working (inline) after engine.close() — the round-10 batcher contract.
"""
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu import pipeline as pl
from mxnet_tpu.gluon.parameter import Parameter
from mxnet_tpu.io import NDArrayIter
from mxnet_tpu.pipeline import AsyncGradReducer, DeviceFeed


def _arrays(n=8, d=4):
    X = onp.arange(n * d, dtype="f").reshape(n, d)
    Y = onp.arange(n, dtype="f")
    return X, Y


# ---------------------------------------------------------------------------
# DeviceFeed


def test_device_feed_preserves_order_and_content():
    X, Y = _arrays()
    it = NDArrayIter(nd.array(X), nd.array(Y), batch_size=4)
    feed = DeviceFeed(it, depth=2)
    batches = list(feed)
    assert len(batches) == 2
    onp.testing.assert_array_equal(batches[0].data[0].asnumpy(), X[:4])
    onp.testing.assert_array_equal(batches[1].data[0].asnumpy(), X[4:])
    onp.testing.assert_array_equal(batches[0].label[0].asnumpy(), Y[:4])
    feed.reset()
    again = [b.data[0].asnumpy() for b in feed]
    assert len(again) == 2
    onp.testing.assert_array_equal(again[0], X[:4])


def test_device_feed_stages_generator_tuples_onto_device():
    def gen():
        for i in range(3):
            yield (onp.full((2, 2), float(i), "f"),
                   onp.full((2,), float(i), "f"))

    feed = DeviceFeed(gen(), depth=2)
    out = list(feed)
    assert len(out) == 3
    for i, (x, y) in enumerate(out):
        assert isinstance(x, nd.NDArray) and isinstance(y, nd.NDArray)
        onp.testing.assert_array_equal(x.asnumpy(),
                                       onp.full((2, 2), float(i), "f"))


def test_device_feed_depth_bounds_staging():
    """At most ``depth`` batches are staged (queued) plus one mid-stage
    in the worker — prefetch must not balloon into buffering the whole
    epoch."""
    produced = []

    def gen():
        for i in range(16):
            produced.append(i)
            yield onp.full((2,), float(i), "f")

    feed = DeviceFeed(gen(), depth=2)
    first = next(feed)  # starts the worker
    time.sleep(0.3)  # give an unbounded worker time to run away
    # consumed 1; queue holds <= 2; worker holds <= 1 mid-stage
    assert len(produced) <= 1 + 2 + 1, produced
    onp.testing.assert_array_equal(first.asnumpy(), [0.0, 0.0])
    feed.close()


def test_device_feed_depth_zero_is_synchronous_passthrough():
    """MXNET_DEVICE_PREFETCH=0: no thread, same values bit-for-bit."""
    X, Y = _arrays()
    it = NDArrayIter(nd.array(X), nd.array(Y), batch_size=4)
    feed = DeviceFeed(it, depth=0)
    n0 = threading.active_count()
    batches = list(feed)
    assert threading.active_count() == n0  # no worker spawned
    assert len(batches) == 2
    assert batches[0].data[0].asnumpy().tobytes() == X[:4].tobytes()


def test_device_feed_depth_from_env(monkeypatch):
    monkeypatch.setenv("MXNET_DEVICE_PREFETCH", "5")
    assert pl.prefetch_depth() == 5
    feed = DeviceFeed([onp.zeros((1,), "f")])
    assert feed._depth == 5
    monkeypatch.setenv("MXNET_DEVICE_PREFETCH", "0")
    assert pl.prefetch_depth() == 0
    assert not pl.pipeline_enabled()
    monkeypatch.delenv("MXNET_DEVICE_PREFETCH")
    assert pl.pipeline_enabled()
    feed.close()


def test_device_feed_worker_exception_propagates_without_deadlock():
    """A source that raises mid-epoch surfaces the ORIGINAL exception in
    the consumer at next(); the worker thread exits; the feed can be
    re-armed afterwards."""

    def gen():
        yield onp.ones((2,), "f")
        yield onp.ones((2,), "f") * 2
        raise ValueError("decode exploded")

    feed = DeviceFeed(gen(), depth=2)
    got = []
    with pytest.raises(ValueError, match="decode exploded"):
        for b in feed:
            got.append(b)
    assert len(got) == 2
    with pytest.raises(StopIteration):
        next(feed)  # failed pass is over, not wedged
    assert pl.pipeline_counters()["feed_errors"] >= 1
    feed.close()


def test_device_feed_close_unblocks_full_queue():
    """close() mid-epoch drains a worker blocked on the bounded queue —
    no deadlock, idempotent, and usable as a context manager."""

    def endless():
        i = 0
        while True:
            yield onp.full((2,), float(i), "f")
            i += 1

    with DeviceFeed(endless(), depth=1) as feed:
        next(feed)
        time.sleep(0.1)  # let the worker wedge itself against the cap
    feed.close()  # second close is a no-op
    # a fresh pass works after close
    assert float(next(iter(feed)).asnumpy()[0]) >= 0.0
    feed.close()


def test_device_feed_survives_engine_close():
    """engine.close() mid-epoch must not wedge the pipeline: DataLoader
    collection ops run inline post-close and the feed drains cleanly
    (the round-10 batcher drain contract)."""
    from mxnet_tpu import engine as _engine
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    try:
        eng = _engine.Engine()
    except RuntimeError:
        pytest.skip("native engine library unavailable")
    orig = _engine._engine
    _engine._engine = eng
    try:
        X = onp.arange(12 * 2, dtype="f").reshape(12, 2)
        loader = DataLoader(ArrayDataset(nd.array(X)), batch_size=4,
                            num_workers=1)
        feed = DeviceFeed(loader, depth=2)
        it = iter(feed)
        got = [next(it).asnumpy()]
        eng.close()  # mid-epoch shutdown
        got.extend(b.asnumpy() for b in it)
        assert len(got) == 3
        onp.testing.assert_array_equal(onp.concatenate(got), X)
        feed.close()
    finally:
        _engine._engine = orig


def test_device_feed_counters_hits_and_stalls():
    pl.reset_pipeline_counters()

    def slow():
        for i in range(3):
            time.sleep(0.05)
            yield onp.full((2,), float(i), "f")

    list(DeviceFeed(slow(), depth=2))
    c = pl.pipeline_counters()
    assert c["prefetch_batches"] == 3
    assert c["prefetch_stalls"] >= 1  # source slower than consumer
    assert c["prefetch_stall_s"] > 0
    assert c["engine_idle_s"] == c["prefetch_stall_s"]

    def fast():
        for i in range(4):
            yield onp.full((2,), float(i), "f")

    pl.reset_pipeline_counters()
    feed = DeviceFeed(fast(), depth=4)
    next(feed)
    time.sleep(0.2)  # worker stages everything ahead
    for b in feed:
        pass
    c = pl.pipeline_counters()
    assert c["prefetch_hits"] >= 3  # the rest were already staged
    assert 0.0 <= c["overlap_ratio"] <= 1.0


# ---------------------------------------------------------------------------
# dispatch-as-ready gradient all-reduce


def _make_params(n, shape=(4, 4), dtype="float32"):
    params = []
    for i in range(n):
        p = Parameter(f"gs_p{i}", shape=shape, dtype=dtype)
        p.initialize()
        p.set_data(nd.array(onp.full(shape, float(i + 1), dtype)))
        params.append(p)
    return params


def _backward_over(params, scale=2.0):
    with autograd.record():
        loss = sum(((p.data() * scale).sum() for p in params),
                   nd.array(0.0))
    loss.backward()


def test_grad_ready_hook_fires_in_order_and_unregisters():
    params = _make_params(3)
    seen = []
    remove = autograd.register_grad_ready_hook(
        lambda arr: seen.append(id(arr)))
    try:
        _backward_over(params)
        assert set(seen) >= {id(p._ndarray) for p in params}
    finally:
        remove()
    seen.clear()
    _backward_over(params)
    assert seen == []  # unregistered
    remove()  # idempotent


def test_reducer_dispatches_buckets_during_backward():
    pl.reset_pipeline_counters()
    params = _make_params(6)
    calls = []

    def fake_reduce(flat):
        calls.append(int(flat.size))
        return flat * 2.0

    itemsize = 4 * 4 * 4
    red = AsyncGradReducer(params, bucket_bytes=2 * itemsize,
                           reduce_fn=fake_reduce).attach()
    try:
        _backward_over(params)
        assert len(calls) == 3  # 6 params / 2-param buckets, mid-backward
        grads = [p.grad() for p in params]
        assert red.flush(grads) == 0  # everything was already reduced
        for g in grads:  # d(2p)/dp = 2, then the fake reduce doubles
            onp.testing.assert_array_equal(g.asnumpy(),
                                           onp.full((4, 4), 4.0, "f"))
        c = pl.pipeline_counters()
        assert c["grad_buckets"] == 3
        assert c["grad_async_grads"] == 6
        assert c["grad_flush_grads"] == 0
    finally:
        red.detach()


def test_reducer_flush_covers_partial_buckets_and_missing_grads():
    params = _make_params(3)
    calls = []

    def fake_reduce(flat):
        calls.append(int(flat.size))
        return flat + 1.0

    # cap bigger than the whole group: nothing dispatches mid-backward
    red = AsyncGradReducer(params, bucket_bytes=1 << 30,
                           reduce_fn=fake_reduce).attach()
    try:
        _backward_over(params)
        assert calls == []
        grads = [p.grad() for p in params]
        red.flush(grads)
        assert len(calls) >= 1  # partial bucket dispatched at flush
        for g in grads:
            onp.testing.assert_array_equal(g.asnumpy(),
                                           onp.full((4, 4), 3.0, "f"))
    finally:
        red.detach()


def test_reducer_respeculates_on_double_backward():
    """Gradient accumulation (a second backward before step) re-signals
    the hook — the reducer re-speculates over the ACCUMULATED buffer,
    so flush binds reduce(final value), never a half-reduced one."""
    params = _make_params(2)
    red = AsyncGradReducer(params, bucket_bytes=1,  # dispatch per grad
                           reduce_fn=lambda f: f * 10.0).attach()
    try:
        _backward_over(params, scale=1.0)   # speculative reduce of 1.0
        _backward_over(params, scale=3.0)   # overwrite; hook re-fires
        grads = [p.grad() for p in params]
        red.flush(grads)
        for g in grads:  # reduce(3.0), NOT reduce(1.0) or raw 3.0
            onp.testing.assert_array_equal(g.asnumpy(),
                                           onp.full((4, 4), 30.0, "f"))
    finally:
        red.detach()


def test_reducer_discards_stale_speculation_on_manual_grad_edit():
    """A grad modified AFTER its speculative dispatch (hand-rolled
    clipping, custom hooks) invalidates the speculation: flush must
    detect the buffer changed and re-reduce the current value."""
    pl.reset_pipeline_counters()
    params = _make_params(2)
    red = AsyncGradReducer(params, bucket_bytes=1,
                           reduce_fn=lambda f: f * 10.0).attach()
    try:
        _backward_over(params, scale=1.0)   # speculative reduce of 1.0
        grads = [p.grad() for p in params]
        for g in grads:  # post-backward manual edit (no hook fires)
            g._data = g.data * 5.0
        red.flush(grads)
        for g in grads:  # reduce(5.0) = 50, NOT stale reduce(1.0) = 10
            onp.testing.assert_array_equal(g.asnumpy(),
                                           onp.full((4, 4), 50.0, "f"))
        assert pl.pipeline_counters()["grad_stale_discards"] >= 2
    finally:
        red.detach()


def test_reducer_knob_off_is_noop_per_round(monkeypatch):
    params = _make_params(2)
    calls = []
    red = AsyncGradReducer(params, bucket_bytes=1,
                           reduce_fn=lambda f: calls.append(1) or f)
    red.attach()
    try:
        monkeypatch.setenv("MXNET_ASYNC_GRAD_SYNC", "0")
        _backward_over(params)
        assert calls == []  # hook no-ops for the whole round
    finally:
        red.detach()


def test_reducer_abandon_rearms_after_knob_flip(monkeypatch):
    """Knob flipped off between backward and step(): the trainer
    abandons the round (speculation discarded, per-round knob read
    re-armed) so later backwards stop dispatching collectives — the
    knob is a true fallback switch at any point in the round."""
    params = _make_params(2)
    calls = []
    red = AsyncGradReducer(params, bucket_bytes=1,
                           reduce_fn=lambda f: calls.append(1) or f)
    red.attach()
    try:
        monkeypatch.setenv("MXNET_ASYNC_GRAD_SYNC", "1")
        _backward_over(params)
        assert calls and red._spec  # speculative dispatch happened
        monkeypatch.setenv("MXNET_ASYNC_GRAD_SYNC", "0")
        red.abandon()  # what Trainer._async_reducer does when off
        assert red._spec == {} and red._pending == {}
        calls.clear()
        _backward_over(params)  # knob re-read: hook must no-op now
        assert calls == []
    finally:
        red.detach()


def test_trainer_abandons_reducer_when_knob_flips_off(monkeypatch):
    """End-to-end version of the nastiest toggle: knob ON during
    backward, OFF by step() time. The trainer must abandon the round
    (not leave the hook armed dispatching collectives forever) and the
    params must match an always-off run."""
    pl.reset_pipeline_counters()
    mx.random.seed(13)
    params = _make_params(3)
    trainer = mx.gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                               kvstore="dist_sync")
    monkeypatch.setenv("MXNET_ASYNC_GRAD_SYNC", "1")
    _backward_over(params, scale=1.0)
    trainer.step(1)  # round 0: reducer created + hook armed
    _backward_over(params, scale=2.0)  # round 1: hook speculates...
    monkeypatch.setenv("MXNET_ASYNC_GRAD_SYNC", "0")  # ...flip mid-round
    trainer.step(1)
    red = trainer._grad_reducer
    assert red is not None and red._spec == {} and red._pending == {}
    buckets_after_flip = pl.pipeline_counters()["grad_buckets"]
    for step in range(2, 4):  # knob stays off: hook must stay quiet
        _backward_over(params, scale=float(step + 1))
        trainer.step(1)
    assert pl.pipeline_counters()["grad_buckets"] == buckets_after_flip

    def run_off():
        mx.random.seed(13)
        ps = _make_params(3)
        tr = mx.gluon.Trainer(ps, "sgd", {"learning_rate": 0.1},
                              kvstore="dist_sync")
        for step in range(4):
            _backward_over(ps, scale=float(step + 1))
            tr.step(1)
        return [p.data().asnumpy().tobytes() for p in ps]

    assert [p.data().asnumpy().tobytes() for p in params] == run_off()


def test_trainer_distributed_async_grad_sync_parity(monkeypatch):
    """Single-process 'dist' trainer: the async path must produce the
    exact grads/params the coalesced-at-step path does, and wire the
    reducer in only when the knob is on."""

    def run(async_on):
        monkeypatch.setenv("MXNET_ASYNC_GRAD_SYNC",
                           "1" if async_on else "0")
        mx.random.seed(11)
        params = _make_params(4)
        trainer = mx.gluon.Trainer(params, "sgd",
                                   {"learning_rate": 0.1},
                                   kvstore="dist_sync")
        for step in range(3):
            _backward_over(params, scale=float(step + 1))
            trainer.step(1)
        return ([p.data().asnumpy().tobytes() for p in params],
                trainer._grad_reducer)

    sync_params, r0 = run(False)
    async_params, r1 = run(True)
    assert sync_params == async_params
    assert r0 is None and r1 is not None
    assert r1._unhook is not None


# ---------------------------------------------------------------------------
# async kvstore


def test_kvstore_async_push_overlaps_and_flushes(monkeypatch):
    monkeypatch.setenv("MXNET_KVSTORE_ASYNC", "1")
    pl.reset_pipeline_counters()
    kv = mx.kvstore.create("local")
    kv.init("w", nd.zeros((4,)))
    gate = threading.Event()
    applied = []

    def updater(key, grad, stored):
        gate.wait(5)
        applied.append(key)
        stored._data = (stored + grad).data

    kv.set_updater(updater)
    t0 = time.perf_counter()
    kv.push("w", nd.ones((4,)))  # must NOT block on the slow updater
    assert time.perf_counter() - t0 < 1.0
    assert applied == []  # still gated: push really was asynchronous
    gate.set()
    out = nd.zeros((4,))
    kv.pull("w", out=out)  # read-your-writes: flushes the pending push
    assert applied == ["w"]
    onp.testing.assert_array_equal(out.asnumpy(), onp.ones(4, "f"))
    assert pl.pipeline_counters()["kvstore_async_pushes"] >= 1


def test_kvstore_async_error_propagates_at_pull(monkeypatch):
    monkeypatch.setenv("MXNET_KVSTORE_ASYNC", "1")
    kv = mx.kvstore.create("local")
    kv.init("w", nd.zeros((2,)))

    def bad_updater(key, grad, stored):
        raise RuntimeError("updater exploded")

    kv.set_updater(bad_updater)
    kv.push("w", nd.ones((2,)))
    with pytest.raises(mx.MXNetError, match="updater exploded"):
        kv.pull("w", out=nd.zeros((2,)))


def test_kvstore_async_off_by_default():
    kv = mx.kvstore.create("local")
    assert kv._async_mode is False


# ---------------------------------------------------------------------------
# DataLoader prefetch/timeout satellite


def test_dataloader_prefetch_env_default_and_override(monkeypatch):
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    ds = ArrayDataset(nd.array(onp.arange(8, dtype="f")))
    assert DataLoader(ds, batch_size=2, num_workers=2)._prefetch == 4
    monkeypatch.setenv("MXNET_DATALOADER_PREFETCH", "7")
    assert DataLoader(ds, batch_size=2, num_workers=2)._prefetch == 7
    # an explicit constructor value always wins over the env knob
    assert DataLoader(ds, batch_size=2, num_workers=2,
                      prefetch=3)._prefetch == 3


def test_dataloader_prefetch_depth_semantics():
    """Any depth yields the same batches in the same order — depth is a
    pipeline knob, never a semantics knob — and the pipelined iterator
    clamps depth >= 1 so prefetch=0 with workers cannot deadlock."""
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    X = onp.arange(10 * 3, dtype="f").reshape(10, 3)
    ds = ArrayDataset(nd.array(X))
    ref = [b.asnumpy().tobytes()
           for b in DataLoader(ds, batch_size=2, num_workers=0)]
    for depth in (0, 1, 4):
        got = [b.asnumpy().tobytes()
               for b in DataLoader(ds, batch_size=2, num_workers=2,
                                   prefetch=depth)]
        assert got == ref, depth


def test_dataloader_timeout_raises_instead_of_hanging():
    from mxnet_tpu.gluon.data import DataLoader

    class Glacial:
        def __len__(self):
            return 4

        def __getitem__(self, i):
            time.sleep(2)
            return nd.zeros((2,))

    loader = DataLoader(Glacial(), batch_size=2, num_workers=1,
                        timeout=0.2)
    with pytest.raises(RuntimeError, match="timeout"):
        next(iter(loader))


def test_dataloader_timeout_disabled_with_nonpositive():
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    ds = ArrayDataset(nd.array(onp.arange(4, dtype="f")))
    assert DataLoader(ds, batch_size=2, num_workers=1,
                      timeout=0)._timeout is None
    assert DataLoader(ds, batch_size=2, num_workers=1,
                      timeout=None)._timeout is None
    assert DataLoader(ds, batch_size=2, num_workers=1,
                      timeout=60)._timeout == 60.0


# ---------------------------------------------------------------------------
# an epoch through the feed is the synchronous loop, bit for bit


def _train_epoch(depth, amp, poison_at=None, steps=6):
    """One seeded epoch of a toy MLP under the fused Trainer.step;
    ``depth=None`` is the classic loop (nd.array on the step thread, the
    loss read back every step), otherwise the batches come through
    ``DeviceFeed(depth)`` and the losses stay on the device until the
    end. Returns (param bytes, losses, loss-scale trace)."""
    from mxnet_tpu import gluon
    from mxnet_tpu.contrib.amp.loss_scaler import LossScaler
    from mxnet_tpu.gluon import nn

    mx.random.seed(7)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(10))
    net.initialize()
    net(nd.zeros((1, 8)))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9})
    if amp:
        trainer._amp_loss_scaler = LossScaler(init_scale=2.0 ** 10,
                                              scale_window=64)
    rs = onp.random.RandomState(123)
    raw = [(rs.rand(4, 8).astype("f"), rs.rand(4, 10).astype("f"))
           for _ in range(steps)]
    if poison_at is not None:
        raw[poison_at] = (onp.full((4, 8), onp.inf, "f"),
                          raw[poison_at][1])
    losses, scales = [], []

    def step(xb, yb):
        with autograd.record():
            loss = ((net(xb) - yb) ** 2).mean()
        loss.backward()
        trainer.step(4)
        if amp:
            scales.append(trainer._amp_loss_scaler.loss_scale)
        return loss

    if depth is None:
        for x, y in raw:
            losses.append(float(step(nd.array(x), nd.array(y)).asnumpy()))
    else:
        feed = DeviceFeed(iter(raw), depth=depth)
        try:
            on_device = [step(xb, yb) for xb, yb in feed]
        finally:
            feed.close()
        losses = [float(l.asnumpy()) for l in on_device]
    params = [p.data().asnumpy().tobytes()
              for p in net.collect_params().values()]
    return params, onp.asarray(losses, "f").tobytes(), scales


@pytest.mark.parametrize("depth,amp", [(2, False), (0, False), (2, True)],
                         ids=["prefetch2", "depth0_fallback",
                              "amp_skip_episode"])
def test_epoch_through_feed_is_bitwise_the_sync_loop(depth, amp):
    """Final parameters, the per-step loss trace and (under AMP, through
    an all-inf batch that forces a fused skip-step) the loss-scale trace
    are identical whether batches are staged ahead by the feed's worker
    or placed on the step thread."""
    poison = 3 if amp else None
    p_sync, l_sync, s_sync = _train_epoch(None, amp, poison)
    p_feed, l_feed, s_feed = _train_epoch(depth, amp, poison)
    assert p_feed == p_sync
    assert l_feed == l_sync
    assert s_feed == s_sync
    if amp:  # the episode really happened: the scale backed off once
        assert any(b < a for a, b in zip(s_sync, s_sync[1:]))


# ---------------------------------------------------------------------------
# observability wiring


def test_profiler_and_runtime_surfaces(monkeypatch, tmp_path):
    import json

    from mxnet_tpu import profiler, runtime

    pl.reset_pipeline_counters()
    list(DeviceFeed([onp.zeros((2,), "f")] * 3, depth=2))
    c = profiler.pipeline_counters()
    assert c["prefetch_batches"] == 3
    assert {"prefetch_hits", "prefetch_stalls", "engine_idle_s",
            "overlap_ratio", "grad_buckets",
            "kvstore_async_pushes"} <= set(c)
    profiler.set_config(filename=str(tmp_path / "prof.json"))
    try:
        fname = profiler.dump()
        with open(fname) as f:
            names = {e["name"] for e in json.load(f)["traceEvents"]}
        assert "pipeline/prefetch_batches" in names
        assert "pipeline/overlap_ratio" in names
    finally:
        profiler.set_config(filename="profile.json")

    feats = runtime.Features()
    assert feats.is_enabled("PIPELINE")
    monkeypatch.setenv("MXNET_DEVICE_PREFETCH", "0")
    assert not runtime.Features().is_enabled("PIPELINE")
