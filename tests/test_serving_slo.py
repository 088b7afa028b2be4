"""SLO-aware serving: priority queues, deadline enforcement at every
queue exit, and admission control (tier-1, no sockets).

Covers: _ClassQueues priority ordering + per-class bounds + sentinel
semantics, RollingHistogram window recovery, AdmissionController
graduated shed thresholds (queue and latency signals), the
``serving_admission`` fault seam (forces the shed path, never for
critical), ShedLoad's Retry-After surface, and per-class
counter/latency observability."""
import queue
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, serving
from mxnet_tpu.gluon import nn
from mxnet_tpu.resilience import faults
from mxnet_tpu.serving import admission as adm
from mxnet_tpu.serving import batcher as bat_mod
from mxnet_tpu.serving import metrics as met

nd = mx.nd


def _mlp(seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    with autograd.pause(train_mode=False):
        net(nd.zeros((1, 8)))
    return net


def _session(net=None, **kw):
    return serving.InferenceSession(net or _mlp(),
                                    input_shapes=[(1, 8)],
                                    buckets=[1, 2, 4], **kw)


def _ref(net, x):
    with autograd.pause(train_mode=False):
        return net(nd.array(x)).asnumpy()


@pytest.fixture(autouse=True)
def _fresh_counters():
    serving.reset_serving_counters()
    yield
    serving.reset_serving_counters()


def _req(cls, deadline=None):
    return bat_mod._Request([onp.zeros((1, 8), "float32")], 1,
                            deadline, cls)


# ---------------------------------------------------------------------------
# _ClassQueues

def test_class_queue_pops_highest_priority_first():
    q = bat_mod._ClassQueues(4)
    q.put_nowait(_req("best_effort"))
    q.put_nowait(_req("standard"))
    q.put_nowait(_req("critical"))
    q.put_nowait(_req("best_effort"))
    order = [q.get_nowait().slo_class for _ in range(4)]
    assert order == ["critical", "standard", "best_effort",
                     "best_effort"]
    with pytest.raises(queue.Empty):
        q.get_nowait()


def test_class_queue_bounds_are_per_class():
    q = bat_mod._ClassQueues(2)
    assert q.maxsize == 2
    assert q.capacity() == 2 * len(met.SLO_CLASSES)
    q.put_nowait(_req("best_effort"))
    q.put_nowait(_req("best_effort"))
    with pytest.raises(queue.Full):
        q.put_nowait(_req("best_effort"))
    # a full best_effort lane does not block the protected class
    q.put_nowait(_req("critical"))
    assert q.qsize() == 3
    assert q.qsize_by_class() == {"critical": 1, "standard": 0,
                                  "best_effort": 2}


def test_class_queue_sentinel_waits_for_data_lanes():
    """Control-lane sentinels (close()) are delivered only once every
    data lane is empty — accepted work always drains first."""
    q = bat_mod._ClassQueues(4)
    q.put_nowait(_req("best_effort"))
    q.put(bat_mod._STOP)  # control lane is unbounded, never Full
    assert q.get_nowait().slo_class == "best_effort"
    assert q.get_nowait() is bat_mod._STOP


# ---------------------------------------------------------------------------
# RollingHistogram

def test_rolling_histogram_forgets_an_aged_spike():
    h = met.RollingHistogram(window_s=20.0)
    t = 1000.0
    for _ in range(100):
        h.observe(0.9, now=t)  # the overload spike
    assert h.quantile(0.99, now=t) > 0.5
    # spike ages out: two frame rotations later only fresh traffic
    # remains — a cumulative histogram would report ~0.9 forever
    t += 25.0
    for _ in range(100):
        h.observe(0.002, now=t)
    assert h.quantile(0.99, now=t) < 0.01


def test_rolling_histogram_merges_adjacent_frames():
    h = met.RollingHistogram(window_s=20.0)
    t = 50.0
    h.observe(0.9, now=t)
    # one rotation (< a full frame late): previous frame still counts
    t += 11.0
    h.observe(0.001, now=t)
    assert h.total == 2
    assert h.quantile(0.99, now=t) > 0.5


# ---------------------------------------------------------------------------
# admission control

def test_normalize_class():
    assert adm.normalize_class(None) == "standard"
    assert adm.normalize_class("critical") == "critical"
    with pytest.raises(ValueError, match="unknown SLO class"):
        adm.normalize_class("vip")


class _FakeBatcher:
    def __init__(self, depth=0, capacity=100):
        self._depth, self._cap = depth, capacity

    def qsize(self):
        return self._depth

    def queue_capacity(self):
        return self._cap


def test_admission_graduated_shed_thresholds():
    """Queue signal: best_effort sheds at the full knob, standard at
    half, critical never — and ShedLoad is a ServerBusy carrying
    Retry-After."""
    fake = _FakeBatcher(depth=95, capacity=100)  # headroom 0.05
    ctl = adm.AdmissionController(fake, slo_ms=100.0,
                                  shed_headroom=0.15,
                                  retry_after_ms=400.0, enabled=True)
    try:
        ctl.check("critical")  # protected: backpressure only
        with pytest.raises(serving.ShedLoad) as ei:
            ctl.check("best_effort")
        assert isinstance(ei.value, serving.ServerBusy)
        assert ei.value.retry_after_s == pytest.approx(0.4)
        with pytest.raises(serving.ShedLoad):
            ctl.check("standard")  # 0.05 < 0.075 too
        # half-full: only best_effort is at risk
        fake._depth = 90  # headroom 0.10: best_effort sheds
        with pytest.raises(serving.ShedLoad):
            ctl.check("best_effort")
        ctl.check("standard")
        snap = ctl.snapshot()
        assert snap["enabled"] and snap["shedding"] == ["best_effort"]
        assert snap["queue_headroom"] == pytest.approx(0.10)
        assert set(snap["p99_ms"]) == set(met.SLO_CLASSES)
    finally:
        ctl.close()


def test_admission_latency_signal_protects_top_class():
    """Latency signal: the rolling p99 of the highest-priority class
    WITH TRAFFIC drives headroom — a blown critical p99 sheds
    best_effort even with empty queues."""
    for _ in range(50):
        met.METRICS.observe_request(0.098, slo_class="critical")
    ctl = adm.AdmissionController(_FakeBatcher(), slo_ms=100.0,
                                  shed_headroom=0.15, enabled=True)
    try:
        assert ctl.headroom() < 0.15
        with pytest.raises(serving.ShedLoad):
            ctl.check("best_effort")
        ctl.check("critical")
        assert met.METRICS.slo_headroom() == ctl.headroom()
    finally:
        ctl.close()


def test_admission_fault_forces_shed_but_never_critical():
    """The serving_admission seam: an armed plan forces the shed path
    for sheddable classes; the protected class never force-sheds."""
    sess = _session()
    bat = serving.DynamicBatcher(sess, max_batch_size=4,
                                 max_latency_ms=1.0)
    x = onp.random.RandomState(0).rand(1, 8).astype("float32")
    try:
        with faults.inject("serving_admission", every=1):
            with pytest.raises(serving.ShedLoad, match="fault-injected"):
                bat.submit(x, slo_class="best_effort")
            with pytest.raises(serving.ShedLoad):
                bat.submit(x, slo_class="standard")
            out = bat.submit(x, slo_class="critical").result(timeout=30)
        assert out.shape == (1, 4)
        stats = serving.serving_stats()
        assert stats["shed"] == 2
        assert stats["shed:best_effort"] == 1
        assert stats["shed:standard"] == 1
        assert stats["shed_rate"] == pytest.approx(2 / 3, abs=1e-3)
    finally:
        bat.close()


def test_admission_disabled_is_plain_backpressure():
    """admission=False: no shed even with the fault armed — the
    round-10 FIFO-with-backpressure behavior."""
    bat = serving.DynamicBatcher(_session(), max_batch_size=4,
                                 max_latency_ms=1.0, admission=False)
    x = onp.random.RandomState(1).rand(1, 8).astype("float32")
    try:
        with faults.inject("serving_admission", every=1):
            out = bat.submit(x, slo_class="best_effort").result(
                timeout=30)
        assert out.shape == (1, 4)
        assert serving.serving_stats()["shed"] == 0
    finally:
        bat.close()


# ---------------------------------------------------------------------------
# deadlines at the queue exits

class _GatedSession:
    """Real session whose predict blocks on a gate — pins the worker
    so queued requests age deterministically."""

    def __init__(self, inner):
        self._inner = inner
        self.gate = threading.Event()
        self.gate.set()
        self.exec_rows = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def predict(self, *arrs):
        self.gate.wait(30)
        self.exec_rows.append(sum(a.shape[0] for a in arrs[:1]))
        return self._inner.predict(*arrs)


def test_expired_request_never_occupies_a_batch_slot():
    """A request that out-waits its deadline in the queue gets
    RequestTimeout at the queue exit and is NEVER executed — the batch
    slot goes to live work."""
    net = _mlp()
    sess = _GatedSession(_session(net))
    bat = serving.DynamicBatcher(sess, max_batch_size=4,
                                 max_latency_ms=1.0)
    xs = [onp.random.RandomState(i).rand(1, 8).astype("float32")
          for i in range(3)]
    try:
        sess.gate.clear()
        fa = bat.submit(xs[0], timeout_ms=30_000, slo_class="critical")
        time.sleep(0.15)  # worker is now pinned inside predict(a)
        fb = bat.submit(xs[1], timeout_ms=40, slo_class="standard")
        fc = bat.submit(xs[2], timeout_ms=30_000,
                        slo_class="best_effort")
        time.sleep(0.15)  # b expires while queued behind the gate
        sess.gate.set()
        assert onp.array_equal(fa.result(timeout=30), _ref(net, xs[0]))
        with pytest.raises(serving.RequestTimeout, match="expired"):
            fb.result(timeout=30)
        assert onp.array_equal(fc.result(timeout=30), _ref(net, xs[2]))
    finally:
        bat.close()
    assert sess.exec_rows == [1, 1], \
        "the expired request must never reach the session"
    stats = serving.serving_stats()
    assert stats["timeouts"] == 1
    assert stats["timeouts:standard"] == 1
    assert stats["deadline_met"] == 2
    assert stats["failures:standard"] == 1
    assert stats["responses:critical"] == 1


def test_close_drain_honors_deadlines_per_class():
    """The close() drain path is also a queue exit: expired requests
    fail with RequestTimeout, live ones still execute."""
    net = _mlp()
    sess = _GatedSession(_session(net))
    bat = serving.DynamicBatcher(sess, max_batch_size=4,
                                 max_latency_ms=1.0)
    x = onp.random.RandomState(7).rand(1, 8).astype("float32")
    try:
        sess.gate.clear()
        fa = bat.submit(x, timeout_ms=30_000, slo_class="critical")
        time.sleep(0.15)
        fb = bat.submit(x, timeout_ms=40, slo_class="best_effort")
        fc = bat.submit(x, timeout_ms=30_000, slo_class="standard")
        time.sleep(0.15)
    finally:
        sess.gate.set()
        bat.close()  # drains every accepted request
    assert onp.array_equal(fa.result(timeout=1), _ref(net, x))
    with pytest.raises(serving.RequestTimeout):
        fb.result(timeout=1)
    assert onp.array_equal(fc.result(timeout=1), _ref(net, x))


def test_backed_up_queue_sheds_best_effort_and_never_critical():
    """Overload against a real queue, no fault seam: with the worker
    pinned the lanes fill, queue headroom falls under the shed knob,
    best_effort is refused at the door while critical keeps being
    admitted, and once the worker runs again every critical request is
    answered with its own rows."""
    net = _mlp()
    sess = _GatedSession(_session(net))
    bat = serving.DynamicBatcher(sess, max_batch_size=4, max_queue=8,
                                 max_latency_ms=1.0, timeout_ms=60_000)
    ctl = bat.admission
    xs = [onp.random.RandomState(i).rand(1, 8).astype("float32")
          for i in range(9)]
    critical = []
    try:
        sess.gate.clear()
        critical.append(bat.submit(xs[0], slo_class="critical"))
        time.sleep(0.15)  # the worker is pinned inside predict
        # 7 a lane = 21 of 24 slots: headroom 0.125, between the
        # best_effort and the standard thresholds
        for i in range(1, 8):
            critical.append(bat.submit(xs[i], slo_class="critical"))
            bat.submit(xs[i], slo_class="standard")
            bat.submit(xs[i], slo_class="best_effort")
        assert ctl.shed_threshold("standard") < ctl.headroom() \
            < ctl.shed_threshold("best_effort")
        with pytest.raises(serving.ShedLoad):
            bat.submit(xs[8], slo_class="best_effort")
        critical.append(bat.submit(xs[8], slo_class="critical"))
    finally:
        sess.gate.set()
        bat.close()
    for fut, x in zip(critical, xs):  # rows of a batch of 4 against a
        # batch of 1: float32 agreement, not bits
        assert onp.allclose(fut.result(timeout=1), _ref(net, x),
                            rtol=1e-5, atol=1e-6)
    stats = serving.serving_stats()
    assert stats["shed:best_effort"] == 1
    assert stats.get("shed:critical", 0) == 0
    assert stats.get("failures:critical", 0) == 0
    assert stats["responses:critical"] == 9


# ---------------------------------------------------------------------------
# observability

def test_per_class_counters_and_snapshot_keys():
    bat = serving.DynamicBatcher(_session(), max_batch_size=4,
                                 max_latency_ms=1.0)
    x = onp.random.RandomState(3).rand(1, 8).astype("float32")
    try:
        bat.submit(x, slo_class="critical").result(timeout=30)
        bat.submit(x).result(timeout=30)  # defaults to standard
        stats = serving.serving_stats()
        assert stats["requests:critical"] == 1
        assert stats["requests:standard"] == 1
        assert stats["responses:critical"] == 1
        assert stats["latency_p99_ms:critical"] > 0
        assert stats["goodput_rps"] > 0
        assert stats["shed_rate"] == 0.0
        assert 0.0 <= stats["slo_headroom"] <= 1.0
        text = met.prometheus_text()
        assert 'mxnet_serving_class_requests_total{slo_class=' \
            '"critical"} 1' in text
        assert "mxnet_serving_slo_headroom" in text
        assert "mxnet_serving_class_latency_p99_seconds" in text
    finally:
        bat.close()


def test_bump_class_unknown_folds_to_standard():
    met.METRICS.bump_class("requests", "not-a-class")
    assert serving.serving_stats()["requests:standard"] == 1
