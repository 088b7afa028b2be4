"""Stateful serving (round 16): SessionStateStore, continuous-batching
decode through DynamicBatcher, and the lifecycle around them.

Covers: step() bitwise-correctness across occupancy buckets vs the
hybridized reference block, mixed-length join/leave streams, session
affinity, TTL + LRU eviction under a tiny byte budget, the
``session_state_evict`` fault seam (blast radius: exactly one client),
close()-drain running in-flight streams to their step boundary and
checkpointing the states, canary promote migrating live sessions, the
decode counter family, and slot-headroom admission for new streams."""
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, serving
from mxnet_tpu.gluon import HybridBlock, nn, rnn
from mxnet_tpu.resilience import faults
from mxnet_tpu.resilience.checkpoint import CheckpointManager
from mxnet_tpu.serving import SessionEvicted, SessionStateStore

nd = mx.nd

N_IN, HID, N_OUT = 4, 6, 3


class _DecodeStep(HybridBlock):
    """GRU cell + projection head, the flat ``(x, h) -> (out, h')``
    state-threading contract a stateful session compiles."""

    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.cell = rnn.GRUCell(HID, input_size=N_IN)
            self.head = nn.Dense(N_OUT)

    def hybrid_forward(self, F, x, h):
        out, states = self.cell(x, [h])
        return self.head(out), states[0]


def _gru(seed=16):
    mx.random.seed(seed)
    net = _DecodeStep()
    net.initialize()
    with autograd.pause(train_mode=False):
        net(nd.zeros((1, N_IN)), nd.zeros((1, HID)))
    return net


def _session(net, **kw):
    kw.setdefault("buckets", [1, 2, 4])
    return serving.InferenceSession(net, input_shapes=[(1, N_IN)],
                                    state_shapes=[(HID,)], **kw)


def _unroll(net, xs, h0=None):
    """Offline reference chain over the HYBRIDIZED block — the bitwise
    ground truth the served step must match exactly."""
    net.hybridize()
    h = nd.array(h0) if h0 is not None else nd.zeros((1, HID))
    out = None
    with autograd.pause(train_mode=False):
        for x in xs:
            out, h = net(nd.array(x), h)
    return out.asnumpy(), h.asnumpy()


def _x(seed, rows=1):
    return onp.random.RandomState(seed).rand(rows, N_IN).astype("float32")


@pytest.fixture(autouse=True)
def _fresh_counters():
    serving.reset_serving_counters()
    yield
    serving.reset_serving_counters()
    faults.disarm()


# ---------------------------------------------------------------------------
# InferenceSession.step()

def test_step_bitwise_vs_hybridized_block_across_buckets():
    net = _gru()
    sess = _session(net)
    net.hybridize()
    rng = onp.random.RandomState(0)
    try:
        for occ in (1, 2, 3, 4):  # 3 pads to bucket 4: must stay
            x = rng.rand(occ, N_IN).astype("float32")  # row-bitwise
            h = rng.rand(occ, HID).astype("float32")
            out, news = sess.step(nd.array(x), states=[nd.array(h)])
            with autograd.pause(train_mode=False):
                ref_o, ref_h = net(nd.array(x), nd.array(h))
            assert onp.array_equal(out.asnumpy(), ref_o.asnumpy()), \
                f"output not bitwise at occupancy {occ}"
            assert onp.array_equal(news[0].asnumpy(), ref_h.asnumpy()), \
                f"new state not bitwise at occupancy {occ}"
        assert serving.serving_stats()["decode_steps"] == 4
    finally:
        sess.close()


def test_incremental_steps_bitwise_vs_replaying_the_prefix():
    """T steps threading the state forward end on the same bits as
    serving token T with no state kept: the whole prefix replayed from
    the zero state through the same executable; both match the offline
    unroll of the hybridized block."""
    net = _gru()
    sess = _session(net)
    xs = [_x(40 + t) for t in range(6)]
    try:
        states = [nd.zeros((1, HID))]
        for x in xs:
            inc, states = sess.step(nd.array(x), states=states)
        replay_h = [nd.zeros((1, HID))]  # token T with no state kept
        for x in xs:
            replay, replay_h = sess.step(nd.array(x), states=replay_h)
    finally:
        sess.close()
    ref_o, ref_h = _unroll(net, xs)
    assert onp.array_equal(inc.asnumpy(), replay.asnumpy())
    assert onp.array_equal(states[0].asnumpy(), replay_h[0].asnumpy())
    assert onp.array_equal(inc.asnumpy(), ref_o)
    assert onp.array_equal(states[0].asnumpy(), ref_h)


def test_step_and_predict_guardrails():
    net = _gru()
    sess = _session(net, buckets=[1, 2])
    try:
        with pytest.raises(mx.MXNetError, match="stateless"):
            sess.predict(_x(0))
        with pytest.raises(ValueError, match="occupancy"):
            sess.step(nd.zeros((3, N_IN)),
                      states=[nd.zeros((3, HID))])
    finally:
        sess.close()
    # a stateless session over the same block has no step()
    sess0 = serving.InferenceSession(
        net, input_shapes=[(1, N_IN), (1, HID)], buckets=[1])
    with pytest.raises(mx.MXNetError, match="stateful"):
        sess0.step(nd.zeros((1, N_IN)), states=[nd.zeros((1, HID))])


# ---------------------------------------------------------------------------
# SessionStateStore policies

def test_store_lru_eviction_under_byte_budget_and_affinity():
    # 4 fp32 scalars = 16 bytes/session; a 32-byte budget caps the
    # pool at 2 slots regardless of max_sessions
    store = SessionStateStore([(4,)], max_sessions=8, byte_budget=32,
                              ttl_s=0)
    assert store.num_slots == 2
    assert store.stats()["bytes_per_session"] == 16
    store.open("a")
    store.open("b")
    store.open("c")  # pool full: LRU ("a") reclaimed
    assert sorted(store.live_sessions()) == ["b", "c"]
    with pytest.raises(SessionEvicted, match="LRU"):
        store.acquire("a")
    with pytest.raises(mx.MXNetError, match="unknown"):
        store.acquire("ghost")
    assert serving.serving_stats()["evictions"] == 1
    # affinity: an in-flight slot is never double-acquired, and
    # eviction pressure reclaims around it
    rec = store.acquire("b")
    with pytest.raises(mx.MXNetError, match="affinity"):
        store.acquire("b")
    store.open("d")  # reclaims LRU "c", never in-flight "b"
    assert store.has("b") and store.has("d") and not store.has("c")
    store.release(rec)
    # an explicit re-open clears the tombstone: the client restarts
    store.open("c")
    rec2 = store.acquire("c")
    store.release(rec2)
    store.close()


def test_store_ttl_expiry_is_lazy_and_clean():
    store = SessionStateStore([(4,)], max_sessions=2, ttl_s=0.05)
    store.open("s", init_states=[onp.ones(4, "float32")])
    assert onp.array_equal(store.read("s")[0], onp.ones(4, "float32"))
    time.sleep(0.08)
    with pytest.raises(SessionEvicted, match="expired"):
        store.acquire("s")
    assert not store.has("s")
    store.close()


def test_store_state_shape_validation():
    store = SessionStateStore([(4,)], max_sessions=2)
    with pytest.raises(mx.MXNetError, match="row shape"):
        store.open("s", init_states=[onp.zeros((5,), "float32")])
    with pytest.raises(mx.MXNetError, match="state tensor"):
        store.open("s", init_states=[onp.zeros((4,), "float32")] * 2)
    store.close()
    with pytest.raises(mx.MXNetError, match="at least one"):
        SessionStateStore([])


# ---------------------------------------------------------------------------
# continuous batching through DynamicBatcher

def test_continuous_batching_mixed_length_streams_bitwise():
    net = _gru()
    sess = _session(net)
    bat = serving.DynamicBatcher(sess, max_batch_size=4,
                                 max_latency_ms=2.0, admission=False)
    rng = onp.random.RandomState(1)
    lengths = {"s0": 2, "s1": 5, "s2": 3}
    xs = {sid: [rng.rand(1, N_IN).astype("float32")
                for _ in range(n)] for sid, n in lengths.items()}
    try:
        # open-loop: each stream submits ALL its steps up front — the
        # per-session FIFO keeps order, streams join/leave the
        # executing batch at step boundaries
        futs = {sid: [bat.submit(x, session_id=sid, block=True)
                      for x in seq] for sid, seq in xs.items()}
        for sid, fs in futs.items():
            final = onp.asarray(fs[-1].result(timeout=60))
            ref_o, ref_h = _unroll(net, xs[sid])
            assert onp.array_equal(final, ref_o), \
                f"stream {sid} final output not bitwise vs unroll"
            # the server-side slot holds exactly the chain's state
            assert onp.array_equal(sess.state_store.read(sid)[0],
                                   ref_h[0])
        st = serving.serving_stats()
        assert st["decode_steps"] >= max(lengths.values())
        assert st["decode_steps"] <= sum(lengths.values())
        assert st["slot_occupancy"] == 3  # streams stay resident
    finally:
        bat.close()
        sess.close()


def test_fault_seam_evicts_exactly_one_client():
    """The ``session_state_evict`` chaos drill: one injected fire maps
    to SessionEvicted on every remaining step of exactly ONE stream —
    the other stream finishes bitwise-correct, and the evicted stream
    never silently restarts from zero state."""
    net = _gru()
    sess = _session(net)
    bat = serving.DynamicBatcher(sess, max_batch_size=2,
                                 max_latency_ms=1.0, admission=False)
    rng = onp.random.RandomState(2)
    xa = [rng.rand(1, N_IN).astype("float32") for _ in range(3)]
    xb = [rng.rand(1, N_IN).astype("float32") for _ in range(3)]
    try:
        # step 1 for both streams opens their slots cleanly
        bat.predict(xa[0], session_id="a")
        bat.predict(xb[0], session_id="b")
        with faults.inject("session_state_evict", at=1):
            fa = [bat.submit(x, session_id="a", block=True)
                  for x in xa[1:]]
            fb = [bat.submit(x, session_id="b", block=True)
                  for x in xb[1:]]
            # "a" re-joins first, so the armed acquire hits it: every
            # remaining step of that one stream fails retryably
            for f in fa:
                with pytest.raises(SessionEvicted, match="re-open"):
                    f.result(timeout=60)
            final_b = onp.asarray(fb[-1].result(timeout=60))
        assert faults.fire_counts()["session_state_evict"] == 1
        ref_b, _ = _unroll(net, xb)
        assert onp.array_equal(final_b, ref_b), \
            "the surviving stream must be untouched"
        assert not sess.state_store.has("a")
        assert serving.serving_stats()["evictions"] == 1
        # the client's explicit re-open clears the tombstone and the
        # stream restarts cleanly from step 0
        sess.state_store.open("a")
        out = onp.asarray(bat.predict(xa[0], session_id="a"))
        ref_a1, _ = _unroll(net, xa[:1])
        assert onp.array_equal(out, ref_a1)
    finally:
        bat.close()
        sess.close()


def test_close_drains_streams_to_boundary_and_checkpoints(tmp_path):
    """close() must EXECUTE every accepted step (streams advance to
    their boundary, nothing drops) and checkpoint the session states;
    a fresh process restores them and the streams resume bitwise."""
    net = _gru()
    sess = _session(net)
    mgr = CheckpointManager(str(tmp_path),
                            session_state=sess.state_store,
                            async_mode=False)
    bat = serving.DynamicBatcher(sess, max_batch_size=2,
                                 max_latency_ms=20.0, admission=False,
                                 state_checkpoint=mgr)
    rng = onp.random.RandomState(3)
    xs = {sid: [rng.rand(1, N_IN).astype("float32") for _ in range(3)]
          for sid in ("u", "v")}
    futs = [bat.submit(x, session_id=sid, block=True)
            for sid, seq in xs.items() for x in seq]
    bat.close()  # in-flight sequences run to their step boundary
    for f in futs:
        assert f.done(), "close() must drain accepted steps"
        f.result(timeout=0)
    refs = {sid: _unroll(net, seq) for sid, seq in xs.items()}
    for sid in xs:
        assert onp.array_equal(sess.state_store.read(sid)[0],
                               refs[sid][1][0])
    sess.close()

    # --- next process: restore and resume ---------------------------
    serving.reset_serving_counters()
    sess2 = _session(net)
    mgr2 = CheckpointManager(str(tmp_path),
                             session_state=sess2.state_store,
                             async_mode=False)
    mgr2.restore()
    assert sorted(sess2.state_store.live_sessions()) == ["u", "v"]
    assert serving.serving_stats()["resumed_sessions"] == 2
    bat2 = serving.DynamicBatcher(sess2, max_batch_size=2,
                                  max_latency_ms=2.0, admission=False)
    try:
        x_next = rng.rand(1, N_IN).astype("float32")
        out = onp.asarray(bat2.predict(x_next, session_id="u"))
        ref_o, _ = _unroll(net, xs["u"] + [x_next])
        assert onp.array_equal(out, ref_o), \
            "resumed stream must continue bitwise from the checkpoint"
    finally:
        bat2.close()
        sess2.close()


# ---------------------------------------------------------------------------
# canary promote migrates live sessions

def test_canary_promote_migrates_live_sessions():
    net = _gru()
    repo = serving.ModelRepository(max_latency_ms=2.0)
    rng = onp.random.RandomState(4)
    xs = {sid: [rng.rand(1, N_IN).astype("float32") for _ in range(2)]
          for sid in ("u1", "u2")}
    try:
        repo.deploy("m", _session(net))
        for sid, seq in xs.items():
            for x in seq:
                repo.submit("m", x, session_id=sid).result(timeout=60)
        v2 = _session(net)
        assert repo.deploy("m", v2) == 2
        assert repo.model_states()["m"]["state"] == "canary"
        serving.reset_serving_counters()
        repo.promote("m")
        st = repo.model_states()["m"]
        assert st["active_version"] == 2
        # both live streams crossed into the new version's store...
        assert sorted(v2.state_store.live_sessions()) == ["u1", "u2"]
        assert serving.serving_stats()["resumed_sessions"] == 2
        assert st["session_state"]["sessions"] == 2
        # ...and continue stepping bitwise — zero dropped sessions
        for sid, seq in xs.items():
            x_next = rng.rand(1, N_IN).astype("float32")
            out = repo.submit(
                "m", x_next, session_id=sid).result(timeout=60)
            ref_o, _ = _unroll(net, seq + [x_next])
            assert onp.array_equal(onp.asarray(out), ref_o), sid
    finally:
        repo.close()


# ---------------------------------------------------------------------------
# observability + admission

def test_decode_counters_in_stats_profiler_and_prometheus():
    from mxnet_tpu import profiler

    net = _gru()
    sess = _session(net)
    try:
        sess.step(nd.zeros((1, N_IN)), states=[nd.zeros((1, HID))])
        sess.state_store.open("live")
        st = serving.serving_stats()
        assert st["decode_steps"] == 1
        assert st["slot_occupancy"] == 1
        assert "evictions" in st and "resumed_sessions" in st
        assert profiler.serving_counters()["decode_steps"] == 1
        text = serving.prometheus_text()
        assert "mxnet_serving_decode_steps_total 1" in text
        assert "mxnet_serving_slot_occupancy 1" in text
        assert "mxnet_serving_evictions_total" in text
    finally:
        sess.close()


def test_admission_sheds_new_streams_when_pool_is_full(monkeypatch):
    """Slot headroom folds into admission ONLY for steps that must
    allocate a state slot: sheddable classes stop claiming slots
    before the pool evicts live streams; held slots and the protected
    class are untouched."""
    from mxnet_tpu.serving.admission import ShedLoad

    monkeypatch.setenv("MXNET_SERVING_SLO_MS", "60000")  # keep the
    # latency term idle so the slot term is what decides
    net = _gru()
    store = SessionStateStore([(HID,)], max_sessions=2, ttl_s=0)
    sess = serving.InferenceSession(
        net, input_shapes=[(1, N_IN)], state_shapes=[(HID,)],
        state_store=store, buckets=[1, 2])
    bat = serving.DynamicBatcher(sess, max_batch_size=2,
                                 max_latency_ms=1.0, admission=True)
    x = _x(7)
    try:
        bat.predict(x, session_id="a")
        bat.predict(x, session_id="b")  # pool now full
        assert bat.admission.snapshot()["slot_headroom"] == 0.0
        with pytest.raises(ShedLoad):
            bat.submit(x, session_id="c", slo_class="best_effort")
        assert serving.serving_stats()["shed"] == 1
        # live streams keep stepping: their slot is already held
        bat.predict(x, session_id="a")
        # the protected class still allocates (evicting LRU "b")
        bat.predict(x, session_id="crit", slo_class="critical")
        assert store.has("crit")
    finally:
        bat.close()
        sess.close()
