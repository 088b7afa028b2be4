"""Paged KV-cache decode (round 21): DecoderBlockLM as a stateful
serving workload over the paged SessionStateStore.

Covers: continuous-batching decode bitwise vs the explicit-state step
loop across page boundaries (and after an eviction + clean re-open),
the `_attention_decode` lax vs interpreted-flash parity, lazy page
allocation + stats/headroom, page-pressure reclaiming whole LRU
sessions (blast radius: exactly one client, survivors bitwise),
checkpoint restore across page geometries (page size flips and
paged -> row-slot) continuing bitwise, canary promote migrating live
paged sessions with zero drops, the `paged_state` artifact salt
re-keying per geometry while row-slot keys stay byte-stable, warm
process start resolving the paged step executable with zero retraces,
and int8 KV pages (accuracy bound + counters + the unbacked-page
scatter guard)."""
import pickle

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, serving
from mxnet_tpu.analysis import quantize
from mxnet_tpu.models import DecoderBlockLM
from mxnet_tpu.resilience.checkpoint import CheckpointManager
from mxnet_tpu.serving import SessionEvicted, SessionStateStore
from mxnet_tpu.utils import compile_cache as cc

nd = mx.nd

VOCAB, EMBED, HEADS, LAYERS, MAXLEN, PT = 32, 16, 2, 1, 16, 4


def _decoder(seed=21, impl="lax"):
    mx.random.seed(seed)
    net = DecoderBlockLM(VOCAB, embed_dim=EMBED, num_layers=LAYERS,
                         num_heads=HEADS, max_len=MAXLEN, impl=impl)
    net.initialize()
    with autograd.pause(train_mode=False):
        net(nd.zeros((1, 1), dtype="int32"), *_zero_states(net))
    return net


def _zero_states(net):
    return [nd.zeros((1,) + s, dtype=dt)
            for s, dt in zip(net.state_row_shapes(),
                             net.state_row_dtypes())]


_OPEN_STORES = []


def _store(net, page_tokens=PT, **kw):
    kw.setdefault("max_sessions", 8)
    kw.setdefault("ttl_s", 0)
    store = SessionStateStore(net.state_row_shapes(),
                              net.state_row_dtypes(),
                              pageable=net.state_row_pageable(),
                              page_tokens=page_tokens, **kw)
    _OPEN_STORES.append(store)
    return store


def _session(net, store, **kw):
    kw.setdefault("buckets", [1, 2, 4])
    return serving.InferenceSession(
        net, input_shapes=[(1, 1)], input_dtypes=["int32"],
        state_store=store, **kw)


def _toks(seed, n):
    return [onp.random.RandomState(seed + t).randint(
        0, VOCAB, size=(1, 1)).astype("int32") for t in range(n)]


def _oracle(sess, toks):
    """Explicit-state step loop — the client-side state-threading
    contract over the SAME executable; server-side paged storage must
    be bitwise transparent to it."""
    states = _zero_states(sess._block)
    out = None
    for x in toks:
        out, states = sess.step(nd.array(x), states=states)
    return onp.asarray(out.data), [onp.asarray(s.data) for s in states]


@pytest.fixture(autouse=True)
def _fresh_counters():
    serving.reset_serving_counters()
    quantize.reset_counters()
    yield
    # sessions never own an explicitly-passed store: close them here
    # or their occupancy probes leak into later tests' gauges
    while _OPEN_STORES:
        _OPEN_STORES.pop().close()
    serving.reset_serving_counters()
    quantize.reset_counters()


@pytest.fixture(scope="module")
def net():
    return _decoder()


# ---------------------------------------------------------------------------
# decode through the batcher, page boundaries, eviction + re-open

def test_paged_decode_bitwise_across_page_boundaries(net):
    """Streams whose prefixes cross page boundaries (lengths 3/6/11
    over 4-token pages) must decode bitwise vs the explicit-state loop;
    page allocation stays lazy (footprint = ceil(prefix / page))."""
    store = _store(net)
    sess = _session(net, store)
    bat = serving.DynamicBatcher(sess, max_batch_size=4,
                                 max_latency_ms=2.0,
                                 timeout_ms=120000.0, admission=False)
    lengths = {"s0": 3, "s1": 6, "s2": 11}
    toks = {sid: _toks(i * 100, n)
            for i, (sid, n) in enumerate(lengths.items())}
    try:
        futs = {sid: [bat.submit(x, session_id=sid, block=True)
                      for x in seq] for sid, seq in toks.items()}
        for sid, fs in futs.items():
            final = onp.asarray(fs[-1].result(timeout=120))
            ref_o, ref_s = _oracle(sess, toks[sid])
            assert onp.array_equal(final, ref_o), \
                f"stream {sid} not bitwise vs explicit-state loop"
            # the server-side dense rows ARE the chain's states
            for row, ref in zip(store.read(sid), ref_s):
                assert onp.array_equal(row, ref[0]), sid
        st = store.stats()
        assert st["page_tokens"] == PT
        # lazy allocation: 1 + 2 + 3 pages, never ceil(16/4) each
        assert st["pages_used"] == 6
        assert store.page_headroom() == pytest.approx(
            (st["pages_total"] - 6) / st["pages_total"])
        # eviction tears down the WHOLE session...
        store.evict("s2", reason="test")
        assert store.stats()["pages_used"] == 3
        with pytest.raises(SessionEvicted, match="re-open"):
            bat.submit(toks["s2"][0], session_id="s2",
                       block=True).result(timeout=120)
        # ...and an explicit re-open restarts clean: null pages gather
        # as exact zeros, so the replayed stream is bitwise again
        store.open("s2")
        fs = [bat.submit(x, session_id="s2", block=True)
              for x in toks["s2"]]
        ref_o, _ = _oracle(sess, toks["s2"])
        assert onp.array_equal(onp.asarray(fs[-1].result(timeout=120)),
                               ref_o)
    finally:
        bat.close()
        sess.close()


def test_attention_decode_lax_vs_interpret_parity():
    """The decode flash kernel (interpreted off-TPU) matches the lax
    reference within documented-ulp, including partial prefixes."""
    from mxnet_tpu.ndarray import registry

    op = registry.get_op("_attention_decode")
    rs = onp.random.RandomState(7)
    B, S, E = 3, MAXLEN, EMBED
    q = nd.array(rs.randn(B, E).astype("f"))
    kc = nd.array(rs.randn(B, S, E).astype("f"))
    vc = nd.array(rs.randn(B, S, E).astype("f"))
    pos = nd.array(onp.array([[0], [5], [S - 1]], "int32"))
    kw = {"num_heads": HEADS, "sm_scale": 1.0 / (E // HEADS) ** 0.5}
    lax = registry.invoke(op, (q, kc, vc, pos),
                          {**kw, "impl": "lax"}).asnumpy()
    itp = registry.invoke(op, (q, kc, vc, pos),
                          {**kw, "impl": "interpret"}).asnumpy()
    assert onp.abs(lax - itp).max() < 1e-5
    # causality: garbage beyond the visible prefix must not leak
    kc2 = nd.array(onp.where(onp.arange(S)[None, :, None] > 5, 999.0,
                             kc.asnumpy()).astype("f"))
    lax2 = registry.invoke(op, (q, kc2, vc, pos),
                           {**kw, "impl": "lax"}).asnumpy()
    assert onp.array_equal(lax[1], lax2[1])


# ---------------------------------------------------------------------------
# page-pool pressure: whole-session LRU reclaim

def test_page_pressure_evicts_whole_lru_session(net):
    """3 slots x 6 pages: a 4th stream's page demand reclaims the LRU
    session ENTIRELY (never a torn cache) and only that one client
    sees SessionEvicted; survivors stay bitwise."""
    store = _store(net, max_sessions=3, byte_budget=3200)
    assert store.num_slots == 3 and store.num_pages == 6
    rs = onp.random.RandomState(11)
    rows = {sid: [rs.randn(*s).astype(dt) for s, dt in
                  zip(net.state_row_shapes(), net.state_row_dtypes())]
            for sid in ("a", "b", "c")}
    for sid in ("a", "b", "c"):  # 2 pages each: the pool is full
        store.open(sid, init_states=rows[sid], tokens=8)
    assert store.page_headroom() == 0.0
    store.open("d", init_states=rows["a"], tokens=4)  # reclaims "a"
    assert sorted(store.live_sessions()) == ["b", "c", "d"]
    with pytest.raises(SessionEvicted, match="re-open"):
        store.acquire("a")
    assert serving.serving_stats()["evictions"] == 1
    pageable = net.state_row_pageable()
    for i, row in enumerate(store.read("b")):  # survivor untouched
        if pageable[i]:  # tokens=8 seeded 2 pages; the rest is null
            assert onp.array_equal(row[:8], rows["b"][i][:8])
            assert not row[8:].any()
        else:
            assert onp.array_equal(row, rows["b"][i])


def test_paged_store_holds_3x_the_sessions_of_row_slots_at_one_budget():
    """One byte budget, sessions holding a 16-token prefix of a
    64-token cache: the row-slot store reserves every session's whole
    cache, the paged store only the pages the prefix touches — at least
    3x the resident sessions, and int8 pages more again. Every session
    opened is still resident: no eviction made the room."""
    mx.random.seed(23)
    net64 = DecoderBlockLM(VOCAB, embed_dim=EMBED, num_layers=LAYERS,
                           num_heads=HEADS, max_len=64, impl="lax")
    net64.initialize()
    zero_rows = [onp.zeros(s, dt) for s, dt in
                 zip(net64.state_row_shapes(), net64.state_row_dtypes())]
    prefix, held = 16, {}
    for key, kw in (("rowslot", dict(page_tokens=0)),
                    ("paged", dict(page_tokens=8)),
                    ("int8", dict(page_tokens=8, kv_int8=True))):
        store = _store(net64, max_sessions=4096, byte_budget=64 * 1024,
                       **kw)
        n = store.num_slots
        if store.paged:
            n = min(n, store.num_pages // -(-prefix // store.page_tokens))
        for i in range(n):
            store.open(f"cap-{i}", init_states=zero_rows, tokens=prefix)
        assert len(store.live_sessions()) == n
        assert serving.serving_stats().get("evictions", 0) == 0
        held[key] = n
    assert held["paged"] >= 3 * held["rowslot"], held
    assert held["int8"] > held["paged"], held


# ---------------------------------------------------------------------------
# checkpoint mid-stream, restore across geometries

def test_checkpoint_mid_stream_restores_across_geometries(net, tmp_path):
    """A checkpoint taken mid-page under 4-token pages must resume
    bitwise under 8-token pages AND under row-slot storage — the
    payload is dense rows, geometry is a server detail."""
    toks = _toks(31, 8)
    sess = _session(net, _store(net))
    mgr = CheckpointManager(str(tmp_path), session_state=sess.state_store,
                            async_mode=False)
    bat = serving.DynamicBatcher(sess, max_batch_size=2,
                                 max_latency_ms=2.0,
                                 timeout_ms=120000.0, admission=False,
                                 state_checkpoint=mgr)
    for x in toks[:6]:  # 6 steps: page 1 full, page 2 half-written
        bat.submit(x, session_id="u", block=True).result(timeout=120)
    bat.close()  # drains to the boundary and checkpoints
    sess.close()
    ref_o, _ = _oracle_fresh(net, toks)

    for page_tokens in (8, 0):  # coarser pages, then row-slot
        serving.reset_serving_counters()
        sess2 = _session(net, _store(net, page_tokens=page_tokens))
        CheckpointManager(str(tmp_path), session_state=sess2.state_store,
                          async_mode=False).restore()
        assert sess2.state_store.live_sessions() == ["u"]
        assert serving.serving_stats()["resumed_sessions"] == 1
        bat2 = serving.DynamicBatcher(sess2, max_batch_size=2,
                                      max_latency_ms=2.0,
                                      timeout_ms=120000.0,
                                      admission=False)
        try:
            for x in toks[6:]:
                out = onp.asarray(bat2.submit(
                    x, session_id="u", block=True).result(timeout=120))
            assert onp.array_equal(out, ref_o), \
                f"restore into page_tokens={page_tokens} not bitwise"
        finally:
            bat2.close()
            sess2.close()


def _oracle_fresh(net, toks):
    sess = _session(net, _store(net))
    try:
        return _oracle(sess, toks)
    finally:
        sess.close()


def test_fleet_migration_page16_restores_into_page64_int8():
    """Round-23 fleet drain wire form: a session exported from a
    replica paging KV at PAGE_TOKENS=16 restores onto a replica
    running page size 64 with int8 KV pages on. The payload is dense
    rows, so the 16 -> 64 crossing itself is bitwise: an fp32
    destination reads back the exported rows byte-for-byte and
    continues bitwise vs the offline oracle; the int8 destination
    keeps every NON-pageable row bitwise and its KV pages inside the
    documented quantization bound (its own storage choice, not a
    migration loss)."""
    mx.random.seed(23)
    net64 = DecoderBlockLM(VOCAB, embed_dim=EMBED, num_layers=LAYERS,
                           num_heads=HEADS, max_len=64, impl="lax")
    net64.initialize()
    with autograd.pause(train_mode=False):
        net64(nd.zeros((1, 1), dtype="int32"), *_zero_states(net64))
    toks = _toks(47, 12)
    sess = _session(net64, _store(net64, page_tokens=16))
    bat = serving.DynamicBatcher(sess, max_batch_size=2,
                                 max_latency_ms=2.0,
                                 timeout_ms=120000.0, admission=False)
    try:
        for x in toks[:6]:
            bat.submit(x, session_id="u", block=True).result(timeout=120)
    finally:
        bat.close()
    # the exact bytes a FleetRouter drain moves between replicas
    wire = pickle.dumps(sess.state_store.export_state(),
                        protocol=pickle.HIGHEST_PROTOCOL)
    sess.close()
    payload = pickle.loads(wire)
    assert list(payload["sessions"]) == ["u"]
    src_rows = payload["sessions"]["u"]["states"]
    pageable = net64.state_row_pageable()
    ref_o, _ = _oracle_fresh(net64, toks)

    # fp32 page-64 destination: dense rows land bitwise, decode
    # continues bitwise
    sess64 = _session(net64, _store(net64, page_tokens=64))
    bat64 = serving.DynamicBatcher(sess64, max_batch_size=2,
                                   max_latency_ms=2.0,
                                   timeout_ms=120000.0, admission=False)
    try:
        assert sess64.state_store.restore_state(
            pickle.loads(wire)) == 1
        for got, want in zip(sess64.state_store.read("u"), src_rows):
            assert onp.array_equal(onp.asarray(got), onp.asarray(want))
        for x in toks[6:]:
            out = onp.asarray(bat64.submit(
                x, session_id="u", block=True).result(timeout=120))
        assert onp.array_equal(out, ref_o), \
            "page 16 -> 64 migration not bitwise"
    finally:
        bat64.close()
        sess64.close()

    # page-64 + int8-KV destination: non-pageable rows stay bitwise,
    # KV pages and the continued decode stay inside the int8 bound
    quantize.reset_counters()
    sess8 = _session(net64, _store(net64, page_tokens=64,
                                   kv_int8=True))
    bat8 = serving.DynamicBatcher(sess8, max_batch_size=2,
                                  max_latency_ms=2.0,
                                  timeout_ms=120000.0, admission=False)
    try:
        assert sess8.state_store.restore_state(
            pickle.loads(wire)) == 1
        assert quantize.counters()["kv_pages_quantized"] > 0
        for got, want, paged in zip(sess8.state_store.read("u"),
                                    src_rows, pageable):
            got, want = onp.asarray(got), onp.asarray(want)
            if paged:
                denom = max(float(onp.abs(want).max()), 1e-6)
                assert float(onp.abs(got - want).max()) / denom < 0.1
            else:
                assert onp.array_equal(got, want)
        for x in toks[6:]:
            out8 = onp.asarray(bat8.submit(
                x, session_id="u", block=True).result(timeout=120))
        denom = max(float(onp.abs(ref_o).max()), 1e-6)
        assert float(onp.abs(out8 - ref_o).max()) / denom < 0.1, \
            "int8 destination drifted past the KV accuracy bound"
    finally:
        bat8.close()
        sess8.close()


# ---------------------------------------------------------------------------
# canary promote migrates paged sessions — zero drops

def test_canary_promote_migrates_paged_sessions(net):
    repo = serving.ModelRepository(max_latency_ms=2.0, admission=False)
    toks = {sid: _toks(i * 50 + 7, 5) for i, sid in
            enumerate(("u1", "u2"))}
    try:
        repo.deploy("m", _session(net, _store(net)))
        for sid, seq in toks.items():
            for x in seq[:3]:
                repo.submit("m", x, session_id=sid).result(timeout=120)
        # v2 stores KV under a DIFFERENT page size: migration is dense
        v2 = _session(net, _store(net, page_tokens=8))
        repo.deploy("m", v2)
        serving.reset_serving_counters()
        repo.promote("m")
        assert sorted(v2.state_store.live_sessions()) == ["u1", "u2"]
        assert serving.serving_stats()["resumed_sessions"] == 2
        for sid, seq in toks.items():
            for x in seq[3:]:
                out = repo.submit("m", x,
                                  session_id=sid).result(timeout=120)
            ref_o, _ = _oracle_fresh(net, seq)
            assert onp.array_equal(onp.asarray(out), ref_o), sid
    finally:
        repo.close()


# ---------------------------------------------------------------------------
# artifact identity + warm start

def test_paged_salt_rekeys_per_geometry_row_slot_stable(net):
    """Page geometry and int8-KV re-key step artifacts; row-slot keys
    ignore the paged knobs entirely (byte-stable across flips)."""
    sess_row = _session(net, _store(net, page_tokens=0))
    sess_p4 = _session(net, _store(net, page_tokens=4))
    sess_p8 = _session(net, _store(net, page_tokens=8))
    sess_i8 = _session(net, _store(net, page_tokens=4, kv_int8=True))
    try:
        fps = [s._step_artifact(1, 0).fingerprint
               for s in (sess_row, sess_p4, sess_p8, sess_i8)]
        assert all(fp is not None for fp in fps)
        assert len(set(fps)) == 4, "each geometry must key its own"
        sess_row2 = _session(net, _store(net, page_tokens=0))
        try:
            assert sess_row2._step_artifact(1, 0).fingerprint == fps[0]
        finally:
            sess_row2.close()
    finally:
        for s in (sess_row, sess_p4, sess_p8, sess_i8):
            s.close()


def test_warm_start_paged_step_zero_retraces(net, tmp_path, monkeypatch):
    """A second process's paged decode session resolves its step
    executable from the disk tier — zero traces before serving."""
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path))
    cold = _session(net, _store(net), buckets=[1])
    x = _toks(3, 1)[0]
    out_c, _ = _oracle(cold, [x])
    cold.close()

    serving.reset_serving_counters()
    cc.reset_compile_cache_counters()
    warm = _session(net, _store(net), buckets=[1])
    try:
        out_w, _ = _oracle(warm, [x])
        st = cc.compile_cache_stats()
        assert st["retraces"] == 0, "warm paged session must not trace"
        assert st["disk_hits"] >= 1
        assert onp.array_equal(out_c, out_w)
    finally:
        warm.close()


# ---------------------------------------------------------------------------
# int8 KV pages

def test_int8_kv_pages_accuracy_and_counters(net):
    store = _store(net, kv_int8=True)
    assert store.stats()["kv_int8"] is True
    sess = _session(net, store)
    bat = serving.DynamicBatcher(sess, max_batch_size=2,
                                 max_latency_ms=2.0,
                                 timeout_ms=120000.0, admission=False)
    toks = _toks(91, 10)
    try:
        for x in toks:
            out = onp.asarray(bat.submit(
                x, session_id="q", block=True).result(timeout=120))
        ref_o, _ = _oracle(sess, toks)  # fp32 client-side states
        denom = max(float(onp.abs(ref_o).max()), 1e-6)
        assert float(onp.abs(out - ref_o).max()) / denom < 0.1, \
            "int8 KV pages drifted past the accuracy bound"
        assert quantize.counters()["kv_pages_quantized"] > 0
    finally:
        bat.close()
        sess.close()


def test_scatter_into_unbacked_page_is_refused(net):
    """scatter() without the acquire() that backs the step's page must
    raise — silently writing the null page would corrupt every
    session."""
    store = _store(net)
    store.open("s")  # fresh table: all null pages
    rec = store._slots["s"]
    rows = [onp.zeros((1,) + s, dt) for s, dt in
            zip(net.state_row_shapes(), net.state_row_dtypes())]
    with pytest.raises(mx.MXNetError, match="unbacked"):
        store.scatter([rec], rows)
