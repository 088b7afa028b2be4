"""Engine lanes + race/stress, pooled-storage strategies, shm NDArray.

Reference: src/engine/threaded_engine_perdevice.cc (per-device pools +
copy workers), tests/python/unittest/test_engine.py +
test_tlocal_racecondition.py (engine stress), src/storage/
pooled_storage_manager.h (Round/Naive/Unpooled strategies +
MXNET_GPU_MEM_POOL_*), src/storage/cpu_shared_storage_manager.h +
gluon dataloader reduce_ndarray (cross-process shm NDArray).
"""
import multiprocessing as mp
import pickle
import threading
import time

import numpy as onp
import pytest

from mxnet_tpu import engine as eng
from mxnet_tpu import nd
from mxnet_tpu.context import Context
from mxnet_tpu.ndarray.shared_mem import SharedNDArray, shared_empty, to_shared


def _native_engine(**kw):
    try:
        return eng.Engine(**kw)
    except RuntimeError:
        pytest.skip("native engine unavailable")


# ---------------------------------------------------------------- engine ---

def test_engine_write_serialization_stress():
    """500 read-modify-write ops on one var from the pool must serialize
    (writer exclusivity) — a lost update means the mutex is broken."""
    e = _native_engine(nthreads=8)
    v = e.new_variable()
    state = {"x": 0}

    def bump():
        cur = state["x"]
        time.sleep(0)  # widen the race window
        state["x"] = cur + 1

    for _ in range(500):
        e.push(bump, mutable_vars=(v,))
    e.wait_for_var(v)
    assert state["x"] == 500


def test_engine_concurrent_push_threads():
    """Pushing from many Python threads at once (the
    test_tlocal_racecondition analog): all ops run exactly once."""
    e = _native_engine(nthreads=4)
    v = e.new_variable()
    lock = threading.Lock()
    count = [0]

    def bump():
        with lock:
            count[0] += 1

    def producer():
        for _ in range(100):
            e.push(bump, mutable_vars=(v,))

    threads = [threading.Thread(target=producer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    e.wait_all()
    assert count[0] == 800


def test_engine_readers_parallel_writers_exclusive():
    e = _native_engine(nthreads=8)
    data = e.new_variable()
    active = [0]
    peak = [0]
    lock = threading.Lock()

    def reader():
        with lock:
            active[0] += 1
            peak[0] = max(peak[0], active[0])
        time.sleep(0.02)
        with lock:
            active[0] -= 1

    for _ in range(8):
        e.push(reader, const_vars=(data,))
    e.wait_all()
    assert peak[0] > 1, "readers never overlapped — engine is serializing reads"


def test_engine_io_lane_does_not_starve_compute():
    """A slow op on the IO lane must not block compute-lane ops — the
    ThreadedEnginePerDevice property (separate pools per lane)."""
    e = _native_engine(nthreads=2, nlanes=2)
    io_var = e.new_variable()
    cpu_var = e.new_variable()
    done = []

    def slow_io():
        time.sleep(1.0)
        done.append("io")

    def fast_compute():
        done.append("c")

    # saturate the IO lane first
    e.push(slow_io, mutable_vars=(io_var,), lane=eng.LANE_IO)
    e.push(slow_io, mutable_vars=(io_var,), lane=eng.LANE_IO)
    t0 = time.perf_counter()
    for _ in range(20):
        e.push(fast_compute, mutable_vars=(cpu_var,),
               lane=eng.LANE_COMPUTE)
    e.wait_for_var(cpu_var)
    elapsed = time.perf_counter() - t0
    assert elapsed < 0.9, \
        f"compute waited {elapsed:.2f}s behind IO-lane work"
    assert done.count("c") == 20
    e.wait_all()


def test_engine_lane_shares_dependency_state():
    """Ops on different lanes touching the SAME var still order."""
    e = _native_engine(nthreads=2, nlanes=2)
    v = e.new_variable()
    order = []

    e.push(lambda: (time.sleep(0.1), order.append("first"))[-1],
           mutable_vars=(v,), lane=eng.LANE_IO)
    e.push(lambda: order.append("second"), mutable_vars=(v,),
           lane=eng.LANE_COMPUTE)
    e.wait_for_var(v)
    assert order == ["first", "second"]


# --------------------------------------------------------------- storage ---

def _fresh_storage(monkeypatch, **env):
    from mxnet_tpu import storage as st

    for k, v in env.items():
        monkeypatch.setenv(k, v)
    s = st.Storage()
    if not s.native:
        pytest.skip("native storage unavailable")
    return s


@pytest.mark.parametrize("pool_type", ["Naive", "Round", "Unpooled"])
def test_storage_strategies_roundtrip(monkeypatch, pool_type):
    s = _fresh_storage(monkeypatch, MXNET_GPU_MEM_POOL_TYPE=pool_type)
    hs = [s.alloc(n) for n in (100, 5000, 100000, 100)]
    for h in hs:
        assert h.ptr
        s.free(h)
    h2 = s.alloc(100)
    assert h2.ptr
    s.direct_free(h2)
    s.release_all()


def test_storage_round_strategy_reuses_pow2_bucket(monkeypatch):
    s = _fresh_storage(monkeypatch, MXNET_GPU_MEM_POOL_TYPE="Round")
    h1 = s.alloc(70000)  # rounds to 128KiB bucket
    p1 = h1.ptr
    s.free(h1)
    h2 = s.alloc(90000)  # same pow2 bucket -> same pointer back
    assert h2.ptr == p1
    s.direct_free(h2)


def test_storage_reserve_cap_returns_memory(monkeypatch):
    # reserve=100 -> cap 0 pooled bytes -> frees go straight to the OS
    s = _fresh_storage(monkeypatch, MXNET_GPU_MEM_POOL_TYPE="Naive",
                       MXNET_GPU_MEM_POOL_RESERVE="100")
    h = s.alloc(4096)
    s.free(h)
    stats = s.stats() if hasattr(s, "stats") else None
    if stats is not None:
        assert stats["pooled_bytes"] == 0


# ------------------------------------------------------------------- shm ---

def test_shared_ndarray_roundtrip():
    a = to_shared(onp.arange(12, dtype="f").reshape(3, 4))
    assert isinstance(a, SharedNDArray)
    assert a.context.device_type == "cpu_shared"
    onp.testing.assert_array_equal(
        a.asnumpy(), onp.arange(12, dtype="f").reshape(3, 4))
    # interops with regular NDArrays through the op layer
    out = (a + nd.ones((3, 4))).asnumpy()
    onp.testing.assert_array_equal(
        out, onp.arange(12, dtype="f").reshape(3, 4) + 1)


def test_shared_ndarray_ctx_api():
    a = nd.array([[1.0, 2.0]], ctx=Context("cpu_shared"))
    assert isinstance(a, SharedNDArray)
    assert a.context == Context("cpu_shared", 0)


def test_shared_ndarray_inplace_write_visible_through_pickle():
    a = shared_empty((4,), "float32")
    a[:] = onp.array([1, 2, 3, 4], "f")
    b = pickle.loads(pickle.dumps(a))  # descriptor transfer, same segment
    onp.testing.assert_array_equal(b.asnumpy(), [1, 2, 3, 4])
    a[1] = 99.0
    onp.testing.assert_array_equal(b.asnumpy(), [1, 99, 3, 4])


def _child_reads_and_writes(payload, q):
    arr = pickle.loads(payload)
    q.put(arr.asnumpy().tolist())
    arr[0] = 42.0  # visible to the parent: same physical pages


def test_shared_ndarray_cross_process(monkeypatch):
    # spawned child re-imports this module; pin it to the CPU backend so
    # it never claims a chip
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    ctx = mp.get_context("spawn")
    a = to_shared(onp.array([7.0, 8.0, 9.0], "f"))
    q = ctx.Queue()
    p = ctx.Process(target=_child_reads_and_writes,
                    args=(pickle.dumps(a), q))
    p.start()
    got = q.get(timeout=60)
    p.join(60)
    assert got == [7.0, 8.0, 9.0]
    assert a.asnumpy()[0] == 42.0
