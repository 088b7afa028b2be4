"""Round-19 int8 serving: quantized SymbolBlocks behind
InferenceSession, int8/fp32 AOT fingerprint coexistence, and the
canary-gated rollout with the MXNET_QUANTIZE_SHADOW accuracy gate —
the ISSUE acceptance scenario: an int8 canary that answers fast but
WRONG (injected accuracy regression) rolls back automatically with
zero client-visible failures, and a clean int8 canary auto-promotes."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, serving
from mxnet_tpu.contrib.quantization import quantize_net_graph
from mxnet_tpu.gluon import nn
from mxnet_tpu.serving.repository import _rel_deviation

nd = mx.nd


def _mlp(seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    with autograd.pause(train_mode=False):
        net(nd.zeros((1, 8)))
    return net


def _quantized(net):
    calib = [nd.array(onp.random.RandomState(i).rand(4, 8)
                      .astype("float32")) for i in range(3)]
    return quantize_net_graph(net, calib_data=calib, calib_mode="naive")


def _session(block, **kw):
    return serving.InferenceSession(block, input_shapes=[(1, 8)],
                                    buckets=[1, 2, 4], **kw)


def _x(seed, rows=1):
    return onp.random.RandomState(seed).rand(rows, 8).astype("float32")


def _ref(net, x):
    with autograd.pause(train_mode=False):
        return net(nd.array(x)).asnumpy()


@pytest.fixture(autouse=True)
def _fresh_counters():
    serving.reset_serving_counters()
    yield
    serving.reset_serving_counters()


def _wait_state(repo, name, state, timeout_s=10.0):
    import time

    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        st = repo.model_states()[name]
        if st["state"] == state:
            return st
        time.sleep(0.01)
    raise AssertionError(
        f"model {name} never reached {state!r}: "
        f"{repo.model_states()[name]}")


class _Corrupt:
    """An int8 rollout gone numerically wrong: executes fine (no
    exceptions, no latency), answers garbage — invisible to the
    failure and latency canary checks, only the shadow gate sees it."""

    def __init__(self, inner, scale=8.0):
        self._inner = inner
        self._scale = scale

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def predict(self, *arrs):
        out = self._inner.predict(*arrs)
        if isinstance(out, (list, tuple)):
            return type(out)(o * self._scale for o in out)
        return out * self._scale


# ---------------------------------------------------------------------------
# quantized graphs behind InferenceSession

def test_session_serves_quantized_graph_accurately():
    net = _mlp(3)
    qb = _quantized(net)
    with serving.ModelRepository(max_latency_ms=1.0, admission=False) as repo:
        repo.deploy("q", _session(qb))
        for i in range(3):
            out = repo.submit("q", _x(i)).result(timeout=30)
            dev = _rel_deviation(out, _ref(net, _x(i)))
            assert dev < 0.1, dev


def test_quantized_block_stores_its_weights_in_under_half_the_bytes():
    """The bytes a request moves: the quantized block keeps its Dense
    weights as int8 (scales and biases stay float32), so its parameters
    take less than half the float32 block's bytes, and the pass counted
    the graph it rewrote."""
    from mxnet_tpu.analysis import quantize

    def param_bytes(block):
        return sum(int(p.data().size) * onp.dtype(p.data().dtype).itemsize
                   for p in block.collect_params().values())

    quantize.reset_counters()
    net = _mlp(5)
    qb = _quantized(net)
    assert quantize.counters()["graphs_quantized"] >= 1
    assert any(onp.dtype(p.data().dtype) == onp.int8
               for p in qb.collect_params().values())
    assert 2 * param_bytes(qb) < param_bytes(net)


def test_int8_fp32_fingerprints_coexist(monkeypatch):
    """The AOT disk keys for the fp32 and int8 versions of the SAME
    model must never collide, int8 keys are salted per lowering mode,
    and the fp32 key ignores the quantize knob entirely."""
    monkeypatch.delenv("MXNET_QUANTIZE_LOWERING", raising=False)
    net = _mlp(4)
    qb = _quantized(net)
    fs, qs = _session(net), _session(qb)
    try:
        fp32_fp = fs._fingerprint(2, 0)
        int8_fp = qs._fingerprint(2, 0)
        assert fp32_fp is not None and int8_fp is not None
        assert fp32_fp != int8_fp
        # the lowering knob re-keys int8 artifacts ...
        monkeypatch.setenv("MXNET_QUANTIZE_LOWERING", "native")
        int8_native = qs._fingerprint(2, 0)
        monkeypatch.setenv("MXNET_QUANTIZE_LOWERING", "dequant")
        int8_dequant = qs._fingerprint(2, 0)
        assert int8_native != int8_dequant
        # ... and leaves every fp32 key byte-stable
        assert fs._fingerprint(2, 0) == fp32_fp
        # different buckets stay distinct within each family
        assert qs._fingerprint(4, 0) != qs._fingerprint(2, 0)
    finally:
        for s in (fs, qs):
            close = getattr(s, "close", None)
            if close:
                close()


# ---------------------------------------------------------------------------
# canary-gated int8 rollout

def test_int8_canary_clean_run_auto_promotes(monkeypatch):
    """A good int8 canary under the shadow accuracy gate: every canary
    request is diffed against the incumbent, int8 deviation stays
    within MXNET_QUANTIZE_SHADOW_TOL, and the version auto-promotes."""
    monkeypatch.setenv("MXNET_QUANTIZE_SHADOW", "1.0")
    monkeypatch.setenv("MXNET_QUANTIZE_SHADOW_TOL", "0.1")
    net = _mlp(5)
    qb = _quantized(net)
    repo = serving.ModelRepository(canary_min_requests=6,
                                   canary_fraction=1.0,
                                   max_latency_ms=1.0, admission=False)
    try:
        repo.deploy("m", _session(net))
        assert repo.deploy("m", _session(qb)) == 2
        for i in range(6):
            out = repo.submit("m", _x(10 + i),
                              slo_class="standard").result(timeout=30)
            dev = _rel_deviation(out, _ref(net, _x(10 + i)))
            assert dev < 0.1, dev  # the client got a usable answer
        st = _wait_state(repo, "m", "serving")
        assert st["active_version"] == 2
        stats = serving.serving_stats()
        assert stats["canary_promotions"] == 1
        assert stats["canary_shadow_checks"] >= 1
        assert stats.get("canary_shadow_mismatches", 0) == 0
        assert stats["canary_rollbacks"] == 0
    finally:
        repo.close()


def test_int8_canary_accuracy_regression_rolls_back(monkeypatch):
    """The ISSUE acceptance scenario: an int8 canary with an injected
    accuracy regression executes without errors and at normal latency —
    only the shadow diff catches it. The breaker trips, the rollout
    rolls back, and no client request ever failed."""
    monkeypatch.setenv("MXNET_QUANTIZE_SHADOW", "1.0")
    monkeypatch.setenv("MXNET_QUANTIZE_SHADOW_TOL", "0.1")
    net = _mlp(6)
    qb = _quantized(net)
    repo = serving.ModelRepository(canary_threshold=3,
                                   canary_fraction=1.0,
                                   canary_min_requests=1000,
                                   max_latency_ms=1.0, admission=False)
    try:
        repo.deploy("m", _session(net))
        repo.deploy("m", _Corrupt(_session(qb)))
        futs = [repo.submit("m", _x(30 + i), slo_class="standard")
                for i in range(6)]
        for f in futs:
            f.result(timeout=30)  # no client-visible failure, ever
        st = _wait_state(repo, "m", "rolled_back")
        assert st["active_version"] == 1
        assert "shadow accuracy deviation" in st["last_transition"]
        stats = serving.serving_stats()
        assert stats["canary_rollbacks"] == 1
        assert stats["canary_shadow_mismatches"] >= 3
        assert stats["canary_failures"] == 0  # it never ERRORED
        # post-rollback traffic is the fp32 incumbent, bitwise
        out = repo.submit("m", _x(99)).result(timeout=30)
        assert onp.array_equal(out, _ref(net, _x(99)))
    finally:
        repo.close()


def test_shadow_disabled_by_default():
    """Without MXNET_QUANTIZE_SHADOW the gate costs nothing: no
    duplicate incumbent runs, no shadow counters."""
    net = _mlp(7)
    with serving.ModelRepository(canary_fraction=1.0,
                                 canary_min_requests=1000,
                                 max_latency_ms=1.0, admission=False) as repo:
        repo.deploy("m", _session(net))
        repo.deploy("m", _Corrupt(_session(_mlp(7))))
        for i in range(4):
            repo.submit("m", _x(i),
                        slo_class="standard").result(timeout=30)
        stats = serving.serving_stats()
        assert stats.get("canary_shadow_checks", 0) == 0
        assert repo.model_states()["m"]["state"] == "canary"
