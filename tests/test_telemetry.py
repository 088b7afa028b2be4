"""mxnet_tpu.telemetry — span tracer, Chrome-trace exporter, unified
metrics registry (round 18).

Covers the six contract surfaces: span nesting/causality across
threads, ring wraparound (drop-oldest + ``dropped_spans``),
Chrome-trace JSON schema, trace-id propagation end-to-end through the
DynamicBatcher, the unified Prometheus exposition (training families
scrapeable next to the serving block), and the ``MXNET_TELEMETRY=0``
zero-emission guarantee; then the training path's own spans
(``SPMDTrainer``, compile, parameter init), recorded by default and on
the wall clock's anchor."""
import json
import re
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, serving, telemetry
from mxnet_tpu.gluon import nn

nd = mx.nd


@pytest.fixture(autouse=True)
def _clean_ring():
    telemetry.reset_trace()
    yield
    telemetry.reset_trace()


def _mlp(in_dim=8, out_dim=4, seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(out_dim))
    net.initialize()
    with autograd.pause(train_mode=False):
        net(nd.zeros((1, in_dim)))
    return net


# ---------------------------------------------------------------------------
# span nesting + cross-thread causality

def test_span_nesting_and_cross_thread_causality(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.reset_trace()
    with telemetry.trace_context("t-abc") as tid:
        assert tid == "t-abc"
        with telemetry.span("outer", cat="test"):
            with telemetry.span("inner", cat="test") as sp:
                sp.set(marker=7)

            def work():
                # another thread has its own span stack; causality
                # crosses via the explicitly-carried trace id
                with telemetry.span("worker", cat="test",
                                    trace_id=tid):
                    pass

            th = threading.Thread(target=work, name="test-worker")
            th.start()
            th.join()
    evs = {e["name"]: e for e in telemetry.events()}
    assert set(evs) == {"outer", "inner", "worker"}
    # same-thread nesting: inner's parent is outer's span id
    assert evs["inner"]["args"]["parent"] == \
        evs["outer"]["args"]["span_id"]
    assert evs["inner"]["args"]["marker"] == 7
    # the worker span has no lexical parent but shares the trace id
    assert "parent" not in evs["worker"]["args"]
    for name in ("outer", "inner", "worker"):
        assert evs[name]["args"]["trace_id"] == "t-abc", name
    assert evs["worker"]["tid"] != evs["outer"]["tid"]
    assert telemetry.thread_names()[evs["worker"]["tid"]] == \
        "test-worker"


def test_span_records_error_type(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.reset_trace()
    with pytest.raises(ValueError):
        with telemetry.span("doomed", cat="test"):
            raise ValueError("boom")
    (ev,) = telemetry.events()
    assert ev["args"]["error"] == "ValueError"


# ---------------------------------------------------------------------------
# ring wraparound

def test_ring_wraparound_drops_oldest(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.reset_trace(capacity=8)
    for i in range(12):
        telemetry.instant(f"ev{i}", cat="test")
    evs = telemetry.events()
    assert len(evs) == 8 == telemetry.buffer_capacity()
    # drop-oldest: the first four are gone, order is preserved
    assert [e["name"] for e in evs] == [f"ev{i}" for i in range(4, 12)]
    assert telemetry.dropped_spans() == 4
    # the drop count rides the export payload
    assert telemetry.build_trace(counters=False)["otherData"][
        "dropped_spans"] == 4


# ---------------------------------------------------------------------------
# Chrome-trace schema

def test_chrome_trace_json_schema(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.reset_trace()
    with telemetry.span("alpha", cat="test", k=1):
        telemetry.instant("mark", cat="test")
    path = tmp_path / "trace.json"
    telemetry.dump_trace(str(path))
    doc = json.load(open(str(path)))  # the acceptance bar: json.load
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    phs = {e["ph"] for e in events}
    assert {"X", "i", "M", "C"} <= phs, phs
    for e in events:
        assert {"name", "ph", "pid"} <= set(e), e
        if e["ph"] in ("X", "i", "M"):
            assert "tid" in e, e  # counter samples are process-scoped
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0 and "cat" in e
        elif e["ph"] == "i":
            assert e["s"] == "t"
    # thread metadata labels the lanes
    mnames = [e for e in events if e["ph"] == "M"]
    assert mnames and all(e["name"] == "thread_name" and
                          "name" in e["args"] for e in mnames)
    # counter samples keep the legacy profiler "<family>/<counter>"
    # naming, so existing dump() consumers parse the same series
    csamples = [e for e in events if e["ph"] == "C"]
    assert csamples and all("/" in e["name"] for e in csamples)
    assert any(e["name"].startswith("compile_cache/")
               for e in csamples)


# ---------------------------------------------------------------------------
# trace-id propagation through the batcher (the serving lifecycle)

def test_trace_id_propagates_through_dynamic_batcher(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    sess = serving.InferenceSession(_mlp(), input_shapes=[(1, 8)],
                                    buckets=[1, 2])
    bat = serving.DynamicBatcher(sess, max_latency_ms=5, num_workers=1)
    telemetry.reset_trace()  # drop construction/compile spans
    try:
        x = onp.random.RandomState(0).rand(1, 8).astype("float32")
        with telemetry.trace_context("req-42"):
            out = bat.predict(x)
    finally:
        bat.close()
    assert out.shape == (1, 4)
    mine = [e for e in telemetry.events()
            if e.get("args", {}).get("trace_id") == "req-42"]
    names = {e["name"] for e in mine}
    # the documented lifecycle, all stamped with ONE trace id
    assert {"serving.admission", "serving.queue_wait",
            "serving.execute", "serving.respond"} <= names, names
    # ...across at least two lanes: the submitting thread and the
    # batch-formation worker
    assert len({e["tid"] for e in mine}) >= 2, mine


# ---------------------------------------------------------------------------
# unified Prometheus exposition

def test_prometheus_exposition_unifies_training_and_serving():
    text = telemetry.prometheus_text()
    # the serving block survives verbatim...
    assert "mxnet_serving_requests_total" in text
    assert "mxnet_serving_request_latency_seconds" in text
    # ...and training-side families are scrapeable for the first time
    assert "mxnet_pipeline_" in text
    assert "mxnet_compile_cache_" in text
    # internal (underscore-prefixed) families stay out of the scrape
    assert "mxnet__graph_opt_passes" not in text


def test_registry_counter_family_roundtrip():
    fam = telemetry.counter_family("test_roundtrip", {"hits": 0})
    fam.reset()
    fam.add("hits")
    fam.add("hits", 2)
    fam.set("gauge", 7)
    assert telemetry.family_snapshot("test_roundtrip") == \
        {"hits": 3, "gauge": 7}
    # idempotent create-or-fetch: same live family, not a new one
    assert telemetry.counter_family("test_roundtrip") is fam
    assert "mxnet_test_roundtrip_hits 3" in telemetry.prometheus_text()
    fam.reset()
    assert telemetry.family_snapshot("test_roundtrip")["hits"] == 0


# ---------------------------------------------------------------------------
# MXNET_TELEMETRY=0: nothing is emitted

def test_disabled_level_emits_nothing(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    telemetry.reset_trace()
    assert not telemetry.tracing()
    sp = telemetry.span("nope", cat="test")
    # the disabled path is ONE shared null span — no allocation
    assert sp is telemetry.span("nope2", cat="test")
    with sp:
        sp.set(k=1)
    telemetry.instant("nope3", cat="test")
    # trace-id plumbing still works (X-Request-Id echo never breaks)
    with telemetry.trace_context("rid-1"):
        assert telemetry.current_trace_id() == "rid-1"
    assert telemetry.current_trace_id() is None
    assert telemetry.events() == []
    assert telemetry.dropped_spans() == 0


# ---------------------------------------------------------------------------
# the training path measured from inside: recorded by default, anchored
# to the wall clock, nothing at level 0

def _spmd_run(steps=3, seed=3):
    """A fresh trainer through ``steps`` steps; (trainer, x, y, losses
    as raw float32 bits)."""
    import jax

    from mxnet_tpu import gluon, parallel

    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer="adamw",
        optimizer_params={"learning_rate": 0.01},
        mesh=parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    rng = onp.random.default_rng(seed)
    x = nd.array(rng.standard_normal((8, 10)).astype("float32"))
    y = nd.array(rng.integers(0, 4, (8,)).astype("float32"))
    losses = [trainer.step(x, y).asnumpy().tobytes() for _ in range(steps)]
    return trainer, x, y, losses


def _named(name):
    return [e for e in telemetry.events() if e["name"] == name]


def test_unix_anchor_puts_a_span_on_the_wall_clock(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    with telemetry.span("anchored", cat="test"):
        wall = time.time_ns()
    (ev,) = _named("anchored")
    start = telemetry.epoch_unix_ns() + ev["ts"] * 1000
    assert abs(start - wall) < 5e6, (start - wall) / 1e6
    # the exporter carries the anchor, so a dump can be laid over a
    # device trace
    assert telemetry.build_trace(counters=False)["otherData"][
        "epoch_unix_ns"] == telemetry.epoch_unix_ns()


def test_structural_spans_are_recorded_with_the_environment_unset(
        monkeypatch):
    monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
    assert telemetry.level() == 1 and telemetry.tracing()
    assert not telemetry.tracing(2)
    _spmd_run()
    assert len(_named("spmd.step")) == 3
    # a full default ring stays small: under 0.6 KB an event
    # (docs/TELEMETRY.md)
    assert telemetry.buffer_capacity() <= 8192


def test_level_zero_records_nothing_from_a_build_and_three_steps(
        monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    before = telemetry.family_snapshot("spmd")
    compiles = telemetry.family_snapshot("compile_cache")["programs"]
    _spmd_run()
    assert telemetry.events() == []
    assert telemetry.family_snapshot("spmd") == before
    assert telemetry.family_snapshot("compile_cache")["programs"] == compiles


def test_spmd_step_nests_place_and_launch_and_build_appears_once(
        monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    before = telemetry.family_snapshot("spmd")
    _spmd_run()
    (build,) = _named("spmd.build")
    (init_fwd,) = _named("spmd.build.init_forward")
    (placed,) = _named("spmd.build.place")
    assert init_fwd["args"]["parent"] == build["args"]["span_id"]
    assert placed["args"]["parent"] == build["args"]["span_id"]
    assert placed["args"]["params"] == 4 and placed["args"]["bytes"] > 0
    # deferred parameters finish inside the init forward
    inits = _named("gluon.param_init")
    weights = [e for e in inits if e["args"]["param"].endswith("_weight")]
    assert len(inits) == 4 and len(weights) == 2
    assert all(e["args"]["parent"] == init_fwd["args"]["span_id"]
               for e in weights)
    assert all(e["args"]["bytes"] > 0 and e["args"]["discarded"] is False
               for e in inits)
    steps = _named("spmd.step")
    assert [e["args"]["step"] for e in steps] == [1, 2, 3]
    for step, place, launch in zip(steps, _named("spmd.step.place"),
                                   _named("spmd.step.launch")):
        sid = step["args"]["span_id"]
        assert place["args"]["parent"] == launch["args"]["parent"] == sid
        assert place["args"]["bytes"] == 8 * 10 * 4 + 8 * 4
        assert step["ts"] <= place["ts"] <= launch["ts"]
        assert launch["ts"] + launch["dur"] <= step["ts"] + step["dur"] + 1
    after = telemetry.family_snapshot("spmd")
    assert after["steps"] - before["steps"] == 3
    assert after["builds"] - before["builds"] == 1
    assert after["placed_bytes"] - before["placed_bytes"] == 3 * 352
    assert "mxnet_spmd_steps" in telemetry.prometheus_text()


@pytest.mark.parametrize("with_state", [False, True])
def test_init_forward_runs_only_where_it_finishes_something(monkeypatch,
                                                           with_state):
    """Every array set before the trainer is built: the deferred-init
    forward still runs where the net keeps state the forward writes
    (BatchNorm's running variance moves once on the zeros batch, 1 ->
    0.9) and is skipped where it has nothing to finish (span argument
    ``ran``)."""
    import jax

    from mxnet_tpu import gluon, parallel

    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"))
    if with_state:
        net.add(nn.BatchNorm())
    net.add(nn.Dense(4))
    net.initialize()
    with autograd.pause(train_mode=False):
        net(nd.zeros((1, 10)))
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mesh=parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    telemetry.reset_trace()
    trainer._ensure_built(nd.zeros((8, 10)), nd.zeros((8,)))
    (init_fwd,) = _named("spmd.build.init_forward")
    assert init_fwd["args"]["ran"] is with_state
    variances = [p.data().asnumpy() for name, p in
                 net.collect_params().items() if name.endswith("running_var")]
    assert len(variances) == int(with_state)
    for var in variances:
        assert onp.allclose(var, 0.9)


def test_set_data_on_a_deferred_parameter_marks_the_init_discarded(
        monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    layer = nn.Dense(4)          # in_units unknown: deferred
    layer.initialize()
    layer.weight.set_data(nd.ones((4, 6)))
    (ev,) = [e for e in _named("gluon.param_init")
             if e["args"]["param"].endswith("weight")]
    assert ev["args"]["discarded"] is True
    assert ev["args"]["bytes"] == 4 * 6 * 4


def test_first_step_yields_compile_spans_and_a_fourth_step_none(
        monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    stats0 = telemetry.family_snapshot("compile_cache")
    trainer, x, y, _ = _spmd_run(steps=3)
    (launch,) = [e for e in _named("spmd.step.launch")][:1]
    for phase in ("compile.trace", "compile.lower", "compile.backend"):
        mine = [e for e in _named(phase)
                if "spmd_step" in (e["args"]["fun_name"] or "")]
        assert len(mine) == 1, (phase, mine)
        # compiled inside the first step's launch, and says so
        assert mine[0]["args"]["parent"] == launch["args"]["span_id"]
    (backend,) = [e for e in _named("compile.backend")
                  if "spmd_step" in e["args"]["fun_name"]]
    assert backend["args"]["cache_hit"] in (True, False)
    stats1 = telemetry.family_snapshot("compile_cache")
    assert stats1["programs"] > stats0["programs"]
    for key in ("trace_s", "lower_s", "backend_compile_s"):
        assert stats1[key] > stats0[key]

    telemetry.reset_trace()
    trainer.step(x, y)
    assert len(_named("spmd.step")) == 1
    assert not [e for e in telemetry.events()
                if e["name"].startswith("compile.")
                or e["name"] == "retrace"]


def test_nested_traces_count_once_and_short_ones_leave_no_span(monkeypatch):
    """What JAX publishes, replayed: a trace of 50 ms that holds one of
    4 ms and one of 0.2 ms, each published before it."""
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    from mxnet_tpu.utils import compile_cache as cc

    event = "/jax/core/compile/jaxpr_trace_duration"
    before = telemetry.family_snapshot("compile_cache")["trace_s"]
    time.sleep(0.06)
    cc._on_jax_duration(event, 0.004, fun_name="inner")
    time.sleep(0.001)
    cc._on_jax_duration(event, 0.0002, fun_name="eager_add")
    time.sleep(0.001)
    cc._on_jax_duration(event, 0.05, fun_name="outer")
    cc._on_jax_duration("/jax/some/other/duration", 9.0)
    after = telemetry.family_snapshot("compile_cache")["trace_s"]
    assert after - before == pytest.approx(0.05, abs=1e-6)
    spans = [(e["args"]["fun_name"], round(e["dur"]))
             for e in _named("compile.trace")]
    assert spans == [("inner", 4000), ("outer", 50000)]


def test_forced_retrace_leaves_an_instant_with_its_label(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    from mxnet_tpu.utils import compile_cache as cc

    fn = cc.counting_jit(lambda a: a * 2, label="telemetry_probe")
    fn(onp.ones((3,), "float32"))
    fn(onp.ones((3,), "float32"))          # cached: no trace
    fn(onp.ones((5,), "float32"))          # new shape: a retrace
    got = [e for e in _named("retrace")
           if e["args"]["label"] == "telemetry_probe"]
    assert len(got) == 2 and all(e["ph"] == "i" for e in got)
    named = [e for e in _named("compile.backend")
             if "telemetry_probe" in e["args"]["fun_name"]]
    assert len(named) == 2


def test_losses_are_bitwise_equal_with_tracing_on_and_off(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    on = _spmd_run()[3]
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    off = _spmd_run()[3]
    assert on == off and len(set(on)) == 3


def test_step_hlo_carries_forward_backward_and_update_scopes():
    trainer, x, y, _ = _spmd_run(steps=1)
    names = set(re.findall(r'op_name="([^"]+)"', trainer.step_hlo(x, y)))
    assert any("jit(spmd_step)/jvp(fwd)/" in n for n in names)
    assert any("jit(spmd_step)/transpose(jvp(fwd))/" in n for n in names)
    assert any("jit(spmd_step)/update/" in n for n in names)
    # and below the phase the scope of every block, by the name its
    # parent gave it, and the loss's
    for scope in ("jvp(fwd)/0/", "jvp(fwd)/1/", "transpose(jvp(fwd))/0/",
                  "transpose(jvp(fwd))/1/", "jvp(fwd)/loss/",
                  "transpose(jvp(fwd))/loss/"):
        assert any("jit(spmd_step)/" + scope in n for n in names), scope
    # the text is the table's source: the step's scopes are published
    table = telemetry.scopes.table("spmd_step")
    assert {e["op_name"] for e in table.values()} <= names | {""}


def test_prefetch_stage_span_carries_the_bytes_it_placed(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    from mxnet_tpu.pipeline import DeviceFeed

    batch = (onp.zeros((4, 3), "float32"), onp.zeros((4,), "int32"))
    feed = DeviceFeed(iter([batch, batch]), depth=1)
    assert len(list(feed)) == 2
    feed.close()
    staged = _named("pipeline.prefetch_stage")
    assert [e["args"]["bytes"] for e in staged] == [64, 64]


def test_each_fused_trainer_step_leaves_one_execute_span(monkeypatch):
    """``gluon.Trainer.step`` on the fused path: one
    ``fused_step.execute`` span a step, on the train lane."""
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    net = _mlp()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.05})
    x, y = nd.ones((2, 8)), nd.zeros((2, 4))
    for _ in range(3):
        with autograd.record():
            loss = ((net(x) - y) ** 2).mean()
        loss.backward()
        trainer.step(2)
    spans = _named("fused_step.execute")
    assert len(spans) == 3
    assert all(e["ph"] == "X" and e["cat"] == "train" for e in spans)
