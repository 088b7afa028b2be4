"""Autotune subsystem (round 24): decision registry, TuningRecord
lifecycle (memory/disk/remote tiers), trial overrides, the
consult-before-heuristic hooks in the fusion cost model and quantize
lowering, salt coexistence with pre-autotune fingerprints, and the
two-process fleet-sharing acceptance path."""
import json
import os

import pytest

from mxnet_tpu import autotune
from mxnet_tpu.autotune import records, registry
from mxnet_tpu.base import MXNetError

DEC = "unit.synthetic"


def _declare():
    return autotune.declare_decision(
        DEC, candidates=(1, 2, 3), default=2, key_doc="(backend,)")


@pytest.fixture
def tuned(tmp_path, monkeypatch):
    """Isolated record dir + clean counters; mode = consult."""
    monkeypatch.setenv("MXNET_AUTOTUNE_DIR", str(tmp_path / "atr"))
    monkeypatch.setenv("MXNET_AUTOTUNE", "consult")
    autotune.reset_autotune_state()
    _declare()
    yield autotune
    autotune.reset_autotune_state()


# ---------------------------------------------------------------------------
# registry

def test_declare_returns_default_and_is_idempotent(tuned):
    assert _declare() == 2  # same declaration: fine, returns default
    point = autotune.get_point(DEC)
    assert point.candidates == (1, 2, 3) and point.default == 2


def test_conflicting_redeclaration_raises(tuned):
    with pytest.raises(MXNetError, match="already declared"):
        autotune.declare_decision(DEC, candidates=(1, 2), default=1)


def test_builtin_decision_points_cataloged(tuned):
    names = autotune.decision_points()
    assert list(names) == sorted(names)
    for expect in ("fusion.min_cluster", "fusion.attn_compute_bound_seq",
                   "fusion.elementwise_bandwidth_log2",
                   "quantize.lowering"):
        assert expect in names


def test_unknown_point_raises(tuned):
    with pytest.raises(MXNetError, match="unknown decision"):
        autotune.get_point("no.such.decision")


# ---------------------------------------------------------------------------
# mode knob

def test_mode_values(tuned, monkeypatch):
    assert autotune.mode() == "consult"
    for raw, want in (("0", "0"), ("off", "0"), ("false", "0"),
                      ("tune", "tune"), ("CONSULT", "consult")):
        monkeypatch.setenv("MXNET_AUTOTUNE", raw)
        assert autotune.mode() == want
    monkeypatch.setenv("MXNET_AUTOTUNE", "bogus")
    with pytest.raises(MXNetError, match="MXNET_AUTOTUNE"):
        autotune.mode()


def test_mode_off_short_circuits_lookup(tuned, monkeypatch):
    records.store_record(DEC, ("cpu",), 3)
    monkeypatch.setenv("MXNET_AUTOTUNE", "0")
    assert autotune.lookup(DEC, ("cpu",)) is None
    c = autotune.counters()
    assert c["lookups"] == 1 and c["hits"] == 0
    # and the salt provider contributes nothing when off
    assert autotune.autotune_salt() == ()


def test_tune_requires_tune_mode(tuned):
    with pytest.raises(MXNetError, match="MXNET_AUTOTUNE=tune"):
        autotune.tune(DEC, ("cpu",), lambda choice: (lambda: 1.0))


# ---------------------------------------------------------------------------
# record lifecycle: memory / disk tiers

def test_store_then_consult_and_disk_roundtrip_bitwise(tuned):
    rec = records.store_record(DEC, ("cpu",), 3,
                               extra={"speedup": 1.25, "won": True})
    fp = records.record_fingerprint(DEC, ("cpu",))
    path = os.path.join(records.records_dir(), fp + ".atr")
    with open(path, "rb") as f:
        blob1 = f.read()
    assert json.loads(blob1) == rec  # what's on disk IS the record
    # storing the same record again is byte-identical (sorted keys,
    # fixed indent — the file format is canonical)
    records.store_record(DEC, ("cpu",), 3,
                         extra={"speedup": 1.25, "won": True})
    with open(path, "rb") as f:
        assert f.read() == blob1
    assert autotune.lookup(DEC, ("cpu",)) == 3
    assert autotune.counters()["hits"] == 1


def test_records_survive_restart(tuned):
    records.store_record(DEC, ("cpu",), 1)
    # "restart": drop every in-memory tier, keep the disk files
    records.reset_record_state()
    assert records.consult(DEC, ("cpu",)) == 1
    assert autotune.counters()["record_load"] == 1


def test_store_rejects_choice_outside_candidates(tuned):
    with pytest.raises(MXNetError, match="outside the declared"):
        records.store_record(DEC, ("cpu",), 99)


def test_unfingerprintable_key_is_heuristic_only(tuned):
    key = (object(),)  # repr carries a memory address: not stable
    assert records.record_fingerprint(DEC, key) is None
    assert records.store_record(DEC, key, 1) is None
    assert records.consult(DEC, key) is None


# ---------------------------------------------------------------------------
# corrupt / drifted records: miss + removal, never a crash

def _plant(tuned, blob):
    fp = records.record_fingerprint(DEC, ("cpu",))
    d = records.records_dir()
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, fp + ".atr")
    with open(path, "wb") as f:
        f.write(blob)
    return path


def test_corrupt_record_is_miss_and_removed(tuned):
    path = _plant(tuned, b"{not json")
    assert records.consult(DEC, ("cpu",)) is None
    assert not os.path.exists(path), "corrupt file must be removed"
    assert autotune.counters()["record_corrupt"] == 1


def test_version_drifted_record_is_miss_and_removed(tuned):
    stale = {"version": 0, "decision": DEC, "key": "('cpu',)",
             "choice": 3}
    path = _plant(tuned, json.dumps(stale).encode())
    assert records.consult(DEC, ("cpu",)) is None
    assert not os.path.exists(path)
    assert autotune.counters()["record_corrupt"] == 1


def test_out_of_candidates_record_is_miss_and_removed(tuned):
    bad = {"version": records.RECORD_VERSION, "decision": DEC,
           "key": "('cpu',)", "choice": 99}
    path = _plant(tuned, json.dumps(bad).encode())
    assert records.consult(DEC, ("cpu",)) is None
    assert not os.path.exists(path)


def test_corrupt_record_never_breaks_decide(tuned):
    """A consult inside the fusion cost model degrades to the heuristic
    when the stored record is garbage — the decision still returns."""
    from mxnet_tpu.kernels import cost_model

    fp = records.record_fingerprint("fusion.min_cluster", ("cpu",))
    d = records.records_dir()
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, fp + ".atr"), "wb") as f:
        f.write(b"\x00garbage\xff")
    dec = cost_model.decide("elementwise", 3, out_shape=(8, 8),
                            backend="cpu")
    assert dec.fuse  # heuristic default (min_cluster=2) applied
    assert autotune.counters()["record_corrupt"] == 1


# ---------------------------------------------------------------------------
# trial overrides

def test_trial_overrides_and_shadows_stored_record(tuned):
    records.store_record(DEC, ("cpu",), 1)
    with records.trial(DEC, ("cpu",), 3):
        assert records.trial_active()
        assert autotune.lookup(DEC, ("cpu",)) == 3
        # the salt carries the trial, marked distinctly from a record
        entries = records.active_entries()
        assert any(c == "trial:3" for _, _, c in entries), entries
    assert autotune.lookup(DEC, ("cpu",)) == 1
    assert not records.trial_active()


def test_nested_trial_same_key_raises(tuned):
    with records.trial(DEC, ("cpu",), 1):
        with pytest.raises(MXNetError, match="nested trial"):
            with records.trial(DEC, ("cpu",), 2):
                pass
    assert not records.trial_active()  # cleanly unwound


# ---------------------------------------------------------------------------
# consult hooks in the shipped policies

def test_decide_consults_min_cluster_record(tuned):
    from mxnet_tpu.kernels import cost_model

    assert cost_model.decide("elementwise", 3, out_shape=(8, 8),
                             backend="cpu").fuse
    with records.trial("fusion.min_cluster", ("cpu",), 4):
        dec = cost_model.decide("elementwise", 3, out_shape=(8, 8),
                                backend="cpu")
    assert not dec.fuse and dec.reason == "too_small"


def test_decide_consults_attention_bound_by_feat_bucket(tuned):
    from mxnet_tpu.kernels import cost_model

    kw = dict(out_shape=(4, 64, 48), backend="cpu",
              score_shape=(4, 64, 64))
    # default bound 64: seq 64 is compute-bound -> unfused
    assert cost_model.decide("attention", 5, **kw).reason == \
        "compute_bound_attention"
    # a record for THIS feat bucket (48 -> 64) flips it
    with records.trial("fusion.attn_compute_bound_seq",
                       ("cpu", 64), 4096):
        assert cost_model.decide("attention", 5, **kw).fuse
    # a record for a DIFFERENT bucket does not
    with records.trial("fusion.attn_compute_bound_seq",
                       ("cpu", 128), 4096):
        assert not cost_model.decide("attention", 5, **kw).fuse


def test_decide_consults_elementwise_bandwidth_cap(tuned):
    from mxnet_tpu.kernels import cost_model

    big = (2048, 4096)  # 2**23 elements: above the default 2**22 cap
    assert cost_model.decide("elementwise", 7, out_shape=big,
                             backend="cpu").reason == "bandwidth_bound"
    with records.trial("fusion.elementwise_bandwidth_log2",
                       ("cpu",), 24):
        assert cost_model.decide("elementwise", 7, out_shape=big,
                                 backend="cpu").fuse


def test_quantize_lowering_consults_record(tuned, monkeypatch):
    from mxnet_tpu.ndarray import ops_quant

    monkeypatch.delenv("MXNET_QUANTIZE_LOWERING", raising=False)
    heuristic = ops_quant.lowering()  # dequant on cpu
    assert heuristic == "dequant"
    with records.trial("quantize.lowering", ("cpu",), "native"):
        assert ops_quant.lowering() == "native"
    # an explicit env choice always beats the record
    monkeypatch.setenv("MXNET_QUANTIZE_LOWERING", "dequant")
    with records.trial("quantize.lowering", ("cpu",), "native"):
        assert ops_quant.lowering() == "dequant"


# ---------------------------------------------------------------------------
# salt coexistence: record-absent fingerprints stay byte-identical

def test_autotune_salt_declared_but_inactive_keeps_fingerprint(tuned):
    from mxnet_tpu import artifact

    key = ("unit", "coexist")
    bare = artifact.CompiledArtifact("unit_autotune", key).fingerprint
    declared = artifact.CompiledArtifact(
        "unit_autotune", key, salts=("autotune",)).fingerprint
    # no active record: adding the salt to the declaration must NOT
    # move the fingerprint (warm pre-autotune caches stay warm)
    assert declared == bare

    records.store_record(DEC, ("cpu",), 3)
    tuned_fp = artifact.CompiledArtifact(
        "unit_autotune", key, salts=("autotune",)).fingerprint
    assert tuned_fp != bare  # a live record separates the executables
    undeclared = artifact.CompiledArtifact(
        "unit_autotune", key).fingerprint
    assert undeclared == bare  # undeclared artifacts unaffected


def test_salt_content_and_graph_opt_tag_form(tuned):
    assert autotune.autotune_salt() == ()
    records.store_record(DEC, ("cpu",), 3)
    salt = autotune.autotune_salt()
    assert salt[0] == "autotune" and salt[1] == records.RECORD_VERSION
    assert (DEC, "('cpu',)", "3") in salt[2:]
    # dropping the directory empties the salt again (scan authority)
    for fn in os.listdir(records.records_dir()):
        os.remove(os.path.join(records.records_dir(), fn))
    records.reset_record_state()
    assert autotune.autotune_salt() == ()


# ---------------------------------------------------------------------------
# the paired-median harness the tuner prices candidates with

def _scripted(costs, calls, side):
    it = iter(costs)

    def measure():
        calls.append(side)
        return next(it)
    return measure


@pytest.mark.parametrize("case", ["median_over_pairs",
                                  "orders_alternate", "raise_propagates"])
def test_paired_speedup(case):
    from mxnet_tpu.autotune._measure import paired_speedup

    calls = []
    if case == "median_over_pairs":
        # per-pair base/test ratios 2, 4, 1: the median pair decides,
        # not the best window of either side
        got = paired_speedup(_scripted([2.0, 4.0, 3.0], calls, "base"),
                             _scripted([1.0, 1.0, 3.0], calls, "test"),
                             pairs=3)
        assert got == (2.0, 1.0, 2.0)
    elif case == "orders_alternate":
        # test first, then base first: drift inside a pair cancels in
        # the median; each half is the min of its ``reps`` windows
        got = paired_speedup(_scripted([4.0, 2.0, 2.0, 6.0], calls, "base"),
                             _scripted([9.0, 1.0, 1.0, 5.0], calls, "test"),
                             pairs=2, reps=2)
        assert calls == ["test", "test", "base", "base",
                         "base", "base", "test", "test"]
        assert got == (2.0, 1.0, 2.0)
    else:
        def broken():
            raise RuntimeError("candidate blew up")

        with pytest.raises(RuntimeError, match="blew up"):
            paired_speedup(lambda: 1.0, broken, pairs=2)


# ---------------------------------------------------------------------------
# tuner: sweep, no-win pin, budget, fault seam

def _fake_measure(costs):
    """make_measure returning constant synthetic 'timings': choice ->
    seconds per window (None = the heuristic default workload)."""
    def factory(choice):
        cost = costs[choice]
        return lambda: cost
    return factory


def test_tune_persists_winner_and_consults_back(tuned, monkeypatch):
    monkeypatch.setenv("MXNET_AUTOTUNE", "tune")
    rec = autotune.tune(DEC, ("cpu",),
                        _fake_measure({None: 1.0, 1: 1.0, 2: 1.0,
                                       3: 0.5}),
                        pairs=2)
    assert rec["choice"] == 3 and rec["won"] is True
    assert rec["speedup"] == pytest.approx(2.0)
    monkeypatch.setenv("MXNET_AUTOTUNE", "consult")
    assert autotune.lookup(DEC, ("cpu",)) == 3
    c = autotune.counters()
    assert c["measurements"] == 3 and c["wins"] == 1


def test_tune_no_win_pins_default_identity(tuned, monkeypatch):
    monkeypatch.setenv("MXNET_AUTOTUNE", "tune")
    rec = autotune.tune(DEC, ("cpu",),
                        _fake_measure({None: 1.0, 1: 1.01, 2: 1.0,
                                       3: 1.005}),
                        pairs=2)
    # nothing beat the default by min_speedup: the DEFAULT is pinned
    # with identity speedup so consults hit without changing behavior
    assert rec["choice"] == 2 and rec["won"] is False
    assert rec["speedup"] == 1.0
    assert autotune.counters()["wins"] == 0
    assert records.consult(DEC, ("cpu",)) == 2


def test_tune_budget_stops_between_candidates(tuned, monkeypatch):
    import time as _time

    monkeypatch.setenv("MXNET_AUTOTUNE", "tune")

    def factory(choice):
        def window():
            _time.sleep(0.02)
            return 1.0
        return window

    rec = autotune.tune(DEC, ("cpu",), factory, pairs=1, budget_ms=1)
    # the first candidate always completes; the budget stops the rest
    assert rec["budget_stopped"] is True
    assert len(rec["measured"]) == 1


def test_tune_fault_seam_skips_candidate(tuned, monkeypatch):
    from mxnet_tpu.resilience import faults

    monkeypatch.setenv("MXNET_AUTOTUNE", "tune")
    with faults.inject("autotune_measure", at=1):
        rec = autotune.tune(DEC, ("cpu",),
                            _fake_measure({None: 1.0, 1: 1.0, 2: 1.0,
                                           3: 0.5}),
                            pairs=2)
    # candidate 1 was skipped by the injected fault; the sweep degraded
    # to the remaining candidates instead of crashing
    assert rec["skipped"] == [1]
    assert [m["choice"] for m in rec["measured"]] == [2, 3]
    assert rec["choice"] == 3
    assert autotune.counters()["measure_failures"] == 1


def test_tune_all_candidates_failing_raises(tuned, monkeypatch):
    from mxnet_tpu.resilience import faults

    monkeypatch.setenv("MXNET_AUTOTUNE", "tune")
    with faults.inject("autotune_measure", every=1, times=3):
        with pytest.raises(MXNetError, match="measured no candidate"):
            autotune.tune(DEC, ("cpu",),
                          _fake_measure({None: 1.0, 1: 1.0, 2: 1.0,
                                         3: 1.0}))


# ---------------------------------------------------------------------------
# fleet sharing: one replica tunes, the fleet consults with zero
# measurements (the round-20 remote artifact tier verbatim)

_CHILD = """
import json, os
from mxnet_tpu import autotune
from mxnet_tpu.autotune import records
autotune.declare_decision(
    "unit.synthetic", candidates=(1, 2, 3), default=2,
    key_doc="(backend,)")
"""


def test_fleet_record_sharing_zero_measurements(
        forced_device_subprocess, tmp_path):
    """Acceptance: replica A tunes and publishes; replica B (fresh dir,
    same remote) consults A's record having measured NOTHING, and the
    record is written through to B's disk for its next restart."""
    remote = {"MXNET_ARTIFACT_REMOTE": "file://" + str(tmp_path / "fleet")}
    a = forced_device_subprocess(_CHILD + """
rec = autotune.tune(
    "unit.synthetic", ("cpu",),
    lambda choice: (lambda: {None: 1.0, 1: 1.0, 2: 1.0, 3: 0.5}[choice]),
    pairs=2)
print(json.dumps({"choice": rec["choice"], "won": rec["won"],
                  "counters": autotune.counters()}))
""", env=dict(remote, MXNET_AUTOTUNE="tune",
              MXNET_AUTOTUNE_DIR=str(tmp_path / "atr_a")))
    assert a["choice"] == 3 and a["won"] is True
    assert a["counters"]["measurements"] == 3

    b_dir = str(tmp_path / "atr_b")
    b = forced_device_subprocess(_CHILD + """
choice = autotune.lookup("unit.synthetic", ("cpu",))
on_disk = sorted(os.listdir(records.records_dir()))
print(json.dumps({"choice": choice, "counters": autotune.counters(),
                  "disk": on_disk}))
""", env=dict(remote, MXNET_AUTOTUNE="consult",
              MXNET_AUTOTUNE_DIR=b_dir))
    assert b["choice"] == 3, "B must consult A's tuned record"
    assert b["counters"]["measurements"] == 0, \
        "the fleet consumes records WITHOUT measuring"
    assert b["counters"]["hits"] == 1
    assert len(b["disk"]) == 1, "remote hit must write through to disk"

    # restart of B: the write-through serves from disk, no remote
    b2 = forced_device_subprocess(_CHILD + """
choice = autotune.lookup("unit.synthetic", ("cpu",))
from mxnet_tpu.artifact import remote
print(json.dumps({"choice": choice,
                  "remote_hits": remote.STATS.snapshot().get(
                      "remote_hits", 0)}))
""", env=dict(remote, MXNET_AUTOTUNE="consult",
              MXNET_AUTOTUNE_DIR=b_dir))
    assert b2["choice"] == 3
    assert b2["remote_hits"] == 0, "disk tier must serve the restart"
