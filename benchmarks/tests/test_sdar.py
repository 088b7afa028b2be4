"""Configuration ``sdar-30b-a3b`` and its cell on the CPU: the rehearsal
through the unedited harness (a sound float32 run is ``correct``, the
fp8 control and each planted fault are not), the operation counts of
``flops/sdar-30b-a3b.py`` against brute force over the mask and over a
routed batch, and the two readers this configuration brings
(``kernel_roofline_named``, ``moe_counters``) on ``testdata/``'s traces
and on hand-made contexts."""
import argparse
import json
import os
import types

import numpy as onp
import pytest

import correct
import run
import trace_reduce as tr

CELL = "sdar30b-train-bd-s4096"
DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
flops = run.load_module("flops", "sdar-30b-a3b.py")
named = run.load_module("metrics", "readers", "kernel_roofline_named.py")
moe_counters = run.load_module("metrics", "readers", "moe_counters.py")
PEAK = run.load_json("peaks.json")["device_kinds"]["TPU v5 lite"]


def _cfg():
    return run.load_json("configs", "sdar-30b-a3b.json")


def _traffic():
    return run.load_json("traffic", "train-bd-s4096.json")


# ---------------------------------------------------------------------------
# the files as ISSUE 30 and the catalog state them

def test_configuration_keeps_every_published_width():
    cfg = _cfg()
    published = dict(hidden_size=2048, num_attention_heads=32,
                     num_key_value_heads=4, head_dim=128,
                     moe_intermediate_size=768, num_experts_per_tok=8,
                     rope_theta=1000000, rms_norm_eps=1e-06,
                     norm_topk_prob=True, intermediate_size=6144,
                     max_position_embeddings=32768, tie_word_embeddings=False)
    assert {k: cfg[k] for k in published} == published
    assert cfg["router_experts"] == cfg["published"]["num_experts"] == 128
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 18992)
    assert cfg["published"]["vocab_size"] == 8 * cfg["vocab_size"]
    assert "8 chips share each layer" in cfg["deployment"]
    # the two departures from ISSUE 30's list carry their reasons
    assert cfg["qk_norm_init"] == 2.0 and "qk_norm_init" in cfg["assumed"]
    assert cfg["train"]["optimizer"]["learning_rate"] == 1e-7
    assert "1e-7" in cfg["assumed"]["optimizer"]
    entry = [c for c in BENCH["configs"] if c["name"] == "sdar-30b-a3b"][0]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == \
        cfg["source"]


def test_cell_is_listed_where_its_metrics_are_read():
    cell = [w for w in BENCH["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("sdar-30b-a3b", "train-bd-s4096", 1)
    tr_ = _traffic()
    assert (tr_["kind"], tr_["seq"], tr_["batch_per_chip"],
            tr_["block_length"], tr_["pool"], tr_["feed_depth"]) == \
        ("train", 4096, 2, 4, 8, 2)
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in BENCH[g] if CELL in m.get("workloads", ())}
    for name in ("train_tokens_per_s", "step_ms_p90", "mfu.tokens",
                 "attn_fwd_ms.tokens", "attn_bwd_ms.tokens",
                 "moe_gmm_ms.tokens", "moe_gmm_roofline.tokens",
                 "bd_attn_fwd_roofline.tokens", "bd_attn_bwd_roofline.tokens",
                 "moe_rows_per_expert.tokens",
                 "moe_load_max_over_mean.tokens", "retraces.tokens"):
        assert name in listed, name
    assert "flash_fwd_roofline.tokens" not in listed
    for m in BENCH["per_layer"]:        # every listed metric has its file
        if CELL in m.get("workloads", ()):
            spec = run.load_json("metrics", m["name"] + ".json")
            assert os.path.exists(os.path.join(
                run.HERE, "metrics", "readers", spec["reader"] + ".py"))


def test_batches_follow_the_noise_schedule():
    model = run.load_module("models", "sdar-30b-a3b.py")
    cfg, tr_ = dict(_cfg(), vocab_size=512), dict(_traffic(), seq=256)
    x, y = model.make_batch(cfg, tr_, 3, onp.random.default_rng(5))
    L, b, mask_id = 256, 4, 511
    assert x.shape == (3, 2 * L) and x.dtype == onp.int32
    assert y.shape == (3, 2, L) and y.dtype == onp.float32
    xt, x0, target, weight = x[:, :L], x[:, L:], y[:, 0], y[:, 1]
    assert (x0 < mask_id).all() and (target == x0).all()
    masked = xt == mask_id
    assert ((xt == x0) | masked).all()
    assert ((weight > 0) == masked).all()
    # one rate a block: 1/t is the same for the block's masked tokens
    t = 1.0 / onp.maximum(weight, 1e-9)
    blocks = masked.reshape(3, L // b, b)
    hi = onp.where(blocks, t.reshape(blocks.shape), -onp.inf).max(-1)
    lo = onp.where(blocks, t.reshape(blocks.shape), onp.inf).min(-1)
    some = blocks.any(-1)
    assert some.any() and (hi[some] - lo[some]).max() < 1e-5
    assert t[masked].min() >= 0.05 - 1e-6 and t[masked].max() <= 1.0 + 1e-6
    assert model.items_per_batch(cfg, tr_, 3) == 3 * L
    with pytest.raises(ValueError):
        model.make_batch(cfg, dict(tr_, block_length=8), 3,
                         onp.random.default_rng(5))


# ---------------------------------------------------------------------------
# the rehearsal: correct has to be able to fail

def _float32(ctx):
    ctx.cfg["train"]["compute_dtype"] = "float32"


def _run(fault=None, seed=4_100_000_007):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.5, trace=0,
                              rehearse=True)
    return run.execute(args, fault=fault, tweak=_float32)


def test_sound_float32_run_is_correct():
    res = _run()
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["compared"]      # the cell has limits


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_planted_fault_is_not_correct(fault):
    res = _run(fault)
    assert res["correct"] is False, res["compared"]
    over = {k for k, v in res["compared"].items() if v["value"] > v["limit"]}
    # the loss is compared in this cell: half a batch moves it past its
    # limit; a state left unchanged leaves the losses and fails the change
    assert {"loss_gap", "loss1_gap"} <= set(res["compared"])
    assert over >= ({"loss_gap", "loss1_gap"} if fault == "half_batch"
                    else {"delta_gap", "delta_med_gap"}), res["compared"]


def test_fp8_control_is_not_correct():
    args = argparse.Namespace(workload=CELL, seed=4_100_000_011, seconds=0.5,
                              trace=0, rehearse=True)
    ctx = run.Ctx(BENCH, run.find_cell(BENCH, CELL), args)
    _float32(ctx)
    run.look_for_chip(ctx)
    loop = run.load_module("loops", "train.py").Loop(ctx)
    loop.setup()
    loop.release()
    sound = loop.verify()
    assert sound and all(v["value"] <= v["limit"] for v in sound.values())
    assert ctx.cfg["train"]["control_precision"] == "fp8"
    control = correct.with_limits(loop.control(), ctx.limits)
    assert any(v["value"] > v["limit"] for v in control.values()), control
    # the expert layer's counts are part of what both sides give
    assert any(k.endswith(".moe.rows") for k in loop.ref_readings["stat"])


# ---------------------------------------------------------------------------
# operations and bytes against brute force

@pytest.mark.parametrize("seq,block", [(64, 4), (96, 8), (128, 1)])
def test_live_pairs_are_the_masks_count(seq, block):
    ref = run.load_module("reference", "sdar-30b-a3b.py")
    mask = onp.asarray(ref.live_mask(seq, block))
    assert mask.shape == (2 * seq, 2 * seq)
    assert flops.live_pairs(seq, block) == int(mask.sum())


def test_attention_counts_over_the_mask_by_brute_force():
    ref = run.load_module("reference", "sdar-30b-a3b.py")
    cfg, tr_ = dict(_cfg(), num_hidden_layers=2), dict(_traffic(), seq=128)
    live = int(onp.asarray(ref.live_mask(128, 4)).sum())
    calls, hq, hkv, d = 2 * tr_["batch_per_chip"], 32, 4, 128
    f_fwd, b_fwd = flops.bd_attn_fwd(cfg, tr_)
    f_bwd, b_bwd = flops.bd_attn_bwd(cfg, tr_)
    assert f_fwd == calls * hq * live * 2 * (2 * d)     # QK^T and PV
    assert f_bwd == calls * hq * live * 5 * (2 * d)     # five products
    q_arr, kv_arr = 256 * hq * d * 2, 256 * hkv * d * 2     # bf16
    assert b_fwd == calls * (2 * q_arr + 2 * kv_arr)    # q o | k v once
    assert b_bwd == calls * (3 * q_arr + 4 * kv_arr)    # q do dq | k v dk dv
    assert flops.flash_fwd_shape(cfg, tr_) == (2 * 32, 256, 128)


def test_grouped_products_counted_over_a_routed_batch():
    """A batch routed by a random router: the assignments that land on
    the 16 held of 128 experts, each through three products of gate+up
    and three of down, are what ``moe_gmm`` counts to the routing's own
    scatter (the count is of the expected rows)."""
    cfg, tr_ = dict(_cfg(), num_hidden_layers=1), dict(_traffic(), seq=512)
    positions = 2 * tr_["seq"] * tr_["batch_per_chip"]
    rs = onp.random.default_rng(3)
    logits = rs.standard_normal((positions, cfg["router_experts"]))
    top = onp.argsort(-logits, -1)[:, :cfg["num_experts_per_tok"]]
    rows = int((top < cfg["num_experts"]).sum())
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    by_hand = rows * 3 * (2 * e * 2 * f + 2 * f * e)
    got, nbytes = flops.moe_gmm(cfg, tr_)
    assert flops.held_per_position(cfg) == 1.0
    assert got == pytest.approx(by_hand, rel=0.05)
    assert got == positions * 3 * (2 * e * 2 * f + 2 * f * e)
    weights = 16 * (e * 2 * f + f * e) * 2
    assert nbytes == pytest.approx(
        3 * (weights + positions * (e + 2 * f + f + e) * 2), rel=1e-12)


def test_model_flops_per_clean_token():
    cfg, tr_ = _cfg(), _traffic()
    e, d, hq, hkv, f, L = 2048, 128, 32, 4, 768, 4096
    position = 2 * e * (hq + 2 * hkv) * d + 2 * hq * d * e + 2 * e * 128 \
        + 1.0 * 3 * 2 * e * f
    attention = (L * L + L * 4) / L * hq * 2 * 2 * d
    forward = 4 * (2 * position + attention) + 2 * e * 18992
    assert flops.forward_flops_per_item(cfg, L) == pytest.approx(forward)
    assert flops.train_flops_per_item(cfg, tr_) == pytest.approx(3 * forward)
    # ISSUE 30's reckoning: 17.9 TFLOP of model work a step of 8,192 tokens
    assert 8192 * 3 * forward == pytest.approx(17.9e12, rel=0.02)


# ---------------------------------------------------------------------------
# the readers

def _trace_ctx(events, work, rate=None, items=512):
    trace = {"devices": {0: events}, "host": []}
    summ = tr.summary(trace)
    measured = {"trace_summary": summ, "items_per_step": items}
    if rate is not None:
        measured["traced_rate"] = rate
    return types.SimpleNamespace(
        trace=trace, peak=PEAK, measured=measured, cfg={}, traffic={},
        flops=types.SimpleNamespace(**work))


def test_named_roofline_on_hand_made_ops():
    ev = [("%moe_gmm_fwd.1 = bf16[512,256]{1,0} custom-call(x)", 0, 4000),
          ("%moe_gmm_dlhs.1 = bf16[512,128]{1,0} custom-call(x)", 5000, 3000),
          ("%moe_gmm_drhs.2 = bf16[4,128,256]{2,1,0} custom-call(x)", 9000,
           3000),
          ("%fusion.7 = bf16[8] fusion(x)", 13000, 7000)]
    # the window is 20 us; at 512 items a step and 51.2 M items/s it
    # holds two steps; a step's work has a 2 us compute roof
    work = {"moe_gmm": lambda cfg, traffic: (2e-6 * PEAK["bf16_flops"], 1.0)}
    ctx = _trace_ctx(ev, work, rate=51.2e6)
    got = named.read(ctx, "moe_gmm", "^%moe_gmm_")
    assert got == pytest.approx(100 * 2 * 2e-6 / 10e-6)
    # memory-bound work is held to the bytes' roof
    work = {"moe_gmm": lambda cfg, traffic: (
        1.0, 1e-6 * PEAK["hbm_bytes_per_s"])}
    ctx = _trace_ctx(ev, work, rate=51.2e6)
    assert named.read(ctx, "moe_gmm", "^%moe_gmm_") == \
        pytest.approx(100 * 2 * 1e-6 / 10e-6)


def test_named_roofline_returns_nothing_not_zero():
    ev = [("%fusion.7 = bf16[8] fusion(x)", 0, 7000)]
    work = {"moe_gmm": lambda cfg, traffic: (1e9, 1e6)}
    # the kernel did not run
    assert named.read(_trace_ctx(ev, work, rate=1e6), "moe_gmm",
                      "^%moe_gmm_") is None
    ran = [("%moe_gmm_fwd.1 = bf16[8] custom-call(x)", 0, 7000)]
    # the configuration counts no such kernel (the accepted cell's flops)
    assert named.read(_trace_ctx(ran, {}, rate=1e6), "moe_gmm",
                      "^%moe_gmm_") is None
    # an untraced run, and a run without a rate
    ctx = _trace_ctx(ran, work, rate=1e6)
    ctx.trace = None
    assert named.read(ctx, "moe_gmm", "^%moe_gmm_") is None
    assert named.read(_trace_ctx(ran, work), "moe_gmm", "^%moe_gmm_") is None
    assert named.read(_trace_ctx(ran, work, rate=1e6), "moe_gmm",
                      "^%moe_gmm_") > 0


def test_named_roofline_on_the_recorded_spmd_trace():
    """``testdata/tiny_spmd.xplane.pb`` (PR 28): two steps of two layers,
    so four ops named ``flash_fwd``; found by the metric's own pattern
    where ``op_time`` finds them, and no grouped product ran there."""
    trace = tr.load(os.path.join(DATA, "tiny_spmd.xplane.pb"))
    summ = tr.summary(trace)
    spec = run.load_json("metrics", "bd_attn_fwd_roofline.tokens.json")
    assert spec["reader"] == "kernel_roofline_named"
    steps = 2.0
    items = 4096
    rate = steps * items / summ["window_s"]
    roof = 1e-5         # seconds of compute a step, by this test's count
    ctx = types.SimpleNamespace(
        trace=trace, peak=PEAK, cfg={}, traffic={},
        measured={"trace_summary": summ, "traced_rate": rate,
                  "items_per_step": items},
        flops=types.SimpleNamespace(
            bd_attn_fwd=lambda c, t: (roof * PEAK["bf16_flops"], 0.0),
            moe_gmm=lambda c, t: (1e9, 1e6)))
    got = named.read(ctx, **spec["args"])
    assert got == pytest.approx(100 * steps * roof / 0.001128768, rel=1e-6)
    spec = run.load_json("metrics", "moe_gmm_roofline.tokens.json")
    assert named.read(ctx, **spec["args"]) is None


def test_recorded_moe_trace_names_the_grouped_products():
    """``testdata/tiny_moe.xplane.pb`` (one v5e, my chip run, PR 30: two
    steps of a 2-layer ``MoEDecoderLM`` under block diffusion, hidden
    128, 2 query heads over 1 key/value head of 128, 8 experts top-2 with
    4 held, through ``SPMDTrainer``): each step and layer runs the
    grouped product twice (gate+up, down) with both backward products,
    and the two masked flash kernels once; the overflow passes' copies
    of them sit in loops that never ran."""
    op_time = run.load_module("metrics", "readers", "op_time.py")
    trace = tr.load(os.path.join(DATA, "tiny_moe.xplane.pb"))
    ev = trace["devices"][0]
    summ = tr.summary(trace)
    for name, calls in (("moe_gmm_fwd", 8), ("moe_gmm_dlhs", 8),
                        ("moe_gmm_drhs", 8), ("flash_fwd", 4),
                        ("flash_bwd", 4)):
        _, n = op_time.device_seconds(ev, summ["window"],
                                      r"^%" + name + r"[.\d]* = ")
        assert n == calls, (name, n)
    spec = run.load_json("metrics", "moe_gmm_ms.tokens.json")
    assert spec["reader"] == "op_time"
    seconds, n = op_time.device_seconds(ev, summ["window"],
                                        spec["args"]["match"])
    assert n == 24 and seconds > 0
    items = 128                         # clean tokens a step
    ctx = types.SimpleNamespace(
        trace=trace, peak=PEAK, cfg={}, traffic={},
        measured={"trace_summary": summ, "items_per_step": items,
                  "traced_rate": 2 * items / summ["window_s"]},
        flops=types.SimpleNamespace(
            moe_gmm=lambda c, t: (1e-6 * PEAK["bf16_flops"], 0.0)))
    assert op_time.read(ctx, **spec["args"]) == \
        pytest.approx(seconds * 1e3 / 2)
    spec = run.load_json("metrics", "moe_gmm_roofline.tokens.json")
    assert named.read(ctx, **spec["args"]) == \
        pytest.approx(100 * 2 * 1e-6 / seconds)
    spec = run.load_json("metrics", "attn_bwd_ms.tokens.json")
    ctx.flops.flash_fwd_shape = lambda c, t: (2 * 2, 128, 128)
    assert op_time.read(ctx, **spec["args"]) > 0


def test_counter_reader_on_a_hand_made_family(monkeypatch):
    cfg = {"num_hidden_layers": 4, "num_experts": 16}
    ctx = types.SimpleNamespace(cfg=cfg)
    counted = {"steps": 10, "assignments_held": 10 * 4 * 16 * 1000,
               "max_expert_rows": 1250}
    monkeypatch.setattr(moe_counters, "counters", lambda: dict(counted))
    assert moe_counters.read(ctx, "rows_per_expert") == pytest.approx(1000)
    assert moe_counters.read(ctx, "max_over_mean") == pytest.approx(1.25)
    with pytest.raises(ValueError):
        moe_counters.read(ctx, "no_such_reading")
    # no step published, no such family, no such program: nothing, not 0
    for nothing in ({"steps": 0, "assignments_held": 0}, {}, None):
        monkeypatch.setattr(moe_counters, "counters", lambda n=nothing: n)
        assert moe_counters.read(ctx, "rows_per_expert") is None
        assert moe_counters.read(ctx, "max_over_mean") is None


def test_counter_reader_reads_the_programs_family():
    from mxnet_tpu.parallel.moe import note_expert_rows

    before = moe_counters.counters() or {}
    note_expert_rows([onp.array([3.0, 5.0]), onp.array([7.0, 1.0])])
    after = moe_counters.counters()
    assert after["steps"] == before.get("steps", 0) + 1
    assert after["assignments_held"] == \
        before.get("assignments_held", 0) + 16
    assert after["max_expert_rows"] == 7
