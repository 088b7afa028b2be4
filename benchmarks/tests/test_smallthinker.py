"""Configuration ``smallthinker-21b-a3b`` and its cell on the CPU: the
files as ISSUE 34 and the catalog state them, the operation counts of
``flops/smallthinker-21b-a3b.py`` against brute force over the reference's
own masks and over a routed batch, and the rehearsal through the unedited
harness (a sound float32 run is ``correct``, the fp8 control and each
planted fault are not)."""
import argparse
import json
import os

import numpy as onp
import pytest

import correct
import run

CELL = "smallthinker21b-train-s16384"
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
flops = run.load_module("flops", "smallthinker-21b-a3b.py")


def _cfg():
    return run.load_json("configs", "smallthinker-21b-a3b.json")


def _traffic():
    return run.load_json("traffic", "train-lm-1x16384.json")


# ---------------------------------------------------------------------------
# the files as ISSUE 34 and the catalog state them

def test_configuration_keeps_every_published_width():
    cfg = _cfg()
    published = dict(
        head_dim=128, hidden_size=2560, max_position_embeddings=16384,
        model_name="smallthinker_21b_instruct", moe_ffn_hidden_size=768,
        moe_num_active_primary_experts=6,
        moe_primary_router_apply_softmax=True, norm_topk_prob=True,
        num_attention_heads=28, num_key_value_heads=4, rms_norm_eps=1e-06,
        rope_layout=[0, 1, 1, 1] * 13, rope_scaling=None,
        rope_theta=1500000, sliding_window_layout=[0, 1, 1, 1] * 13,
        sliding_window_size=4096, tie_word_embeddings=False)
    assert {k: cfg[k] for k in published} == published
    assert cfg["published"] == dict(num_hidden_layers=52,
                                    moe_num_primary_experts=64,
                                    vocab_size=151936)
    assert cfg["reduced"] == ["num_hidden_layers", "moe_num_primary_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
            cfg["vocab_size"]) == (4, 8, 18992)
    assert cfg["router_experts"] == 64 and cfg["experts_first"] == 0
    assert cfg["num_experts"] == cfg["moe_num_primary_experts"]
    assert cfg["published"]["vocab_size"] == 8 * cfg["vocab_size"]
    assert cfg["deployment"].startswith("8 chips share each layer")
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    for key in ("router_input", "window", "rope", "no_aux_loss", "optimizer",
                "weights", "activation"):
        assert key in cfg["assumed"], key
    assert cfg["train"]["optimizer"]["learning_rate"] == 1e-7
    assert cfg["train"]["control_precision"] == "fp8"
    entry = [c for c in BENCH["configs"]
             if c["name"] == "smallthinker-21b-a3b"][0]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == \
        cfg["source"]
    assert entry["file"] == "benchmarks/configs/smallthinker-21b-a3b.json"


def test_cell_is_listed_where_its_metrics_are_read():
    cell = [w for w in BENCH["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("smallthinker-21b-a3b", "train-lm-1x16384", 1)
    assert len(cell["why"]) <= 200
    tr_ = _traffic()
    assert (tr_["kind"], tr_["rate_metric"], tr_["seq"],
            tr_["batch_per_chip"], tr_["pool"], tr_["feed_depth"],
            tr_["span_steps"], tr_["trace_seconds"]) == \
        ("train", "train_tokens_per_s", 16384, 1, 8, 2, 1, 4.0)
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in BENCH[g] if CELL in m.get("workloads", ())}
    for name in ("train_tokens_per_s", "step_ms_p90", "mfu.tokens",
                 "attn_fwd_ms.tokens", "attn_bwd_ms.tokens",
                 "moe_gmm_ms.tokens", "moe_gmm_roofline.tokens",
                 "win_attn_fwd_roofline.tokens",
                 "win_attn_bwd_roofline.tokens",
                 "moe_rows_per_expert.tokens",
                 "moe_load_max_over_mean.tokens", "retraces.tokens",
                 "wgrad_update_ms.tokens", "device_idle.tokens",
                 "setup_compile_s", "setup_programs"):
        assert name in listed, name
    for other in ("flash_fwd_roofline.tokens", "bd_attn_fwd_roofline.tokens",
                  "bd_attn_bwd_roofline.tokens"):
        assert other not in listed
    for m in BENCH["per_layer"]:        # every listed metric has its file
        if CELL in m.get("workloads", ()):
            spec = run.load_json("metrics", m["name"] + ".json")
            assert os.path.exists(os.path.join(
                run.HERE, "metrics", "readers", spec["reader"] + ".py"))
            if spec["reader"] == "kernel_roofline_named":
                assert hasattr(flops, spec["args"]["kernel"]), m["name"]
    # the accepted cells' lists end as they did, the new cell appended
    for g in ("end_to_end", "per_layer"):
        for m in BENCH[g]:
            cells = m.get("workloads", [])
            if CELL in cells:
                assert cells[-1] == CELL and cells.count(CELL) == 1


def test_batches_are_ids_of_the_slice_and_shifted_by_the_loss():
    model = run.load_module("models", "smallthinker-21b-a3b.py")
    cfg, tr_ = dict(_cfg(), vocab_size=512), dict(_traffic(), seq=256)
    x, y = model.make_batch(cfg, tr_, 3, onp.random.default_rng(5))
    assert x.shape == (3, 256) and x.dtype == onp.int32 and (x == y).all()
    assert x.min() >= 0 and x.max() < 512 and len(onp.unique(x)) > 256
    assert model.items_per_batch(cfg, tr_, 3) == 3 * 256
    assert model.example_input(cfg, tr_).shape == (1, 256)
    w = onp.ones((4, 6))
    assert model.to_program("l0.attn.qkv.w", w).shape == (6, 4)
    assert model.to_program("head.w", w).shape == (6, 4)
    assert model.to_program("l0.moe.router.w", w).shape == (4, 6)
    assert model.to_program("embed.w", w).shape == (4, 6)


# ---------------------------------------------------------------------------
# operations and bytes against brute force

@pytest.mark.parametrize("seq,window", [(64, 16), (96, 96), (128, 1),
                                        (64, None), (48, 200)])
def test_live_pairs_are_the_masks_count(seq, window):
    ref = run.load_module("reference", "smallthinker-21b-a3b.py")
    mask = onp.asarray(ref.live_mask(seq, window))
    assert mask.shape == (seq, seq)
    assert flops.live_pairs(seq, window) == int(mask.sum())
    by_hand = sum(1 for i in range(seq) for j in range(seq)
                  if j <= i and (window is None or i - j < window))
    assert by_hand == int(mask.sum())


def test_the_cells_live_pairs_and_operations():
    cfg, tr_ = _cfg(), _traffic()
    assert flops.layer_pairs(cfg, 16384) == [134225920] + [58722304] * 3
    e, d, hq, hkv, f, S = 2560, 128, 28, 4, 768, 16384
    position = 2 * e * (hq + 2 * hkv) * d + 2 * hq * d * e + 2 * e * 64 \
        + 6 * 8 / 64 * 3 * 2 * e * f
    attention = (134225920 + 3 * 58722304) / S * hq * 2 * 2 * d
    forward = 4 * position + attention + 2 * e * 18992
    assert flops.held_per_position(cfg) == 0.75
    assert flops.forward_flops_per_item(cfg, S) == pytest.approx(forward)
    assert flops.train_flops_per_item(cfg, tr_) == pytest.approx(3 * forward)
    # ISSUE 34's reckoning: 28.2 TFLOP a step; attention 47% of forward
    assert S * 3 * forward == pytest.approx(28.2e12, rel=0.01)
    assert attention / forward == pytest.approx(0.47, abs=0.01)


def test_attention_counts_over_the_masks_by_brute_force():
    ref = run.load_module("reference", "smallthinker-21b-a3b.py")
    cfg = dict(_cfg(), sliding_window_size=32)
    tr_ = dict(_traffic(), seq=128, batch_per_chip=2)
    live = int(onp.asarray(ref.live_mask(128)).sum()) \
        + 3 * int(onp.asarray(ref.live_mask(128, 32)).sum())
    hq, hkv, d, batch, layers = 28, 4, 128, 2, 4
    f_fwd, b_fwd = flops.win_attn_fwd(cfg, tr_)
    f_bwd, b_bwd = flops.win_attn_bwd(cfg, tr_)
    assert f_fwd == batch * hq * live * 2 * (2 * d)     # QK^T and PV
    assert f_bwd == batch * hq * live * 5 * (2 * d)     # five products
    q_arr, kv_arr = 128 * hq * d * 2, 128 * hkv * d * 2     # bf16
    calls = layers * batch
    assert b_fwd == calls * (2 * q_arr + 2 * kv_arr)    # q o | k v once
    assert b_bwd == calls * (3 * q_arr + 4 * kv_arr)    # q do dq | k v dk dv
    assert flops.flash_fwd_shape(cfg, tr_) == (2 * 28, 128, 128)


def test_grouped_products_counted_over_a_routed_batch():
    """A batch routed by a random router: the assignments that land on
    the 8 held of 64 experts, each through three products of gate+up and
    three of down, are what ``moe_gmm`` counts to the routing's own
    scatter (the count is of the expected rows)."""
    cfg, tr_ = dict(_cfg(), num_hidden_layers=1), dict(_traffic(), seq=4096)
    positions = tr_["seq"] * tr_["batch_per_chip"]
    rs = onp.random.default_rng(3)
    logits = rs.standard_normal((positions, cfg["router_experts"]))
    top = onp.argsort(-logits, -1)[:, :cfg["moe_num_active_primary_experts"]]
    rows = int((top < cfg["moe_num_primary_experts"]).sum())
    e, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    by_hand = rows * 3 * (2 * e * 2 * f + 2 * f * e)
    got, nbytes = flops.moe_gmm(cfg, tr_)
    assert got == pytest.approx(by_hand, rel=0.05)
    assert got == 0.75 * positions * 3 * (2 * e * 2 * f + 2 * f * e)
    weights = 8 * (e * 2 * f + f * e) * 2
    assert nbytes == pytest.approx(
        3 * (weights + 0.75 * positions * (e + 2 * f + f + e) * 2),
        rel=1e-12)
    # the cell: 1,536 rows an expert a step
    full = dict(_traffic())
    assert full["seq"] * flops.held_per_position(_cfg()) / 8 == 1536


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
    assert over, res["compared"]
    if fault == "state_unchanged":
        assert over >= {"delta_gap", "delta_med_gap"} & set(res["compared"])
        assert {"delta_gap", "delta_med_gap"} & set(res["compared"])


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
