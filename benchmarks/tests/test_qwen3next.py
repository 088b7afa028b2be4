"""Configuration ``qwen3-next-80b-a3b`` and its cell on the CPU: the
files as ISSUE 36 and the catalog state them, the operation counts of
``flops/qwen3-next-80b-a3b.py`` against brute force (a count of the
reference's own recurrence, the causal mask, a routed batch), and the
rehearsal through the unedited harness (a sound float32 run is
``correct``, the fp8 control and each planted fault are not)."""
import argparse
import json
import os
import re

import numpy as onp
import pytest

import correct
import run

CELL = "qwen3next80b-train-s8192"
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
flops = run.load_module("flops", "qwen3-next-80b-a3b.py")


def _cfg():
    return run.load_json("configs", "qwen3-next-80b-a3b.json")


def _traffic():
    return run.load_json("traffic", "train-lm-1x8192.json")


# ---------------------------------------------------------------------------
# the files as ISSUE 36 and the catalog state them

def test_configuration_keeps_every_published_width():
    cfg = _cfg()
    published = dict(
        decoder_sparse_step=1, full_attention_interval=4, head_dim=256,
        hidden_act="silu", hidden_size=2048, intermediate_size=5120,
        linear_conv_kernel_dim=4, linear_key_head_dim=128,
        linear_num_key_heads=16, linear_num_value_heads=32,
        linear_value_head_dim=128, max_position_embeddings=262144,
        mlp_only_layers=[], model_type="qwen3_next",
        moe_intermediate_size=512, norm_topk_prob=True,
        num_attention_heads=16, num_experts_per_tok=10,
        num_key_value_heads=2, partial_rotary_factor=0.25,
        rms_norm_eps=1e-06, rope_scaling=None, rope_theta=10000000,
        shared_expert_intermediate_size=512, tie_word_embeddings=False,
        use_sliding_window=False)
    assert {k: cfg[k] for k in published} == published
    assert cfg["published"] == dict(num_hidden_layers=48, num_experts=512,
                                    vocab_size=151936)
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert cfg["num_hidden_layers"] == 4 and cfg["vocab_size"] == 18992
    assert cfg["num_experts"] in (16, 8)        # 8 by the memory rule
    assert cfg["router_experts"] == 512 and cfg["experts_first"] == 0
    assert cfg["published"]["vocab_size"] == 8 * cfg["vocab_size"]
    chips = cfg["router_experts"] // cfg["num_experts"]
    assert cfg["deployment"].startswith(f"{chips} chips share each layer")
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    for key in ("norm_gammas", "fused_column_order", "linear_init", "no_mtp",
                "no_aux_loss", "no_bias", "optimizer", "weights", "rope"):
        assert key in cfg["assumed"], key
    assert cfg["train"]["optimizer"]["learning_rate"] == 1e-7
    assert cfg["train"]["control_precision"] == "fp8"
    entry = [c for c in BENCH["configs"]
             if c["name"] == "qwen3-next-80b-a3b"][0]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == \
        cfg["source"]
    assert entry["file"] == "benchmarks/configs/qwen3-next-80b-a3b.json"
    assert len(entry["why"]) <= 200


def test_cell_is_listed_where_its_metrics_are_read():
    cell = [w for w in BENCH["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("qwen3-next-80b-a3b", "train-lm-1x8192", 1)
    assert len(cell["why"]) <= 200
    tr_ = _traffic()
    assert (tr_["kind"], tr_["rate_metric"], tr_["seq"],
            tr_["batch_per_chip"], tr_["pool"], tr_["feed_depth"],
            tr_["trace_seconds"]) == \
        ("train", "train_tokens_per_s", 8192, 1, 8, 2, 4.0)
    assert tr_["span_steps"] in (2, 3)      # a reading of 250 ms or more
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in BENCH[g] if CELL in m.get("workloads", ())}
    for name in ("train_tokens_per_s", "step_ms_p90", "mfu.tokens",
                 "attn_fwd_ms.tokens", "attn_bwd_ms.tokens",
                 "moe_gmm_ms.tokens", "moe_gmm_roofline.tokens",
                 "gdn_ms.tokens", "gdn_fwd_roofline.tokens",
                 "gdn_bwd_roofline.tokens", "full_attn_fwd_roofline.tokens",
                 "full_attn_bwd_roofline.tokens",
                 "moe_rows_per_expert.tokens",
                 "moe_load_max_over_mean.tokens", "retraces.tokens",
                 "wgrad_update_ms.tokens", "device_idle.tokens",
                 "setup_compile_s", "setup_programs"):
        assert name in listed, name
    for other in ("flash_fwd_roofline.tokens", "bd_attn_fwd_roofline.tokens",
                  "bd_attn_bwd_roofline.tokens",
                  "win_attn_fwd_roofline.tokens",
                  "win_attn_bwd_roofline.tokens"):
        assert other not in listed
    for m in BENCH["per_layer"]:        # every listed metric has its file
        if CELL in m.get("workloads", ()):
            spec = run.load_json("metrics", m["name"] + ".json")
            assert os.path.exists(os.path.join(
                run.HERE, "metrics", "readers", spec["reader"] + ".py"))
            if spec["reader"] == "kernel_roofline_named":
                assert hasattr(flops, spec["args"]["kernel"]), m["name"]
    # the three cells accepted before it keep their order and this cell
    # follows them (whatever a later PR appends after it: the SmallThinker
    # cell's test pinned itself LAST, which the next cell had to break)
    before = ["opt1.3b-train-s2048", "sdar30b-train-bd-s4096",
              "smallthinker21b-train-s16384"]
    for g in ("end_to_end", "per_layer"):
        for m in BENCH[g]:
            cells = m.get("workloads", [])
            if CELL in cells:
                assert cells.count(CELL) == 1
                earlier = [c for c in cells[:cells.index(CELL)]]
                assert earlier == [c for c in before if c in cells]
    names = [m["name"] for m in BENCH["per_layer"]]
    mine = ["gdn_ms.tokens", "gdn_fwd_roofline.tokens",
            "gdn_bwd_roofline.tokens", "full_attn_fwd_roofline.tokens",
            "full_attn_bwd_roofline.tokens"]
    at = names.index(mine[0])
    assert names[at:at + 5] == mine and at > names.index(
        "win_attn_bwd_roofline.tokens")
    for m in BENCH["per_layer"][at:at + 5]:
        assert m["workloads"][0] == CELL
        assert m["layer"] == ("kernels (kernels/gated_delta.py)"
                              if m["name"].startswith("gdn_")
                              else "kernels (kernels/flash_attention.py)")
        assert m["moves"] == "train_tokens_per_s"
    # each pass's roofline reads the preparation with the walk, and the
    # backward's second run of the preparation by a name of its own
    fwd = run.load_json("metrics", "gdn_fwd_roofline.tokens.json")
    bwd = run.load_json("metrics", "gdn_bwd_roofline.tokens.json")
    ops = {"gdn_prep_fwd": fwd, "gdn_fwd": fwd, "gdn_prep_refwd": bwd,
           "gdn_bwd": bwd, "gdn_prep_bwd": bwd}
    for op, spec in ops.items():
        for line in (f"%{op} = (bf16[1,32,8192,128]", f"%{op}.11 = (bf16["):
            hit = [s_ is spec for s_ in (fwd, bwd)
                   if re.search(s_["args"]["match"], line)]
            assert hit == [True], (op, line)


def test_batches_are_ids_of_the_slice_and_shifted_by_the_loss():
    model = run.load_module("models", "qwen3-next-80b-a3b.py")
    cfg, tr_ = dict(_cfg(), vocab_size=512), dict(_traffic(), seq=256)
    x, y = model.make_batch(cfg, tr_, 3, onp.random.default_rng(5))
    assert x.shape == (3, 256) and x.dtype == onp.int32 and (x == y).all()
    assert x.min() >= 0 and x.max() < 512 and len(onp.unique(x)) > 256
    assert model.items_per_batch(cfg, tr_, 3) == 3 * 256
    assert model.example_input(cfg, tr_).shape == (1, 256)
    w = onp.ones((4, 6))
    for leaf in ("l0.attn.qkvz.w", "l0.attn.ba.w", "l0.attn.conv.w",
                 "l3.attn.qkv.w", "l0.attn.out.w", "head.w"):
        assert model.to_program(leaf, w).shape == (6, 4), leaf
    for leaf in ("l0.moe.router.w", "embed.w", "l0.moe.shared.w13",
                 "l0.moe.shared.gate.w"):
        assert model.to_program(leaf, w).shape == (4, 6), leaf


# ---------------------------------------------------------------------------
# operations and bytes against brute force

def test_the_cells_operations():
    cfg, tr_ = _cfg(), _traffic()
    e, S, held = 2048, 8192, cfg["num_experts"]
    linear = 2 * e * 12288 + 2 * e * 64 + 2 * 4096 * e + 2 * 4 * 8192 \
        + 32 * 7 * 128 * 128
    full = 2 * e * 9216 + 2 * 4096 * e + (S + 1) / 2 * 16 * 2 * 2 * 256
    experts = 2 * e * 512 + 3 * 2 * e * 512 + 2 * e \
        + 10 * held / 512 * 3 * 2 * e * 512
    forward = 3 * linear + full + 4 * experts + 2 * e * 18992
    assert flops.layer_kinds(cfg) == (3, 1)
    assert flops.held_per_position(cfg) == 10 * held / 512
    assert flops.forward_flops_per_item(cfg, S) == pytest.approx(forward)
    assert flops.train_flops_per_item(cfg, tr_) == pytest.approx(3 * forward)
    if held == 16:
        # ISSUE 36's reckoning: 3.7 TFLOP forward, 11.2 a step; the
        # linear layers 47% of forward operations
        assert S * forward == pytest.approx(3.7e12, rel=0.02)
        assert S * 3 * forward == pytest.approx(11.2e12, rel=0.02)
        assert 3 * linear / forward == pytest.approx(0.47, abs=0.015)
        assert S * flops.held_per_position(cfg) / held == 160
    assert flops.flash_fwd_shape(cfg, tr_) == (16, 8192, 256)


def test_the_rules_work_counted_on_the_references_own_recurrence():
    """A brute-force count: the multiplies and adds of one position and
    value head of the recurrence as the reference writes it (the decay,
    M^T k, the rank-one update, M^T q) are 7 dk dv to the lower order
    terms, and the full layer's live pairs are the causal mask's."""
    dk, dv = 128, 128
    decay = dk * dv
    mtk = dk * dv + (dk - 1) * dv            # multiplies and adds
    update = dk * dv + dk * dv               # k d^T, M +
    mtq = dk * dv + (dk - 1) * dv
    assert decay + mtk + update + mtq == pytest.approx(7 * dk * dv,
                                                       rel=0.01)
    cfg = _cfg()
    assert flops.rule_flops_per_position(cfg) == 32 * 7 * dk * dv
    seq = 96
    live = sum(1 for i in range(seq) for j in range(seq) if j <= i)
    # an item's share of the scores is (seq + 1) / 2 pairs a head: none
    # at "seq" -1, so the difference is the full layer's two products
    scores = flops.forward_flops_per_item(cfg, seq) \
        - flops.forward_flops_per_item(cfg, -1)
    assert scores * seq == pytest.approx(live * 16 * 2 * 2 * 256)


def test_rule_kernels_flops_and_bytes():
    cfg = _cfg()
    tr_ = dict(_traffic(), seq=512, batch_per_chip=2)
    positions, layers = 1024, 3
    f_fwd, b_fwd = flops.gdn_fwd(cfg, tr_)
    f_bwd, b_bwd = flops.gdn_bwd(cfg, tr_)
    assert f_fwd == layers * positions * 32 * 7 * 128 * 128
    assert f_bwd == 3 * f_fwd       # twice the forward, and it again
    # bfloat16 q, k a key head, v a value head, g and beta float32
    qkvgb = 2 * (2 * 16 * 128 + 32 * 128) + 2 * 4 * 32
    o = 2 * 32 * 128
    assert b_fwd == layers * positions * (qkvgb + o)
    assert b_bwd == layers * positions * (2 * qkvgb + o)   # do in, 5 out
    # the roof is bytes: about a quarter of a millisecond a layer forward
    full = _traffic()
    flops_, bytes_ = flops.gdn_fwd(cfg, full)
    assert bytes_ / 819e9 > flops_ / 197e12
    assert bytes_ / 819e9 / 3 == pytest.approx(0.25e-3, rel=0.05)


def test_full_layers_flash_calls_counted_over_the_causal_mask():
    """The D=256 flash calls' work: the causal mask's live pairs counted
    one by one, two products forward and five backward; q-sized arrays
    a query head, k- and v-sized a key/value head, bfloat16."""
    cfg = dict(_cfg(), num_hidden_layers=8)         # two full layers
    tr_ = dict(_traffic(), seq=96, batch_per_chip=2)
    live = sum(1 for i in range(96) for j in range(96) if j <= i)
    calls = 2 * 2
    f_fwd, b_fwd = flops.full_attn_fwd(cfg, tr_)
    f_bwd, b_bwd = flops.full_attn_bwd(cfg, tr_)
    assert f_fwd == calls * 16 * live * 2 * 2 * 256
    assert f_bwd == calls * 16 * live * 5 * 2 * 256
    q, kv = 96 * 16 * 256 * 2, 96 * 2 * 256 * 2
    assert b_fwd == calls * (2 * q + 2 * kv)        # q, o; k, v
    assert b_bwd == calls * (3 * q + 4 * kv)        # q, do, dq; k, v, dk, dv
    # the cell: 0.55 TFLOP forward (ISSUE 36), a roof of operations
    flops_, bytes_ = flops.full_attn_fwd(_cfg(), _traffic())
    assert flops_ == pytest.approx(0.55e12, rel=0.01)
    assert flops_ / 197e12 > bytes_ / 819e9


def test_grouped_products_counted_over_a_routed_batch():
    """A batch routed by a random router: the assignments that land on
    the held of 512 experts, each through three products of gate+up and
    three of down, are what ``moe_gmm`` counts to the routing's own
    scatter (the count is of the expected rows)."""
    cfg, tr_ = dict(_cfg(), num_hidden_layers=1), _traffic()
    positions = tr_["seq"] * tr_["batch_per_chip"]
    held = cfg["num_experts"]
    rs = onp.random.default_rng(3)
    logits = rs.standard_normal((positions, cfg["router_experts"]))
    top = onp.argsort(-logits, -1)[:, :cfg["num_experts_per_tok"]]
    rows = int((top < held).sum())
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    by_hand = rows * 3 * (2 * e * 2 * f + 2 * f * e)
    got, nbytes = flops.moe_gmm(cfg, tr_)
    assert got == pytest.approx(by_hand, rel=0.05)
    expected = positions * 10 * held / 512
    assert got == expected * 3 * (2 * e * 2 * f + 2 * f * e)
    weights = held * (e * 2 * f + f * e) * 2
    assert nbytes == pytest.approx(
        3 * (weights + expected * (e + 2 * f + f + e) * 2), rel=1e-12)


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
    over = {k for k, v in res["compared"].items()
            if v["value"] is None or v["value"] > v["limit"]}
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
