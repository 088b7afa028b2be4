"""Configuration ``lfm2-24b-a2b`` and its cell on the CPU: the files as
the catalog states them, the operation counts of
``flops/lfm2-24b-a2b.py`` against hand counts, the limits file, and the
rehearsal through the unedited harness (a sound float32 run is
``correct``, the fp8 control and a state left unchanged are not)."""
import argparse
import json
import os

import numpy as onp
import pytest

import correct
import run

CELL = "lfm2moe24b-train-s8192"
NAME = "lfm2-24b-a2b"
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
flops = run.load_module("flops", NAME + ".py")

#: the catalog row's ``config``, every number and group as published
CATALOG = dict(
    conv_L_cache=3, conv_bias=False, hidden_size=2048,
    intermediate_size=11776, max_position_embeddings=128000,
    model_type="lfm2_moe", moe_intermediate_size=1536, norm_eps=1e-05,
    norm_topk_prob=True, num_attention_heads=32, num_dense_layers=2,
    num_experts_per_tok=4, num_key_value_heads=8,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    routed_scaling_factor=1, use_expert_bias=True)
LAYER_TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv",
                                  "conv"] * 9 + ["full_attention", "conv"]


def _cfg(rehearse=False):
    cfg = run.load_json("configs", NAME + ".json")
    if rehearse:
        cfg.update(cfg["rehearse"])
    return cfg


def _traffic():
    return run.load_json("traffic", "train-lm-1x8192.json")


# ---------------------------------------------------------------------------
# the files

def test_configuration_keeps_every_published_width():
    cfg = _cfg()
    assert {k: cfg[k] for k in CATALOG} == CATALOG
    assert cfg["layer_types"] == LAYER_TYPES and len(LAYER_TYPES) == 40
    assert cfg["source"] == \
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    assert cfg["published"] == dict(num_hidden_layers=40, num_experts=64,
                                    vocab_size=65536)
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (6, 8, 8192)
    assert cfg["router_experts"] == cfg["published"]["num_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["head_dim"] * cfg["num_attention_heads"] == \
        cfg["hidden_size"]
    for key in ("score", "expert_bias_rate", "tie_word_embeddings",
                "intermediate_size", "norm_eps", "optimizer"):
        assert key in cfg["assumed"], key
    assert cfg["expert_bias_rate"] == 1e-3 and cfg["tie_word_embeddings"]
    assert cfg["train"]["control_precision"] == "fp8"
    entry = [c for c in BENCH["configs"] if c["name"] == NAME][0]
    assert entry["file"] == "benchmarks/configs/" + NAME + ".json"
    assert entry["reduced"] == cfg["reduced"]


def test_parameters_and_resident_bytes_as_reckoned():
    """558.4 M parameters, 9.16 GB at 16.4 B a parameter; the layers
    built are two dense conv layers and one period of expert layers."""
    ref = run.load_module("reference", NAME + ".py")
    cfg = _cfg()
    leaves = ref.leaf_shapes(cfg)
    n = sum(int(onp.prod(shape)) for shape, kind in leaves.values()
            if kind != "state")
    assert n / 1e6 == pytest.approx(558.4, abs=0.1)
    assert n * 16.4 / 1e9 == pytest.approx(9.16, abs=0.01)
    assert flops.layer_kinds(cfg) == (5, 1, 2, 4)
    assert [k for k in cfg["layer_types"][:6]] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv"]


def test_cell_is_listed_where_its_metrics_are_read():
    cell = [w for w in BENCH["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "train-lm-1x8192", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in BENCH[g] if CELL in m.get("workloads", ())}
    for name in ("train_tokens_per_s", "step_ms_p90", "mfu.tokens",
                 "attn_fwd_ms.tokens", "attn_bwd_ms.tokens",
                 "mixer_glue_ms.tokens", "attn_bwd_xla_ms.tokens",
                 "head_loss_ms.tokens", "wgrad_update_ms.tokens",
                 "device_idle.tokens", "retraces.tokens",
                 "step_scope_attributed.tokens", "setup_compile_s",
                 "setup_init_forward_s", "moe_gmm_ms.tokens",
                 "moe_gmm_roofline.tokens", "moe_glue_ms.tokens",
                 "full_attn_fwd_roofline.tokens",
                 "full_attn_bwd_roofline.tokens", "short_conv_ms.tokens",
                 "short_conv_fwd_roofline.tokens",
                 "short_conv_bwd_roofline.tokens"):
        assert name in listed, name
    # the q/k prologue's kernels take heads of 128 lanes: at 64 the twin;
    # the rows and load readers average over every layer, and two of the
    # six hold no experts
    for other in ("flash_fwd_roofline.tokens", "gdn_ms.tokens",
                  "qk_prologue_ms.tokens", "delta_prologue_ms.tokens",
                  "ssm_scan_ms.tokens", "win_attn_fwd_roofline.tokens",
                  "moe_rows_per_expert.tokens",
                  "moe_load_max_over_mean.tokens"):
        assert other not in listed, other
    for m in BENCH["per_layer"]:        # every listed metric has its file
        if CELL in m.get("workloads", ()):
            spec = run.load_json("metrics", m["name"] + ".json")
            assert os.path.exists(os.path.join(
                run.HERE, "metrics", "readers", spec["reader"] + ".py"))
            if spec["reader"] == "kernel_roofline_named":
                assert hasattr(flops, spec["args"]["kernel"]), m["name"]
            if spec["args"].get("while_carrying"):
                assert hasattr(flops, spec["args"]["while_carrying"])
    # the cells accepted before it keep their order and this cell follows
    # them (whatever a later PR appends after it)
    before = ["opt1.3b-train-s2048", "sdar30b-train-bd-s4096",
              "smallthinker21b-train-s16384", "qwen3next80b-train-s8192",
              "phi4flash3.8b-train-s8192"]
    for g in ("end_to_end", "per_layer"):
        for m in BENCH[g]:
            cells = m.get("workloads", [])
            if CELL in cells:
                assert cells.count(CELL) == 1
                assert cells[:cells.index(CELL)] == \
                    [c for c in before if c in cells]
    names = [m["name"] for m in BENCH["per_layer"]]
    mine = ["short_conv_ms.tokens", "short_conv_fwd_roofline.tokens",
            "short_conv_bwd_roofline.tokens"]
    at = names.index(mine[0])
    assert names[at:at + 3] == mine and at > names.index(
        "delta_prologue_ms.tokens")
    for m in BENCH["per_layer"][at:at + 3]:
        assert m["workloads"] == [CELL]
        assert m["layer"] == "kernels (kernels/short_conv.py)"
        assert m["moves"] == "train_tokens_per_s"


def test_short_conv_metrics_find_the_kernels_by_name():
    import re

    spans = run.load_json("metrics", "short_conv_ms.tokens.json")
    fwd = run.load_json("metrics", "short_conv_fwd_roofline.tokens.json")
    bwd = run.load_json("metrics", "short_conv_bwd_roofline.tokens.json")
    for op, spec in (("short_conv_fwd", fwd), ("short_conv_bwd", bwd)):
        for line in (f"%{op} = (bf16[1,8192,2048]", f"%{op}.3 = (bf16["):
            hit = [s is spec for s in (fwd, bwd)
                   if re.search(s["args"]["match"], line)]
            assert hit == [True], (op, line)
            assert re.search(spans["args"]["match"], line)
    assert not re.search(spans["args"]["match"], "%flash_fwd = (bf16[")


def test_batches_are_ids_of_the_slice_and_the_program_layout():
    model = run.load_module("models", NAME + ".py")
    cfg, tr_ = dict(_cfg(), vocab_size=512), dict(_traffic(), seq=256)
    x, y = model.make_batch(cfg, tr_, 3, onp.random.default_rng(5))
    assert x.shape == (3, 256) and x.dtype == onp.int32 and (x == y).all()
    assert x.min() >= 0 and x.max() < 512 and len(onp.unique(x)) > 256
    assert model.items_per_batch(cfg, tr_, 3) == 3 * 256
    assert model.example_input(cfg, tr_).shape == (1, 256)
    w = onp.ones((4, 6))
    for leaf in ("l0.attn.in.w", "l0.attn.conv.w", "l0.attn.out.w",
                 "l2.attn.qkv.w", "l0.mlp.w13", "l1.mlp.w2"):
        assert model.to_program(leaf, w).shape == (6, 4), leaf
    for leaf in ("embed.w", "l3.moe.router.w"):
        assert model.to_program(leaf, w).shape == (4, 6), leaf
    assert model.to_program("l3.moe.w13", onp.ones((2, 4, 6))).shape == \
        (2, 4, 6)


# ---------------------------------------------------------------------------
# operations and bytes against hand counts

def test_the_cells_operations():
    """A hand sum at the cell's sizes, and the reckoning its ``why``
    states: 292.1 M multiply-accumulates a token forward (conv mixers
    29%, dense MLPs 50%, attention 9%, held experts 6.5%, head 6%), 14.4
    TFLOP a training step."""
    cfg, tr_ = _cfg(), _traffic()
    e, s, v = 2048, 8192, 8192
    conv = 2 * e * 3 * e + 2 * e * e + 8 * e
    attention = 2 * e * 48 * 64 + 2 * 32 * 64 * e + (s + 1) / 2 * 32 * 4 * 64
    dense = 6 * e * 11776
    experts = 2 * e * 64 + 4 * 8 / 64 * 6 * e * 1536
    forward = 5 * conv + attention + 2 * dense + 4 * experts + 2 * e * v
    assert flops.forward_flops_per_item(cfg, s) == pytest.approx(forward)
    assert flops.train_flops_per_item(cfg, tr_) == pytest.approx(3 * forward)
    assert forward / 2 / 1e6 == pytest.approx(292.1, abs=0.1)
    assert 3 * s * forward / 1e12 == pytest.approx(14.36, abs=0.01)
    for part, share in ((5 * conv, 0.29), (2 * dense, 0.50),
                        (attention, 0.09), (4 * 4 * 8 / 64 * 6 * e * 1536,
                                            0.065), (2 * e * v, 0.06)):
        assert part / forward == pytest.approx(share, abs=0.006)
    assert flops.flash_fwd_shape(cfg, tr_) == (32, 8192, 64)


def test_short_conv_work_counted():
    """``short_conv_fwd`` / ``short_conv_bwd``: the five conv layers'
    bytes, B|C|x in and y out forward (134 MB a layer), dy and B|C|x in
    and d(B|C|x) out backward (235 MB), bfloat16; the roof is the bytes,
    0.16 and 0.29 ms a layer at 819 GB/s."""
    cfg, tr_ = _cfg(), _traffic()
    f_fwd, b_fwd = flops.short_conv_fwd(cfg, tr_)
    f_bwd, b_bwd = flops.short_conv_bwd(cfg, tr_)
    assert b_fwd == 5 * 8192 * 4 * 2048 * 2
    assert b_bwd == 5 * 8192 * 7 * 2048 * 2
    assert b_fwd / 5 / 1e6 == pytest.approx(134.2, abs=0.1)
    assert b_bwd / 5 / 1e6 == pytest.approx(234.9, abs=0.1)
    assert f_fwd == 5 * 8192 * 2048 * 8 and f_bwd == 3 * f_fwd
    assert b_fwd / 819e9 > f_fwd / 197e12
    assert b_fwd / 819e9 / 5 == pytest.approx(0.164e-3, rel=0.01)
    assert b_bwd / 819e9 / 5 == pytest.approx(0.287e-3, rel=0.01)


def test_attention_and_experts_work():
    """The one attention layer's flash calls over the causal live pairs at
    (1, 32 over 8, 8192, 64), and the grouped products of the four
    expert layers at 512 expected rows an expert."""
    cfg, tr_ = _cfg(), _traffic()
    pairs = 8192 * 8193 // 2
    assert flops.full_attn_fwd(cfg, tr_)[0] == 32 * pairs * 2 * 2 * 64
    assert flops.full_attn_bwd(cfg, tr_)[0] == 32 * pairs * 5 * 2 * 64
    rows = 8192 * 4 * 8 / 64
    assert rows / 8 == 512
    f, _ = flops.moe_gmm(cfg, tr_)
    assert f == 4 * 3 * 2 * rows * (2048 * 3072 + 1536 * 2048)


# ---------------------------------------------------------------------------
# the limits and the rehearsal: correct has to be able to fail

def test_the_limits_file_names_every_compared_number():
    """The first gradient's three numbers, each between its sound reading
    (12 seeds or more on the chip) and the fp8 control's (six or more);
    the two change numbers and the routing state's, between the sound
    reading and what a state left unchanged reads; the control fails at
    least one limit on every seed."""
    lim = run.load_json("limits", CELL + ".json")
    assert lim["cell"] == CELL
    assert set(lim["limits"]) == {"grad_diff_least", "grad_diff_med",
                                  "grad_med_gap", "delta_gap",
                                  "delta_med_gap", "stat_gap"}
    for name, limit in lim["limits"].items():
        r = lim["readings"][name]
        assert r["lower"] < limit < r["upper"], name
        assert r["seeds"] >= 12, name
        if name.startswith("grad"):
            assert min(r["control_fp8"]) == r["upper"], name
            assert len(r["control_fp8"]) >= 6, name
    n = len(lim["readings"]["grad_diff_med"]["control_fp8"])
    for i in range(n):
        assert any(r["control_fp8"][i] > lim["limits"][k]
                   for k, r in lim["readings"].items()), i


def _float32(ctx):
    ctx.cfg["train"]["compute_dtype"] = "float32"


def _run(fault=None, seed=4_200_000_007):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.5, trace=0,
                              rehearse=True)
    return run.execute(args, fault=fault, tweak=_float32)


def test_sound_float32_run_is_correct():
    res = _run()
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["compared"]


def test_state_left_unchanged_is_not_correct():
    res = _run("state_unchanged")
    assert res["correct"] is False, res["compared"]
    over = {k for k, v in res["compared"].items()
            if v["value"] is None or v["value"] > v["limit"]}
    assert over >= {"delta_gap", "delta_med_gap"} & set(res["compared"])
    assert {"delta_gap", "delta_med_gap"} & set(res["compared"])


def test_routing_state_left_unchanged_is_not_correct(monkeypatch):
    """A compiled step that never moves the selection bias (its rule
    planted as the identity) reads about 1 on ``stat_gap``: the bias's
    leaves, in steps of the rate, are no smaller than the median state
    leaf. Nothing else tells it from the sound run."""
    from mxnet_tpu.parallel import moe

    monkeypatch.setattr(moe, "update_expert_bias", lambda steps, load: steps)
    res = _run()
    assert res["correct"] is False, res["compared"]
    over = {k for k, v in res["compared"].items() if v["value"] > v["limit"]}
    assert over == {"stat_gap"}, res["compared"]
    assert res["compared"]["stat_gap"]["value"] > 0.5


def test_fp8_control_is_not_correct():
    args = argparse.Namespace(workload=CELL, seed=4_200_000_011, seconds=0.5,
                              trace=0, rehearse=True)
    ctx = run.Ctx(BENCH, run.find_cell(BENCH, CELL), args)
    _float32(ctx)
    run.look_for_chip(ctx)
    loop = run.load_module("loops", "train.py").Loop(ctx)
    loop.setup()
    loop.release()
    sound = loop.verify()
    assert sound and all(v["value"] <= v["limit"] for v in sound.values())
    control = correct.with_limits(loop.control(), ctx.limits)
    assert any(v["value"] > v["limit"] for v in control.values()), control
