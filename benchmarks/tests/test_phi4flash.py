"""Configuration ``phi4-mini-flash-3.8b`` and its cell on the CPU: the
files as the catalog states them, the operation counts of
``flops/phi4-mini-flash-3.8b.py`` against hand counts at the rehearsal's
sizes (live pairs counted one by one, the scan's work on the reference's
own recurrence), the limits file, and the rehearsal through the unedited
harness (a sound float32 run is ``correct``, the fp8 control and a state
left unchanged are not)."""
import argparse
import json
import os

import numpy as onp
import pytest

import correct
import run

CELL = "phi4flash3.8b-train-s8192"
NAME = "phi4-mini-flash-3.8b"
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
flops = run.load_module("flops", NAME + ".py")


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
    catalog = dict(
        embd_pdrop=0, hidden_act="silu", hidden_size=2560,
        intermediate_size=10240, layer_norm_eps=1e-05,
        max_position_embeddings=262144, mb_per_layer=2,
        model_type="phi4flash", num_attention_heads=40,
        num_key_value_heads=20, resid_pdrop=0, sliding_window=512,
        tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False)
    assert {k: cfg[k] for k in catalog} == catalog
    assert (cfg["head_dim"], cfg["mamba_d_state"], cfg["mamba_d_conv"],
            cfg["mamba_expand"], cfg["mamba_dt_rank"]) == (64, 16, 4, 2, 160)
    assert cfg["mamba_expand"] * cfg["hidden_size"] == 5120
    assert cfg["published"] == dict(num_hidden_layers=32, vocab_size=200064)
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (6, 25008)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["layers"] == ["mamba", "window", "mamba", "causal", "gmu",
                             "cross"]
    # the published layer rule at L = 32 gives each layer of the cut its
    # kind at the index it stands for
    L = cfg["published"]["num_hidden_layers"]

    def kind(i):
        if i < L // 2:
            return "mamba" if i % cfg["mb_per_layer"] == 0 else "window"
        if i == L // 2:
            return "mamba"
        if i == L // 2 + 1:
            return "causal"
        return "gmu" if i % 2 == 0 else "cross"

    assert [kind(i) for i in cfg["published_layer_index"]] == cfg["layers"]
    for key in ("mamba_sizes", "mamba_init", "bias", "layer_rule",
                "memory_is_gated_output", "differential_attention",
                "window", "no_positions", "cross", "norms", "loss",
                "optimizer", "weights"):
        assert key in cfg["assumed"], key
    assert cfg["train"]["optimizer"]["learning_rate"] == 1e-7
    assert cfg["train"]["control_precision"] == "fp8"
    assert cfg["deployment"].startswith("the 32 layers lie on pipeline")
    entry = [c for c in BENCH["configs"] if c["name"] == NAME][0]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    assert len(entry["why"]) <= 200


def test_parameters_and_resident_bytes_as_reckoned():
    """697.1 M parameters, 11.43 GB at 16.4 B a parameter."""
    ref = run.load_module("reference", NAME + ".py")
    count = sum(int(onp.prod(shape))
                for shape, _ in ref.leaf_shapes(_cfg()).values())
    assert count == pytest.approx(697.1e6, rel=1e-3)
    assert count * 16.4 == pytest.approx(11.43e9, rel=2e-3)


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
                 "setup_init_forward_s", "ssm_scan_ms.tokens",
                 "ssm_scan_fwd_roofline.tokens",
                 "ssm_scan_bwd_roofline.tokens",
                 "win_attn_fwd_roofline.tokens",
                 "win_attn_bwd_roofline.tokens"):
        assert name in listed, name
    for other in ("flash_fwd_roofline.tokens", "moe_gmm_ms.tokens",
                  "gdn_ms.tokens", "qk_prologue_ms.tokens",
                  "full_attn_fwd_roofline.tokens",
                  "full_attn_bwd_roofline.tokens"):
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
              "smallthinker21b-train-s16384", "qwen3next80b-train-s8192"]
    for g in ("end_to_end", "per_layer"):
        for m in BENCH[g]:
            cells = m.get("workloads", [])
            if CELL in cells:
                assert cells.count(CELL) == 1
                assert cells[:cells.index(CELL)] == \
                    [c for c in before if c in cells]
    names = [m["name"] for m in BENCH["per_layer"]]
    mine = ["ssm_scan_ms.tokens", "ssm_scan_fwd_roofline.tokens",
            "ssm_scan_bwd_roofline.tokens"]
    at = names.index(mine[0])
    assert names[at:at + 3] == mine and at > names.index(
        "qk_prologue_ms.tokens")
    for m in BENCH["per_layer"][at:at + 3]:
        assert m["workloads"] == [CELL]
        assert m["layer"] == "kernels (kernels/selective_scan.py)"
        assert m["moves"] == "train_tokens_per_s"


def test_scan_metrics_find_the_kernels_by_name():
    import re

    spans = run.load_json("metrics", "ssm_scan_ms.tokens.json")
    fwd = run.load_json("metrics", "ssm_scan_fwd_roofline.tokens.json")
    bwd = run.load_json("metrics", "ssm_scan_bwd_roofline.tokens.json")
    for op, spec in (("ssm_scan_fwd", fwd), ("ssm_scan_bwd", bwd)):
        for line in (f"%{op} = (bf16[1,8192,5120]", f"%{op}.3 = (bf16["):
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
    for leaf in ("l0.attn.in.w", "l0.attn.conv.w", "l0.attn.dt.w",
                 "l3.attn.qkv.w", "l4.attn.out.w", "l5.mlp.fc2.w"):
        assert model.to_program(leaf, w).shape == (6, 4), leaf
    for leaf in ("embed.w", "l0.attn.a_log"):
        assert model.to_program(leaf, w).shape == (4, 6), leaf
    assert model.layer_list(_cfg())[1] == {"window": 512}


# ---------------------------------------------------------------------------
# operations and bytes against hand counts

def test_the_cells_operations():
    """A hand sum at the cell's sizes, and the reckoning its ``why``
    states: 12.5 TFLOP forward a step, 37-38 a training step, the mixers
    and their maps 30% of the forward."""
    cfg, tr_ = _cfg(), _traffic()
    e, di, n, r, f, s, v = 2560, 5120, 16, 160, 10240, 8192, 25008
    mamba = 2 * e * 2 * di + 2 * 4 * di + 2 * di * (r + 2 * n) \
        + 2 * r * di + 7 * di * n + 2 * di * e
    gmu = 4 * e * di
    causal = (s + 1) / 2 * 20 * 12 * 64
    window = (512 * 513 / 2 + (s - 512) * 512) / s * 20 * 12 * 64
    selfp = 2 * e * 5120 + 2 * e * e
    crossp = 2 * e * e + 2 * e * e
    mlp = 3 * 2 * e * f
    forward = 2 * mamba + gmu + selfp + window + selfp + causal \
        + crossp + causal + 6 * mlp + 2 * e * v
    assert flops.forward_flops_per_item(cfg, s) == pytest.approx(forward)
    assert flops.train_flops_per_item(cfg, tr_) == pytest.approx(3 * forward)
    assert s * forward == pytest.approx(12.5e12, rel=0.01)
    assert 3 * s * forward / 1e12 == pytest.approx(37.5, abs=0.5)
    mixers = 2 * mamba + gmu + 2 * selfp + window + causal + crossp + causal
    assert mixers / forward == pytest.approx(0.30, abs=0.01)
    assert flops.flash_fwd_shape(cfg, tr_) == (20, 8192, 64)


def test_live_pairs_counted_one_by_one():
    """At the rehearsal's sizes: the window's and the causal mask's live
    pairs counted pair by pair, and a layer's maps 12 d a pair and
    differential head."""
    cfg = _cfg(rehearse=True)
    seq, w = 96, cfg["sliding_window"]
    live_w = sum(1 for i in range(seq) for j in range(seq)
                 if j <= i and i - j < w)
    live_c = sum(1 for i in range(seq) for j in range(seq) if j <= i)
    assert flops.live_pairs("window", seq, w) == live_w
    assert flops.live_pairs("causal", seq, w) == live_c
    assert flops.live_pairs("window", 8, w) == 8 * 9 // 2    # w >= S
    one = dict(cfg, layers=["causal"], num_hidden_layers=1)
    none = dict(one, num_attention_heads=0, num_key_value_heads=0)
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    per_item = flops.forward_flops_per_item(one, seq) \
        - flops.forward_flops_per_item(none, seq)
    e = cfg["hidden_size"]
    proj = 2 * e * (h + 2 * cfg["num_key_value_heads"]) * d + 2 * h * d * e
    assert per_item * seq == pytest.approx(
        proj * seq + live_c * h // 2 * 12 * d)


def test_attention_kernels_work_counted_call_by_call():
    """``win_attn_fwd`` / ``win_attn_bwd`` at the rehearsal's sizes: two
    calls a differential layer (window, causal, cross), each over h/2
    query heads and h_kv/2 key/value heads, q and k at d, v at 2d; the
    forward's QK^T and PV, the backward's five products; bytes q, o, k, v
    forward, q, do, dq, k, v, dk, dv backward, each once."""
    cfg = _cfg(rehearse=True)
    seq, batch = 96, 2
    tr_ = dict(_traffic(), seq=seq, batch_per_chip=batch)
    h, hkv = cfg["num_attention_heads"] // 2, cfg["num_key_value_heads"] // 2
    d, item = cfg["head_dim"], 4 if cfg["train"]["compute_dtype"] == \
        "float32" else 2
    w = cfg["sliding_window"]
    layers = [k for k in flops.kinds(cfg) if k not in ("mamba", "gmu")]
    assert layers == ["window", "causal", "cross"]
    live = [sum(1 for i in range(seq) for j in range(seq)
                if j <= i and (k != "window" or i - j < w)) for k in layers]
    calls = 2 * batch
    f_fwd, b_fwd = flops.win_attn_fwd(cfg, tr_)
    f_bwd, b_bwd = flops.win_attn_bwd(cfg, tr_)
    assert f_fwd == calls * h * sum(live) * (2 * d + 2 * 2 * d)
    assert f_bwd == calls * h * sum(live) * (3 * 2 * d + 2 * 2 * 2 * d)
    assert b_fwd == 3 * calls * seq * item * (h * 3 * d + hkv * 3 * d)
    assert b_bwd == 3 * calls * seq * item * (h * 4 * d + hkv * 6 * d)
    # the forward matches the maps' share of forward_flops_per_item
    cell = _cfg()
    f_cell = flops.win_attn_fwd(cell, _traffic())[0]
    e = cell["hidden_size"]
    maps = sum(flops.live_pairs(k, 8192, 512) for k in
               ("window", "causal", "causal")) * 20 * 12 * 64
    assert f_cell == maps
    # at the cell's size the roof is the products: 5.5 ms forward, 12.9
    # backward a step
    f_bwd_cell, b_bwd_cell = flops.win_attn_bwd(cell, _traffic())
    assert f_cell / 197e12 == pytest.approx(5.55e-3, rel=0.01)
    assert f_bwd_cell / 197e12 == pytest.approx(12.95e-3, rel=0.01)
    assert b_bwd_cell / 819e9 < f_bwd_cell / 197e12 and e == 2560


def test_scan_work_counted_on_the_references_own_recurrence():
    """The multiplies, adds and exponentials of one position, channel and
    state of the reference's recurrence (delta A, its exponential, the
    decay of h, delta u times B, the sum, C h and its sum over states)
    are 7; per position Di N of them; the bytes u, z, B, C in bfloat16,
    delta float32, g out, and backward the five cotangents and g's."""
    cfg = _cfg(rehearse=True)
    di, n = cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"]
    per_state = 1 + 1 + 1 + 1 + 1 + 1 + 1
    assert flops.scan_flops_per_position(cfg) == per_state * di * n
    tr_ = dict(_traffic(), seq=512, batch_per_chip=2)
    f_fwd, b_fwd = flops.ssm_scan_fwd(cfg, tr_)
    f_bwd, b_bwd = flops.ssm_scan_bwd(cfg, tr_)
    layers, positions = 2, 1024
    assert f_fwd == layers * positions * 7 * di * n
    assert f_bwd == 3 * f_fwd
    ins = 2 * di + 4 * di + 2 * di + 2 * 2 * n      # u, delta, z, B, C
    assert b_fwd == layers * positions * (ins + 2 * di)
    assert b_bwd == layers * positions * (2 * ins + 2 * di)
    # at the cell's size the roof is the bytes: 1.0 ms a step forward
    f_cell, b_cell = flops.ssm_scan_fwd(_cfg(), _traffic())
    assert b_cell / 819e9 > f_cell / 197e12
    assert b_cell / 819e9 == pytest.approx(1.03e-3, rel=0.02)


# ---------------------------------------------------------------------------
# the limits and the rehearsal: correct has to be able to fail

def test_the_limits_file_names_every_compared_number():
    lim = run.load_json("limits", CELL + ".json")
    assert lim["cell"] == CELL and lim["limits"]
    for name, limit in lim["limits"].items():
        assert name in lim["readings"], name
        r = lim["readings"][name]
        assert r["lower"] < limit, name
        assert r["upper"] is None or limit < r["upper"], name
        assert len(r["control_fp8"]) >= 6 and r["seeds"] >= 12, name
    # the control fails at least one limit on every seed it was read on
    n = len(next(iter(lim["readings"].values()))["control_fp8"])
    for i in range(n):
        assert any(r["control_fp8"][i] > lim["limits"][k]
                   for k, r in lim["readings"].items()
                   if k in lim["limits"]), i


def _float32(ctx):
    ctx.cfg["train"]["compute_dtype"] = "float32"


def _run(fault=None, seed=4_000_000_007):
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


def test_fp8_control_is_not_correct():
    args = argparse.Namespace(workload=CELL, seed=4_000_000_011, seconds=0.5,
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
