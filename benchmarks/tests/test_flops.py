"""Operation counts from shapes (counts only; the CPU gives no rate)."""
import pytest

import run


def _cfg(name):
    return run.load_json("configs", name + ".json")


def test_resnet50_v1_counts_from_layer_shapes():
    f = run.load_module("flops", "resnet50_v1.py")
    cfg = _cfg("resnet50_v1")
    # MXNet's v1 puts the stride on the first 1x1: 3.86 G multiply-
    # accumulates, not the 4.09 G of the v1.5 variant that bench.py's
    # constant carried (as "GFLOP").
    assert f.forward_macs_per_item(cfg) == 3_857_973_248
    assert f.forward_flops_per_item(cfg) == 2 * 3_857_973_248
    assert f.train_flops_per_item(cfg) == 6 * 3_857_973_248
    assert f.score_flops_per_item(cfg) == f.forward_flops_per_item(cfg)
    convs = list(f._convs(cfg))
    assert len(convs) == 1 + 16 * 3 + 4
    assert convs[0] == (112, 7, 7, 3, 64) and convs[-1][0] == 7


def test_opt_counts_per_token_and_flash_forward():
    f = run.load_module("flops", "opt-1.3b.py")
    cfg = _cfg("opt-1.3b")
    traffic = run.load_json("traffic", "train-lm-2x2048.json")
    e, ffn, v, s = 2048, 8192, 50272, 2048
    layer = 2 * (4 * e * e + 2 * e * ffn) + 2 * s * e
    assert f.forward_flops_per_item(cfg, s) == 8 * layer + 2 * e * v
    assert f.train_flops_per_item(cfg, traffic) == pytest.approx(3.235e9,
                                                                 rel=1e-3)
    flops, nbytes = f.flash_fwd(cfg, traffic)
    assert f.flash_fwd_shape(cfg, traffic) == (64, 2048, 64)
    assert flops == 64 * 2 * 2 * (2048 * 2048 / 2) * 64
    assert nbytes == 64 * 4 * 2048 * 64 * 2
