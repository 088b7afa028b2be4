"""Operations of ResNet-50 v1, counted from the layer shapes.

A multiply-accumulate is two floating-point operations, which is how the
chip's published peak counts them. Only convolutions and the classifier
are counted (BatchNorm, ReLU and pooling are under 1%). Training is
three times the forward pass: the backward pass computes a gradient for
the input and one for the weights of every layer. Recomputation is not
counted.
"""
from __future__ import annotations


def _convs(cfg):
    """(out_h, kh, kw, cin, cout) of every convolution, in order."""
    size = cfg["image_size"]
    c0 = cfg["stem_channels"]
    h = (size + 2 * 3 - 7) // 2 + 1
    yield h, 7, 7, 3, c0
    h = (h + 2 - 3) // 2 + 1          # 3x3/2 max pool
    cin = c0
    for si, (n, ch) in enumerate(zip(cfg["layers"], cfg["channels"])):
        for bi in range(n):
            stride = (1 if si == 0 else 2) if bi == 0 else 1
            mid = ch // 4
            ho = (h - 1) // stride + 1
            yield ho, 1, 1, cin, mid      # stride on the first 1x1 (v1)
            yield ho, 3, 3, mid, mid
            yield ho, 1, 1, mid, ch
            if bi == 0:
                yield ho, 1, 1, cin, ch   # projection shortcut
            h, cin = ho, ch


def forward_macs_per_item(cfg):
    macs = sum(h * h * kh * kw * ci * co for h, kh, kw, ci, co in _convs(cfg))
    return macs + cfg["channels"][-1] * cfg["classes"]


def forward_flops_per_item(cfg):
    return 2 * forward_macs_per_item(cfg)


def train_flops_per_item(cfg, traffic=None):
    """Model FLOPs of one image through forward and backward."""
    return 3 * forward_flops_per_item(cfg)


def score_flops_per_item(cfg, traffic=None):
    return forward_flops_per_item(cfg)
