"""Builds configuration ``resnet50_v1`` through the program's public API
and ties its parameters to the reference's leaves."""
from __future__ import annotations

import numpy as onp


def build_net(cfg):
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.gluon.model_zoo.vision import resnet

    kw = dict(classes=cfg["classes"], layout=cfg["layout"],
              stem_s2d=cfg["stem_s2d"])
    if cfg["layers"] == [3, 4, 6, 3] and cfg["stem_channels"] == 64 \
            and cfg["channels"] == [256, 512, 1024, 2048]:
        return vision.resnet50_v1(**kw)
    # a rehearsal's narrow copy of the same block structure
    return resnet.ResNetV1(resnet.BottleneckV1, cfg["layers"],
                           [cfg["stem_channels"]] + cfg["channels"], **kw)


def loss_block(cfg):
    from mxnet_tpu import gluon

    return gluon.loss.SoftmaxCrossEntropyLoss()


def example_input(cfg, traffic):
    s = cfg["image_size"]
    return onp.zeros((1, s, s, 3), "float32")


def items_per_batch(cfg, traffic, batch):
    return batch


def make_batch(cfg, traffic, batch, rng):
    """(images NHWC float32 in [0, 1), labels as float32 class ids)."""
    s = cfg["image_size"]
    x = rng.random((batch, s, s, 3), dtype=onp.float32)
    y = rng.integers(0, cfg["classes"], batch).astype("float32")
    return x, y


def to_program(leaf, value):
    """A reference leaf in the program's layout: convolutions HWIO ->
    OHWI, the classifier (in, out) -> (out, in)."""
    if value.ndim == 4:
        return value.transpose(3, 0, 1, 2)
    if value.ndim == 2:
        return value.T
    return value

