"""Toy SSD-style detector: the MultiBox pipeline end to end.

Reference workflow: example/ssd (MultiBoxPrior → MultiBoxTarget →
SmoothL1 + softmax losses → MultiBoxDetection at inference), shrunk to a
synthetic dataset of colored squares so it runs in seconds on CPU/TPU.

Run: JAX_PLATFORMS=cpu python examples/train_ssd_toy.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, gluon
from mxnet_tpu.gluon import nn

IMG = 64
CLASSES = 2  # square / circle-ish blob


def synth_batch(rng, batch):
    """Images with ONE bright square each; label = (cls, x0, y0, x1, y1)."""
    x = rng.rand(batch, 3, IMG, IMG).astype("f") * 0.1
    labels = onp.zeros((batch, 1, 5), "f")
    for i in range(batch):
        cls = rng.randint(0, CLASSES)
        w = rng.randint(12, 28)
        x0 = rng.randint(0, IMG - w)
        y0 = rng.randint(0, IMG - w)
        x[i, cls, y0:y0 + w, x0:x0 + w] = 1.0
        labels[i, 0] = [cls, x0 / IMG, y0 / IMG, (x0 + w) / IMG,
                        (y0 + w) / IMG]
    return nd.array(x), nd.array(labels)


class ToySSD(gluon.Block):
    """Imperative Block: the heads use concrete shapes for reshaping
    (hybridize-safe variants would use reshape((0, -1, ...)) codes)."""
    def __init__(self, num_anchors, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.backbone = nn.HybridSequential()
            for ch in (16, 32, 64):
                self.backbone.add(
                    nn.Conv2D(ch, 3, strides=2, padding=1,
                              activation="relu"))
            self.cls_head = nn.Conv2D(num_anchors * (CLASSES + 1), 3,
                                      padding=1)
            self.loc_head = nn.Conv2D(num_anchors * 4, 3, padding=1)

    def forward(self, x):
        feat = self.backbone(x)  # (B, 64, 8, 8)
        cls = self.cls_head(feat)  # (B, A*(C+1), 8, 8)
        loc = self.loc_head(feat)  # (B, A*4, 8, 8)
        B = cls.shape[0]
        cls = cls.transpose((0, 2, 3, 1)).reshape(B, -1, CLASSES + 1)
        loc = loc.transpose((0, 2, 3, 1)).reshape(B, -1)
        return feat, cls, loc


def main():
    mx.random.seed(0)
    rng = onp.random.RandomState(0)
    sizes = [0.2, 0.4]
    ratios = [1.0, 1.5]
    num_anchors = len(sizes) + len(ratios) - 1
    net = ToySSD(num_anchors)
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 2e-3})
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    anchors = None
    for step in range(120):
        x, labels = synth_batch(rng, 16)
        with autograd.record():
            feat, cls_preds, loc_preds = net(x)
            if anchors is None:
                anchors = nd.contrib.MultiBoxPrior(
                    feat, sizes=sizes, ratios=ratios)
            loc_t, loc_mask, cls_t = nd.contrib.MultiBoxTarget(
                anchors, labels, cls_preds.transpose((0, 2, 1)))
            cls_loss = ce(cls_preds.reshape(-1, CLASSES + 1),
                          cls_t.reshape(-1))
            loc_loss = nd.mean(nd.smooth_l1(
                (loc_preds - loc_t) * loc_mask, scalar=1.0))
            loss = nd.mean(cls_loss) + loc_loss
        loss.backward()
        trainer.step(16)
        if step % 20 == 0:
            print(f"step {step}: loss={float(loss.asscalar()):.4f}")

    # inference: decode + NMS
    x, labels = synth_batch(rng, 4)
    feat, cls_preds, loc_preds = net(x)
    probs = nd.softmax(cls_preds, axis=-1).transpose((0, 2, 1))
    dets = nd.contrib.MultiBoxDetection(probs, loc_preds, anchors,
                                        threshold=0.1)
    kept = dets.asnumpy()[0]
    kept = kept[kept[:, 0] >= 0]
    print(f"detections for image 0 (gt cls {int(labels.asnumpy()[0,0,0])}"
          f" box {labels.asnumpy()[0,0,1:].round(2)}):")
    for d in kept[:3]:
        print(f"  cls={int(d[0])} score={d[1]:.2f} box={d[2:].round(2)}")
    final = float(loss.asscalar())
    print("done; final loss", round(final, 4))
    assert final < 2.0, "training diverged"


if __name__ == "__main__":
    main()
