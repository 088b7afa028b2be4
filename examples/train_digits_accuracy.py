"""Accuracy-parity proxy on REAL data (VERDICT r4 item 9).

This zero-egress environment cannot download CIFAR/ImageNet, but
scikit-learn ships the UCI handwritten-digits dataset (1797 8x8 images,
10 classes) inside the package. Published-comparable baselines on the
standard split: sklearn's own classifier example reports ~97% (SVM,
https://scikit-learn.org/stable/auto_examples/classification/
plot_digits_classification.html); small CNNs reach 98-99%.

This script trains a gluon CNN end to end through the full framework
stack (NDArrayIter -> HybridBlock -> autograd -> Trainer/SGD) and
reports test accuracy. Passing bar: >= 0.97 — matching the published
classical baseline through OUR training loop.

  python examples/train_digits_accuracy.py            # ~2 min on CPU
  python examples/train_digits_accuracy.py --json ACCURACY_r05.json
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=30)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--json", default=None,
                   help="write the accuracy artifact here")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    import numpy as onp
    from sklearn.datasets import load_digits
    from sklearn.model_selection import train_test_split

    import mxnet_tpu as mx
    from mxnet_tpu import nd, autograd, gluon
    from mxnet_tpu.gluon import nn

    # mx.random.seed drives the device PRNG; NDArrayIter's shuffle
    # rides numpy's global RNG — seed it too for a reproducible run
    onp.random.seed(args.seed)
    digits = load_digits()
    X = (digits.images.astype("float32") / 16.0)[:, None, :, :]  # NCHW
    y = digits.target.astype("float32")
    # the canonical evaluation split (sklearn example: 50/50
    # train/test, shuffle with fixed seed)
    Xtr, Xte, ytr, yte = train_test_split(
        X, y, test_size=0.5, random_state=args.seed, shuffle=True)

    mx.random.seed(args.seed)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(32, 3, padding=1, activation="relu"),
            nn.Conv2D(32, 3, padding=1, activation="relu"),
            nn.MaxPool2D(2),
            nn.Flatten(),
            nn.Dense(128, activation="relu"),
            nn.Dropout(0.3),
            nn.Dense(10))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9})

    train_iter = mx.io.NDArrayIter(nd.array(Xtr), nd.array(ytr),
                                   batch_size=args.batch, shuffle=True)
    t0 = time.perf_counter()
    for epoch in range(args.epochs):
        train_iter.reset()
        total = correct = 0
        for batch in train_iter:
            xb, yb = batch.data[0], batch.label[0]
            with autograd.record():
                out = net(xb)
                l = loss_fn(out, yb).mean()
            l.backward()
            trainer.step(1)
            pred = out.asnumpy().argmax(1)
            correct += int((pred == yb.asnumpy()).sum())
            total += xb.shape[0]
        if (epoch + 1) % 10 == 0:
            print(f"epoch {epoch + 1}: train acc "
                  f"{correct / max(total, 1):.4f}")
    train_s = time.perf_counter() - t0

    with autograd.pause(train_mode=False):
        logits = net(nd.array(Xte)).asnumpy()
    acc = float((logits.argmax(1) == yte).mean())
    print(f"test accuracy: {acc:.4f} on {len(yte)} held-out digits "
          f"(published classical baseline ~0.97) — trained in "
          f"{train_s:.1f}s")
    payload = {
        "metric": "digits_test_accuracy", "value": round(acc, 4),
        "unit": "top1", "vs_baseline": round(acc / 0.97, 3),
        "extra": {"dataset": "sklearn load_digits (UCI, 1797x8x8)",
                  "split": "50/50 random_state=%d" % args.seed,
                  "published_baseline": 0.97,
                  "epochs": args.epochs, "train_seconds": round(train_s, 1),
                  "note": "zero-egress proxy for VERDICT item 9: real "
                          "data through the full gluon training stack"}}
    print(json.dumps(payload))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(payload, f)
    return acc


if __name__ == "__main__":
    raise SystemExit(0 if main() >= 0.97 else 1)
