"""MLP classifier with the Module API (reference:
example/image-classification/train_mnist.py).

Synthetic data stands in for MNIST (no dataset egress in this
environment); swap in mx.gluon.data.vision.MNIST for the real thing.

  python examples/train_mnist_mlp.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu.io import NDArrayIter
from mxnet_tpu.module import Module


def mlp_symbol(num_classes=10):
    data = sym.Variable("data")
    net = sym.FullyConnected(data, name="fc1", num_hidden=128,
                             weight=sym.Variable("fc1_weight"),
                             bias=sym.Variable("fc1_bias"))
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, name="fc2", num_hidden=64,
                             weight=sym.Variable("fc2_weight"),
                             bias=sym.Variable("fc2_bias"))
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, name="fc3", num_hidden=num_classes,
                             weight=sym.Variable("fc3_weight"),
                             bias=sym.Variable("fc3_bias"))
    return sym.SoftmaxOutput(net, sym.Variable("softmax_label"),
                             name="softmax")


def main():
    rs = onp.random.RandomState(0)
    X = rs.rand(2048, 784).astype("f")
    w = rs.randn(784, 10).astype("f")
    y = (X @ w).argmax(1).astype("f")
    train = NDArrayIter(X[:1792], y[:1792], batch_size=128,
                        shuffle=True, label_name="softmax_label")
    val = NDArrayIter(X[1792:], y[1792:], batch_size=128,
                      label_name="softmax_label")
    mod = Module(mlp_symbol())
    mod.fit(train, eval_data=val, optimizer="sgd",
            optimizer_params={"learning_rate": 0.3, "momentum": 0.9},
            num_epoch=8,
            batch_end_callback=mx.callback.Speedometer(128, 10))
    score = mod.score(val, "acc")
    print("validation accuracy:", score)


if __name__ == "__main__":
    main()
