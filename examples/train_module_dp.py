"""Module-API data parallelism: the reference's
``Module(context=[mx.gpu(0), mx.gpu(1), ...])`` flow on a TPU device
mesh (reference: example/image-classification with --gpus, backed by
DataParallelExecutorGroup — here ONE batch-sharded XLA computation).

  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/train_module_dp.py --ndev 8
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--ndev", type=int, default=0,
                   help="contexts to bind (default: all devices)")
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--epochs", type=int, default=6)
    args = p.parse_args()

    import jax
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import sym, io
    from mxnet_tpu.module import Module

    ndev = args.ndev or jax.device_count()
    ctxs = [mx.cpu(i) if jax.devices()[0].platform == "cpu" else mx.tpu(i)
            for i in range(ndev)]
    print(f"binding over {ndev} context(s): {ctxs}")

    rs = onp.random.RandomState(0)
    X = rs.randn(1024, 16).astype("f")
    y = (X[:, :8].sum(1) > X[:, 8:].sum(1)).astype("f")

    data = sym.Variable("data")
    net = sym.FullyConnected(data, name="fc1", num_hidden=64)
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, name="fc2", num_hidden=2)
    out = sym.SoftmaxOutput(net, sym.Variable("softmax_label"),
                            name="softmax")

    mod = Module(out, context=ctxs if ndev > 1 else ctxs[0])
    train = io.NDArrayIter(X, y, batch_size=args.batch, shuffle=True)
    mod.fit(train, num_epoch=args.epochs, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            eval_metric="acc",
            batch_end_callback=mx.callback.Speedometer(args.batch, 8))
    score = mod.score(io.NDArrayIter(X, y, batch_size=args.batch), "acc")
    print(f"final accuracy over {ndev} device(s): {dict(score)}")


if __name__ == "__main__":
    main()
