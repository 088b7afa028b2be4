"""ResNet training on a device mesh with the compiled SPMD path
(reference: example/image-classification/train_imagenet.py +
--benchmark 1, rebuilt around SPMDTrainer instead of kvstore devices).

  python examples/train_resnet_spmd.py --batch 64 --steps 10 --bf16
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/train_resnet_spmd.py --dp 4 --mp 2
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--image-size", type=int, default=96)
    p.add_argument("--classes", type=int, default=100)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--depth", type=int, default=18)
    p.add_argument("--dp", type=int, default=0, help="data-parallel way")
    p.add_argument("--mp", type=int, default=1,
                   help="tensor-parallel way")
    p.add_argument("--optimizer", default="sgd",
                   choices=["sgd", "adam", "adamw", "lamb"])
    p.add_argument("--bf16", action="store_true")
    args = p.parse_args()

    import numpy as onp
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import nd, gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision

    ndev = jax.device_count()
    dp = args.dp or max(ndev // args.mp, 1)
    mesh = parallel.make_mesh({"dp": dp, "mp": args.mp})
    print(f"mesh: dp={dp} x mp={args.mp} over {ndev} device(s)")

    mx.random.seed(0)
    net = getattr(vision, f"resnet{args.depth}_v1")(classes=args.classes)
    net.initialize(mx.init.Xavier())
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer=args.optimizer,
        optimizer_params={"learning_rate": 0.05, "momentum": 0.9}
        if args.optimizer == "sgd" else {"learning_rate": 1e-3},
        mesh=mesh,
        compute_dtype="bfloat16" if args.bf16 else None)

    rs = onp.random.RandomState(0)
    x = nd.array(rs.rand(args.batch, 3, args.image_size,
                         args.image_size).astype("f"))
    y = nd.array(rs.randint(0, args.classes, args.batch).astype("f"))
    loss = trainer.step(x, y)  # compile
    loss.wait_to_read()
    t0 = time.perf_counter()
    for i in range(args.steps):
        loss = trainer.step(x, y)
    loss.wait_to_read()
    dt = time.perf_counter() - t0
    print(f"loss={float(loss.asscalar()):.4f}  "
          f"{args.batch * args.steps / dt:.1f} img/s")


if __name__ == "__main__":
    main()
