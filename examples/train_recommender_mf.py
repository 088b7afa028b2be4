"""Matrix-factorization recommender (reference:
example/recommenders/demo1-MF.ipynb + example/sparse/matrix_factorization
— the classic two-Embedding dot-product model, trained here with the
gluon API on synthetic ratings).

  python examples/train_recommender_mf.py --users 200 --items 120
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--users", type=int, default=200)
    p.add_argument("--items", type=int, default=120)
    p.add_argument("--rank", type=int, default=16)
    p.add_argument("--ratings", type=int, default=4000)
    p.add_argument("--epochs", type=int, default=15)
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import nd, autograd, gluon
    from mxnet_tpu.gluon import nn

    onp.random.seed(args.seed)
    mx.random.seed(args.seed)
    # synthetic low-rank ground truth + noise
    true_u = onp.random.randn(args.users, 4).astype("f")
    true_i = onp.random.randn(args.items, 4).astype("f")
    u_idx = onp.random.randint(0, args.users, args.ratings)
    i_idx = onp.random.randint(0, args.items, args.ratings)
    ratings = (true_u[u_idx] * true_i[i_idx]).sum(1) + \
        0.1 * onp.random.randn(args.ratings).astype("f")

    class MFBlock(gluon.HybridBlock):
        def __init__(self, n_users, n_items, rank):
            super().__init__()
            self.user_emb = nn.Embedding(n_users, rank)
            self.item_emb = nn.Embedding(n_items, rank)

        def hybrid_forward(self, F, users, items):
            u = self.user_emb(users)
            i = self.item_emb(items)
            return (u * i).sum(axis=1)

    net = MFBlock(args.users, args.items, args.rank)
    net.initialize(mx.init.Normal(0.1))
    net.hybridize()
    loss_fn = gluon.loss.L2Loss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.02})

    n = args.ratings
    t0 = time.perf_counter()
    first = last = None
    for epoch in range(args.epochs):
        perm = onp.random.permutation(n)
        total = 0.0
        for s in range(0, n - args.batch + 1, args.batch):
            sel = perm[s:s + args.batch]
            bu = nd.array(u_idx[sel].astype("f"))
            bi = nd.array(i_idx[sel].astype("f"))
            br = nd.array(ratings[sel])
            with autograd.record():
                pred = net(bu, bi)
                l = loss_fn(pred, br).mean()
            l.backward()
            trainer.step(1)
            total += float(l.asscalar())
        mse = 2 * total / max(1, (n // args.batch))  # L2Loss = 1/2 MSE
        if first is None:
            first = mse
        last = mse
    dt = time.perf_counter() - t0
    print(f"MF {args.users}x{args.items} rank={args.rank}: train MSE "
          f"{first:.4f} -> {last:.4f} in {dt:.1f}s")
    assert last < first * 0.25, "matrix factorization did not converge"
    return last


if __name__ == "__main__":
    main()
