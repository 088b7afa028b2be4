"""Per-operator performance sweeps (reference: benchmark/opperf/ —
``run_performance_test`` + the category sweeps of opperf.py, which the
reference drives through its profiler to catch op-level regressions).

TPU-native measurement rules:
- one warmup call compiles (jit caches by shape/dtype);
- timing syncs through ``jax.device_get`` of a scalar reduced from the
  output: the window ends when the result has reached the host;
- forward+backward measures ``jax.value_and_grad`` of sum(op(*inputs))
  — the op's actual training cost, vjp included.

    python -m mxnet_tpu.benchmark.opperf            # default suite
    python -m mxnet_tpu.benchmark.opperf --ops dot,conv2d --dtype bfloat16

Programmatic (reference benchmark_utils.py:95 run_performance_test):

    from mxnet_tpu.benchmark import run_performance_test
    r = run_performance_test(lambda x, y: mx.nd.dot(x, y),
                             inputs=[(256, 256), (256, 256)])
"""
from __future__ import annotations

import argparse
import json
import time

__all__ = ["run_performance_test", "run_op_suite", "DEFAULT_SUITE"]


def _time_fn(fn, args, warmup, runs):
    import jax

    out = fn(*args)  # compile + warm caches
    for _ in range(warmup - 1):
        out = fn(*args)
    _ = jax.device_get(out)
    t0 = time.perf_counter()
    for _ in range(runs):
        out = fn(*args)
    _ = jax.device_get(out)  # host readback ends the window
    return (time.perf_counter() - t0) / runs


def run_performance_test(op_fn, inputs, run_backward=True, dtype="float32",
                         warmup=2, runs=10, flops=None, name=None):
    """Time one operator; returns a result dict.

    op_fn: callable over NDArrays. inputs: list of shapes (tuples) or
    ready numpy arrays. flops: optional FLOP count per call for a
    GFLOP/s column. Mirrors reference run_performance_test semantics
    (forward and forward+backward timed separately)."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    from .. import nd

    rng = onp.random.RandomState(0)
    arrs = []
    for spec in inputs:
        a = rng.rand(*spec).astype("float32") if isinstance(
            spec, (tuple, list)) else onp.asarray(spec)
        arrs.append(a)
    cdtype = jnp.dtype(dtype)
    datas = [jnp.asarray(a).astype(cdtype) if onp.issubdtype(
        a.dtype, onp.floating) else jnp.asarray(a) for a in arrs]

    def fwd(*ds):
        out = op_fn(*[nd.NDArray(d) for d in ds])
        outs = out if isinstance(out, (list, tuple)) else [out]
        return sum(jnp.sum(o.data.astype(jnp.float32)) for o in outs)

    fwd_jit = jax.jit(fwd)  # graft-lint: allow(jit-nocache)
    fwd_s = _time_fn(fwd_jit, datas, warmup, runs)
    result = {"op": name or getattr(op_fn, "__name__", "op"),
              "dtype": str(dtype),
              "inputs": [list(a.shape) for a in arrs],
              "fwd_ms": round(fwd_s * 1e3, 4)}
    if flops:
        result["fwd_gflops"] = round(flops / fwd_s / 1e9, 2)
    argnums = tuple(i for i, d in enumerate(datas)
                    if jnp.issubdtype(d.dtype, jnp.floating))
    if run_backward and not argnums:
        result["backward"] = "skipped (no floating inputs)"
    elif run_backward:
        grad = jax.grad(fwd, argnums=argnums)

        def bwd_scalar(*ds):
            # reduce to ONE scalar inside the jit so the barrier reads
            # back 4 bytes (same rule as the forward column) — but
            # contract each gradient WITH ITS INPUT: a plain sum would
            # let XLA constant-fold trivial VJPs (grad of sum(a+b) is
            # ones → the whole backward disappears), and the column
            # would read 0
            gs = grad(*ds)
            return sum(jnp.vdot(g.astype(jnp.float32),
                                ds[i].astype(jnp.float32))
                       for g, i in zip(gs, argnums))

        bwd_s = _time_fn(jax.jit(bwd_scalar),  # graft-lint: allow(jit-nocache)
                         datas, warmup, runs)
        result["fwd_bwd_ms"] = round(bwd_s * 1e3, 4)
    return result


def _suite():
    """Representative op per §2.2 family at a size that exercises the
    MXU/VPU without minute-long CPU fallbacks."""
    from .. import nd

    B = 64
    return {
        "broadcast_add": (lambda a, b: nd.broadcast_add(a, b),
                          [(B, 1024), (B, 1024)], 2 * B * 1024),
        "exp": (lambda a: nd.exp(a), [(B, 1024)], None),
        "sum": (lambda a: nd.sum(a, axis=1), [(B, 4096)], None),
        "topk": (lambda a: nd.topk(a, k=8, axis=1), [(B, 1024)], None),
        "dot": (lambda a, b: nd.dot(a, b), [(512, 512), (512, 512)],
                2 * 512 ** 3),
        "batch_dot": (lambda a, b: nd.batch_dot(a, b),
                      [(B, 64, 64), (B, 64, 64)], 2 * B * 64 ** 3),
        "conv2d": (
            lambda x, w, b: nd.Convolution(x, w, b, kernel=(3, 3),
                                           num_filter=64, pad=(1, 1)),
            [(8, 64, 28, 28), (64, 64, 3, 3), (64,)],
            2 * 8 * 64 * 64 * 9 * 28 * 28),
        "fully_connected": (
            lambda x, w, b: nd.FullyConnected(x, w, b, num_hidden=1024),
            [(B, 1024), (1024, 1024), (1024,)], 2 * B * 1024 * 1024),
        "batch_norm_train": (
            lambda x, g, b, m, v: nd.batch_norm(x, g, b, m, v,
                                                use_batch_stats=True),
            [(8, 64, 28, 28), (64,), (64,), (64,), (64,)], None),
        "softmax": (lambda a: nd.softmax(a, axis=-1), [(B, 4096)], None),
        "embedding": (
            lambda i, w: nd.Embedding(i, w, input_dim=10000,
                                      output_dim=256),
            ["_idx", (10000, 256)], None),
        "layer_norm": (lambda x, g, b: nd.LayerNorm(x, g, b, axis=-1),
                       [(B, 1024), (1024,), (1024,)], None),
        "sgd_mom_update": (
            lambda w, g, m: nd.sgd_mom_update(w, g, m, lr=0.1,
                                              momentum=0.9),
            [(1024, 1024), (1024, 1024), (1024, 1024)], None),
        "transpose": (lambda a: nd.transpose(a, (1, 0)), [(2048, 2048)],
                      None),
    }


def DEFAULT_SUITE():
    """Names in the default sweep (built lazily — the suite table
    touches mx.nd)."""
    return sorted(_suite())


def run_op_suite(ops=None, dtype="float32", warmup=2, runs=10):
    """Run the (filtered) default sweep; returns a list of result
    dicts (reference opperf.py category runs)."""
    import numpy as onp

    suite = _suite()
    names = list(suite) if not ops else [o for o in ops if o in suite]
    unknown = [] if not ops else [o for o in ops if o not in suite]
    if unknown:
        raise ValueError(f"unknown suite ops {unknown}; "
                         f"available: {sorted(suite)}")
    results = []
    rng = onp.random.RandomState(1)
    for n in names:
        fn, shapes, flops = suite[n]
        inputs = [rng.randint(0, 10000, (64,)).astype("f")
                  if s == "_idx" else s for s in shapes]
        no_bwd = n in ("topk", "sgd_mom_update", "embedding")
        results.append(run_performance_test(
            fn, inputs, run_backward=not no_bwd, dtype=dtype,
            warmup=warmup, runs=runs, flops=flops, name=n))
    return results


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--ops", default=None,
                   help="comma-separated subset of the suite")
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "float16", "bfloat16"])
    p.add_argument("--runs", type=int, default=10)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--output", default=None, help="write JSON here")
    args = p.parse_args(argv)
    ops = args.ops.split(",") if args.ops else None
    results = run_op_suite(ops, dtype=args.dtype, runs=args.runs,
                       warmup=args.warmup)
    import jax

    payload = {"device": str(jax.devices()[0].device_kind),
               "dtype": args.dtype, "results": results}
    text = json.dumps(payload, indent=2)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
