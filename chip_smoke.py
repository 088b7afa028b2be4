"""Does the system still start on the chip? One process, one TPU.

Drives the two hot paths through the entry points a user calls, at the
full published size of ResNet-50 v1 (1000 classes, 224x224), with random
weights from a fixed seed:

  train    SPMDTrainer on a one-device mesh, bf16 compute, batch 128:
           one compile, five steps on a repeated batch.
  serve    InferenceSession -> DynamicBatcher -> ModelServer(port=0):
           warm the buckets, POST eight /predict requests over HTTP,
           compare with the hybridized net's own forward on the chip.
  kernels  each Pallas kernel once against its lax twin, and the native
           int8 fully-connected lowering against the dequant one.
  prologue the q/k norms, RoPE and layout of grouped-query attention as
           their kernel pair against the plain twin.
  delta_prologue
           the causal convolution, SiLU, l2 norms and layout before the
           gated delta rule as their kernel pair against the plain twin.
  short_conv
           LFM2's gated short convolution as its kernel pair against the
           plain twin, and both timed alone at the LFM2 cell's shape.
  ssm_scan the selective scan of a Mamba layer as its kernel pair
           against the lax.scan twin, and both timed alone at the
           Phi-4-mini-flash cell's shape.

``--chips 4`` runs ONLY the data-parallel phase and what it is compared
with (same seed, same global batch, one-device mesh vs dp=4 mesh).
``--rehearse`` lifts the platform check, shrinks sizes and interprets
the kernels so the control flow can be walked on the CPU; a rehearsal
never prints the result line.

Run with no arguments on one chip, the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Any phase that fails raises: non-zero exit, no result line. The script
refuses to run when jax's first device is not a TPU, starts no child
process, and places no cache itself (jax's persistent cache goes where
JAX_COMPILATION_CACHE_DIR says, else to <checkout>/.jax_cache).
"""
from __future__ import annotations

import argparse
import io
import json
import os
import sys
import time
import urllib.request

import numpy as onp

SEED = 0


class Cfg:
    """Sizes of a run. The chip run is the published ResNet-50 width;
    the rehearsal only walks the control flow."""

    def __init__(self, rehearse):
        self.rehearse = rehearse
        self.platform = "cpu" if rehearse else "tpu"
        self.image = 32 if rehearse else 224
        self.classes = 10 if rehearse else 1000
        self.train_batch = 8 if rehearse else 128
        self.serve_buckets = (1, 4) if rehearse else (1, 8)
        # Pallas runs compiled on the chip; interpreted in a rehearsal
        self.pallas = "interpret" if rehearse else "pallas"

    def expect(self, ok, msg):
        """Assert a property of the training dynamics. It holds at the
        real size; at the rehearsal's (BatchNorm over eight 1x1 maps)
        the dynamics are chaotic, so there it is only reported."""
        if self.rehearse:
            log(f"rehearsal, not asserted: {'holds' if ok else 'FAILS'}: "
                f"{msg}")
        else:
            assert ok, msg


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def assert_on(arrays, platform, what):
    """Every array lives on a device of ``platform`` — asked of the
    arrays themselves, not of the context they were made under."""
    for name, a in arrays:
        plats = {d.platform for d in a.devices()}
        assert plats == {platform}, \
            f"{what} {name} lives on {sorted(plats)}, expected {platform}"


def build_net(cfg, hybridize=False):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(SEED)
    net = vision.resnet50_v1(classes=cfg.classes, layout="NHWC",
                             stem_s2d=True)
    net.initialize(mx.init.Xavier())
    if hybridize:
        net.hybridize()
    return net


def build_trainer(cfg, mesh, lr=0.05):
    from mxnet_tpu import gluon, parallel

    return parallel.SPMDTrainer(
        build_net(cfg), gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer="sgd",
        optimizer_params={"learning_rate": lr, "momentum": 0.9},
        mesh=mesh, compute_dtype="bfloat16")


def train_batch(cfg):
    from mxnet_tpu import nd

    rng = onp.random.RandomState(SEED)
    b, s = cfg.train_batch, cfg.image
    x = nd.array(rng.rand(b, s, s, 3).astype("f"))
    y = nd.array(rng.randint(0, cfg.classes, b).astype("f"))
    return x, y


def run_steps(trainer, x, y, steps):
    """(losses, seconds per step, retraces after the first step). The
    loss readback is the barrier: a step is over when its loss is on
    the host."""
    import jax

    from mxnet_tpu.utils import compile_cache as cc

    losses, secs, retraced = [], [], None
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(jax.device_get(trainer.step(x, y).data)))
        secs.append(time.perf_counter() - t0)
        if retraced is None:
            retraced = cc.compile_cache_stats()["retraces"]
    later = cc.compile_cache_stats()["retraces"] - retraced
    return losses, secs, later


# ---------------------------------------------------------------------------
# phases

def phase_train(cfg):
    import jax

    from mxnet_tpu import parallel

    mesh = parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = build_trainer(cfg, mesh)
    x, y = train_batch(cfg)
    losses, secs, later = run_steps(trainer, x, y, steps=5)
    log(f"train: build+compile+first step {secs[0]:.1f}s, "
        f"later steps {[round(s, 4) for s in secs[1:]]}s")
    log(f"train: loss trace {[round(v, 4) for v in losses]}")
    assert all(onp.isfinite(losses)), f"non-finite loss: {losses}"
    cfg.expect(losses[-1] < losses[0],
               f"loss falls on the repeated batch: {losses}")
    assert later == 0, f"{later} retrace(s) after the first step"
    params = trainer.param_arrays()
    assert len(params) > 100, len(params)
    assert_on(params.items(), cfg.platform, "parameter")
    log(f"train: {len(params)} parameters on {cfg.platform}, "
        "0 retraces after the first step")


def _post_npy(url, arr):
    buf = io.BytesIO()
    onp.save(buf, arr)
    req = urllib.request.Request(
        url + "/predict", data=buf.getvalue(),
        headers={"Content-Type": "application/x-npy",
                 "X-Timeout-Ms": "120000"})
    with urllib.request.urlopen(req, timeout=300) as resp:
        assert resp.status == 200, resp.status
        return onp.load(io.BytesIO(resp.read()), allow_pickle=False)


def phase_serve(cfg):
    from mxnet_tpu import autograd, nd, serving
    from mxnet_tpu.serving.metrics import METRICS
    from mxnet_tpu.utils import compile_cache as cc

    # an operator's SLO for a 224x224 model whose requests carry
    # megabytes: admission control must not shed the smoke's traffic
    os.environ.setdefault("MXNET_SERVING_SLO_MS", "10000")
    s = cfg.image
    net = build_net(cfg, hybridize=True)
    t0 = time.perf_counter()
    sess = serving.InferenceSession(
        net, input_shapes=[(1, s, s, 3)], buckets=list(cfg.serve_buckets),
        warm=False)
    warm = sess.warmup()
    log(f"serve: init + warm-up of buckets {sess.buckets} "
        f"{time.perf_counter() - t0:.1f}s, {warm}")
    assert warm["compiles"] + warm["disk_hits"] == len(sess.buckets), warm
    assert_on(((n, p.data().data) for n, p in
               net.collect_params().items()), cfg.platform, "serving param")

    rng = onp.random.RandomState(SEED + 1)
    small, big = cfg.serve_buckets
    batches = [small, big, small, small, big, small, big, big]
    payloads = [rng.rand(b, s, s, 3).astype("f") for b in batches]
    # the reference: the hybridized net's own eval forward on the chip
    # (compiles one CachedOp per batch size — before the counters below)
    refs = []
    with autograd.pause(train_mode=False):
        for x in payloads:
            out = net(nd.array(x))
            assert_on([("", out.data)], cfg.platform, "reference output")
            refs.append(out.asnumpy())
    direct = sess.predict(payloads[1])
    assert_on([("", direct.data)], cfg.platform, "served output")

    bat = serving.DynamicBatcher(sess, timeout_ms=120000)
    srv = serving.ModelServer(batcher=bat, port=0).start()
    try:
        traced = cc.compile_cache_stats()["retraces"]
        compiled = METRICS.snapshot()["warm_compiles"]
        outs = [_post_npy(srv.address, x) for x in payloads]
        traced = cc.compile_cache_stats()["retraces"] - traced
        compiled = METRICS.snapshot()["warm_compiles"] - compiled
    finally:
        srv.stop()
        bat.close()
        sess.close()
    assert traced == 0 and compiled == 0, \
        f"{traced} retrace(s), {compiled} compile(s) after warm-up"
    # Tolerance: both sides are fp32 programs of the same graph, but
    # they are two executables (CachedOp vs the serving bucket) that
    # XLA may fuse differently, and the TPU's default fp32 convolution
    # rounds operands to bf16 passes — so agreement is to bf16-pass
    # rounding of the largest logit, not bitwise.
    worst = 0.0
    for b, got, ref in zip(batches, outs, refs):
        assert got.shape == ref.shape == (b, cfg.classes), got.shape
        assert onp.isfinite(got).all()
        worst = max(worst, float(onp.abs(got - ref).max()
                                 / max(onp.abs(ref).max(), 1e-30)))
    log(f"serve: 8 requests (batches {batches}) answered, worst "
        f"deviation from the net's own forward {worst:.3e} of max |logit|; "
        "0 compiles after warm-up; server, batcher, session closed")
    assert worst < 1e-2, worst


def _lowered_has_kernel(fn, *args):
    import jax

    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def phase_kernels(cfg):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import kernels
    # importing attention and norm_act registers the cluster ops
    from mxnet_tpu.kernels import attention, cost_model, norm_act  # noqa: F401
    from mxnet_tpu.kernels.flash_attention import flash_attention
    from mxnet_tpu.ndarray import ops_quant
    from mxnet_tpu.ndarray.registry import get_op

    on_chip = not cfg.rehearse
    rs = onp.random.RandomState(SEED + 2)

    def randn(*shape):
        return jnp.asarray(rs.randn(*shape).astype("f"))

    def check(name, got, ref, tol, fn=None, args=()):
        err = float(jnp.abs(got - ref).max())
        log(f"kernels: {name} max |pallas - lax| = {err:.3e} (tol {tol})")
        assert bool(jnp.isfinite(got).all()) and err < tol, (name, err)
        assert_on([(name, got)], cfg.platform, "kernel output")
        if on_chip and fn is not None:
            assert _lowered_has_kernel(fn, *args), \
                f"{name}: no tpu_custom_call in the lowered program"

    # The tolerance is the interpret-mode parity tests' documented-ulp
    # bound (1e-5 absolute on O(1) fp32 values). It is a statement about
    # fp32 arithmetic, so both twins are traced at HIGHEST matmul
    # precision: the TPU's default rounds fp32 matmul operands to bf16,
    # which the kernel and its lax twin would apply at different points.
    with jax.default_matmul_precision("highest"):
        b, h, sq, d = (2, 2, 128, 64) if cfg.rehearse else (8, 12, 512, 64)
        q, k, v = (randn(b, h, sq, d) for _ in range(3))

        def flash(q, k, v):
            # use_pallas=None is the user's path: pallas on the TPU
            return flash_attention(q, k, v, causal=True,
                                   use_pallas=None if on_chip else True)

        ref = flash_attention(q, k, v, causal=True, use_pallas=False)
        check("flash forward", flash(q, k, v), ref, 1e-5, flash, (q, k, v))

        # the fused backward kernel against the scan (the xla path's
        # backward). The gradients reach ~5 and the kernel recomputes p
        # as exp(s - lse) where the scan divides by the sum: the TPU's
        # exp and log agree to ~5e-6 of a value, so 2e-5 of the largest
        do = randn(b, h, sq, d)

        def grads(attend):
            return jax.grad(lambda q, k, v: (attend(q, k, v) * do).sum(),
                            (0, 1, 2))

        scans = kernels.counters().get("flash_bwd_scan", 0)
        got = grads(flash)(q, k, v)
        assert kernels.counters().get("flash_bwd_scan", 0) == scans
        want = grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True, use_pallas=False))(q, k, v)
        for name, g, w in zip("qkv", got, want):
            # one lowering shows the kernels of all three
            check(f"flash backward d{name}", g, w, 1e-4,
                  grads(flash) if name == "q" else None, (q, k, v))

        bsz, heads, seq, hd = (2, 2, 64, 16) if cfg.rehearse \
            else (8, 12, 1024, 64)
        dq = randn(bsz, heads * hd)
        kc, vc = randn(bsz, seq, heads * hd), randn(bsz, seq, heads * hd)
        pos = jnp.asarray(rs.randint(0, seq, (bsz, 1)).astype("int32"))
        dec = get_op("_attention_decode").fn
        kw = {"num_heads": heads, "sm_scale": 1.0 / hd ** 0.5}

        def decode(q, kc, vc, pos):
            return dec(q, kc, vc, pos, impl=cfg.pallas, **kw)

        vmem_refusals = kernels.counters().get("fallback_vmem_bound", 0)
        check("decode flash", decode(dq, kc, vc, pos),
              dec(dq, kc, vc, pos, impl="lax", **kw), 1e-5, decode,
              (dq, kc, vc, pos))
        assert kernels.counters().get("fallback_vmem_bound", 0) \
            == vmem_refusals

    rows, c = (16, 128) if cfg.rehearse else (4096, 768)
    # LayerNorm parameters near their initial (1, 0)
    x, g, beta = randn(rows, c), 1.0 + 0.1 * randn(c), 0.1 * randn(c)
    norm = get_op("_fused_norm_act").fn
    # relu is exact, so it holds the kernel's normalise + affine to the
    # 1e-5 bound. tanh is not: on the chip XLA's tanh and Mosaic's are
    # both hardware approximations, each 4.4e-5 from a float64 reference
    # and 2.2e-5 from each other on this input (my chip run, PR 26), so
    # the twins are held to 1e-4 there.
    for act_op, act_type, tol in (("relu", None, 1e-5),
                                  ("activation", "tanh", 1e-4)):
        decision = cost_model.decide(
            "norm_act", 2, out_shape=(rows, c),
            backend=jax.default_backend(), act_type=act_type)
        if on_chip:  # the cost model itself must pick the kernel here
            assert (decision.fuse, decision.impl) == (True, "pallas"), \
                decision
        nkw = {"norm_kw": (), "act_op": act_op,
               "act_kw": (("act_type", act_type),) if act_type else ()}

        def norm_act(x, g, beta):
            return norm(x, g, beta, impl=cfg.pallas, **nkw)

        check(f"norm_act {act_type or act_op}", norm_act(x, g, beta),
              norm(x, g, beta, impl="lax", **nkw), tol, norm_act,
              (x, g, beta))

    # int8 fully-connected at the ResNet-50 head's width. auto must
    # resolve to the MXU's native int8 x int8 -> int32 contraction on
    # the chip. Both lowerings land on the int32 lattice: the dequant
    # one accumulates the same integer products in fp32, exact below
    # 2^24, so the documented tolerance is one lattice step.
    os.environ["MXNET_QUANTIZE_LOWERING"] = "auto"
    resolved = ops_quant.lowering()
    log(f"kernels: MXNET_QUANTIZE_LOWERING=auto resolved to {resolved!r}")
    assert resolved == ("native" if on_chip else "dequant"), resolved
    m, kdim, n = (8, 64, 16) if cfg.rehearse else (128, 2048, 1000)
    qd = jnp.asarray(rs.randint(-127, 128, (m, kdim)).astype("int8"))
    qw = jnp.asarray(rs.randint(-127, 128, (n, kdim)).astype("int8"))
    lo, hi = jnp.float32(-1.0), jnp.float32(1.0)
    qfc = get_op("_contrib_quantized_fully_connected").fn

    accs = {}
    for mode in ("auto", "dequant"):
        os.environ["MXNET_QUANTIZE_LOWERING"] = mode

        def fc(qd, qw):  # a new function per mode: lowering() is read
            # while tracing, and jit caches traces by function
            return qfc(qd, qw, lo, hi, lo, hi, num_hidden=n,
                       no_bias=True)[0]

        text = jax.jit(fc).lower(qd, qw).as_text()
        accs[mode] = jax.jit(fc)(qd, qw)
        int8_dot = "xi8>) -> tensor<%dx%dxi32>" % (m, n) in text
        if on_chip:
            assert int8_dot == (mode == "auto"), (mode, text[-2000:])
    os.environ["MXNET_QUANTIZE_LOWERING"] = "auto"
    step = int(jnp.abs(accs["auto"] - accs["dequant"]).max())
    log(f"kernels: quantized FC {m}x{kdim}x{n}, {resolved} vs dequant "
        f"max lattice distance {step}")
    assert accs["auto"].dtype == jnp.int32 and step <= 1, step
    assert int(jnp.abs(accs["auto"]).max()) > 0
    assert_on(accs.items(), cfg.platform, "quantized FC output")


def phase_masked(cfg):
    """The kernels of the mixture-of-experts steps against their plain
    twins: the flash forward and backward under ``BlockDiffusionMask``
    and under ``SlidingWindowMask`` with grouped heads, and the grouped
    matrix products. Float32 at ``highest`` to the tolerances
    ``phase_kernels`` holds the causal kernels to; then bfloat16 at the
    cells' own shapes, (2, 32 over 4, 8192, 128) and (16384, 2048) x
    (16, 2048, 1536), (16384, 768) x (16, 768, 2048), to bfloat16's
    rounding, and at 16,384 positions of one key/value head's group of 7,
    window and causal, where a head's dq is past one block and the
    backward walks it in segments; the causal kernels at a head size of
    256 in a group of 8, (1, 16 over 2, 8192, 256); and the gated delta
    rule's two kernels (``gdn_fwd``, ``gdn_bwd``) against their
    ``lax.scan`` twin, at (1, 32 over 16, 8192, 128). The kernels are
    forced (``use_pallas=True``): what the chip's compiler refuses fails
    here, nothing falls back."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import kernels
    from mxnet_tpu.kernels.flash_attention import (
        BlockDiffusionMask, SlidingWindowMask, flash_attention)
    from mxnet_tpu.kernels.gated_delta import gated_delta_rule
    from mxnet_tpu.kernels.grouped_matmul import grouped_matmul

    on_chip = not cfg.rehearse
    rs = onp.random.RandomState(SEED + 3)

    def randn(dtype, *shape):
        return jnp.asarray(rs.randn(*shape).astype("f"), dtype)

    def close(name, got, want, tol, scale=1.0):
        got, want = (jnp.asarray(a, jnp.float32) for a in (got, want))
        err = float(jnp.abs(got - want).max()) / scale
        log(f"masked: {name} max |pallas - plain| = {err:.3e} (tol {tol})")
        assert bool(jnp.isfinite(got).all()) and err < tol, (name, err)

    def attention(dtype, b, hq, hkv, size, d, tols, window=None):
        """Over ``size`` positions: causal within ``window`` positions (0:
        causal alone), or block diffusion over two halves by default."""
        mask = BlockDiffusionMask(size // 2, 4) if window is None else \
            SlidingWindowMask(size, window) if window else None
        q, do = (randn(dtype, b, hq, size, d) for _ in range(2))
        k, v = (randn(dtype, b, hkv, size, d) for _ in range(2))

        def run(pallas):
            def f(q, k, v, do):
                o, vjp = jax.vjp(lambda *a: flash_attention(
                    *a, mask=mask, causal=mask is None, use_pallas=pallas),
                    q, k, v)
                return (o,) + vjp(do)
            return jax.jit(f)

        scans = kernels.counters().get("flash_bwd_scan", 0)
        got = run(True)(q, k, v, do)
        assert kernels.counters().get("flash_bwd_scan", 0) == scans
        if on_chip:
            assert "tpu_custom_call" in run(True).lower(
                q, k, v, do).as_text()
        # the plain twin one query head at a time: its (S, S) scores in
        # float32 would not fit the chip for all heads at once
        group, plain = hq // hkv, run(False)
        f32 = jnp.float32
        want = [jnp.zeros(a.shape, f32) for a in (q, q, k, v)]
        for bi in range(b):
            for h in range(hq):
                kv = (slice(bi, bi + 1), slice(h // group, h // group + 1))
                qh = (slice(bi, bi + 1), slice(h, h + 1))
                o, dq, dk, dv = plain(q[qh].astype(f32), k[kv].astype(f32),
                                      v[kv].astype(f32), do[qh].astype(f32))
                want[0] = want[0].at[qh].set(o)
                want[1] = want[1].at[qh].set(dq)
                want[2] = want[2].at[kv].add(dk)
                want[3] = want[3].at[kv].add(dv)
        for name, g, w, tol in zip(("forward", "dq", "dk", "dv"), got, want,
                                   tols):
            close(f"flash {jnp.dtype(dtype).name} {(b, hq, hkv, size, d)}"
                  f" {mask or 'causal'} {name}", g, w, tol)

    def products(dtype, m, k, n, groups, tol):
        lhs, dout = randn(dtype, m, k), randn(dtype, m, n)
        rhs = randn(dtype, groups, k, n) * 0.05
        # uneven: an empty group, a large one, sizes off the tile, and
        # rows past the last group
        cut = onp.sort(rs.randint(0, m - m // 16, groups - 2))
        sizes = onp.diff(onp.concatenate([[0], cut, [m - m // 16]]))
        sizes = jnp.asarray(onp.concatenate([[0], sizes]), jnp.int32)

        def run(pallas):
            def f(lhs, rhs, dout):
                out, vjp = jax.vjp(lambda a, b: grouped_matmul(
                    a, b, sizes, use_pallas=pallas), lhs, rhs)
                return (out,) + vjp(dout)
            return jax.jit(f)

        got = run(True)(lhs, rhs, dout)
        if on_chip:
            assert "tpu_custom_call" in run(True).lower(
                lhs, rhs, dout).as_text()
        want = run(False)(lhs, rhs, dout)
        for name, g, w in zip(("product", "dlhs", "drhs"), got, want):
            close(f"grouped {jnp.dtype(dtype).name} ({m}, {k}) x "
                  f"({groups}, {k}, {n}) {name}", g, w, tol,
                  scale=float(jnp.abs(jnp.asarray(w, jnp.float32)).max()))

    def rule(dtype, b, hk, hv, size, d, tols):
        """The gated delta rule and its five gradients, kernels against
        the twin, each to ``tols`` of the twin's largest element."""
        unit = lambda a: a / jnp.linalg.norm(  # noqa: E731
            a, axis=-1, keepdims=True)
        q = (unit(randn(jnp.float32, b, hk, size, d)) * d ** -0.5) \
            .astype(dtype)
        k = unit(randn(jnp.float32, b, hk, size, d)).astype(dtype)
        v, do = (randn(dtype, b, hv, size, d) for _ in range(2))
        g = -jnp.abs(randn(jnp.float32, b, hv, size)) \
            * jnp.linspace(0.01, 16.0, hv)[None, :, None]
        beta = jax.nn.sigmoid(randn(jnp.float32, b, hv, size))

        def run(pallas):
            def f(q, k, v, g, beta, do):
                o, vjp = jax.vjp(lambda *a: gated_delta_rule(
                    *a, use_pallas=pallas), q, k, v, g, beta)
                return (o,) + vjp(do)
            return jax.jit(f)

        got = run(True)(q, k, v, g, beta, do)
        if on_chip:
            text = run(True).lower(q, k, v, g, beta, do).as_text()
            assert "gdn_fwd" in text and "gdn_bwd" in text
        want = run(False)(q, k, v, g, beta, do)
        for name, a, w, tol in zip(("forward", "dq", "dk", "dv", "dg",
                                    "dbeta"), got, want, tols):
            close(f"gated delta {jnp.dtype(dtype).name} "
                  f"{(b, hk, hv, size, d)} {name}", a, w, tol,
                  scale=float(jnp.abs(jnp.asarray(w, jnp.float32)).max()))

    with jax.default_matmul_precision("highest"):
        attention(jnp.float32, *((1, 4, 2, 256, 32) if cfg.rehearse
                                 else (1, 8, 2, 2048, 128)),
                  (1e-5, 1e-4, 1e-4, 1e-4))
        # a head size of 256, 8 query heads a key/value head, causal
        attention(jnp.float32, *((1, 8, 1, 256, 256) if cfg.rehearse
                                 else (1, 8, 1, 1024, 256)),
                  (1e-5, 1e-4, 1e-4, 1e-4), window=0)
        rule(jnp.float32, *((1, 1, 2, 192, 128) if cfg.rehearse
                            else (1, 2, 4, 1024, 128)), (1e-4,) * 6)
        attention(jnp.float32, *((1, 4, 2, 256, 32) if cfg.rehearse
                                 else (1, 14, 2, 2048, 128)),
                  (1e-5, 1e-4, 1e-4, 1e-4),
                  window=128 if cfg.rehearse else 512)
        products(jnp.float32, *((512, 128, 256, 4) if cfg.rehearse
                                else (4096, 768, 2048, 16)), 1e-5)
    if cfg.rehearse:
        return
    # bfloat16 carries 8 bits: results of size ~1 and gradients of size
    # ~10 round by up to 4e-3 and 4e-2 of a unit on either side
    attention(jnp.bfloat16, 2, 32, 4, 8192, 128, (2e-2, 1e-1, 2e-1, 2e-1))
    walked = kernels.counters().get("flash_bwd_q_segments", 0)
    for window in (4096, 0):    # the head past one dq block, in segments
        attention(jnp.bfloat16, 1, 7, 1, 16384, 128,
                  (2e-2, 1e-1, 2e-1, 2e-1), window=window)
    assert kernels.counters()["flash_bwd_q_segments"] >= walked + 2 * 2
    # the full layer of the cell qwen3next80b-train-s8192, and its rule
    attention(jnp.bfloat16, 1, 16, 2, 8192, 256, (2e-2, 1e-1, 2e-1, 2e-1),
              window=0)
    rule(jnp.bfloat16, 1, 16, 32, 8192, 128, (2e-2,) * 6)
    products(jnp.bfloat16, 16384, 2048, 1536, 16, 1e-2)
    products(jnp.bfloat16, 16384, 768, 2048, 16, 1e-2)
    counted = kernels.counters()
    assert counted.get("flash_mask_pallas", 0) > 0
    assert counted.get("moe_gmm_pallas", 0) > 0
    assert counted.get("gdn_pallas", 0) > 0


def phase_prologue(cfg):
    """The prologue of grouped-query attention (``kernels/qk_prologue.py``:
    the q/k norms, RoPE and the layout the flash kernels read) as its
    kernel pair against the plain twin: q, k, v, the gate, and the VJP to
    ``qkv`` and both gammas, each to ``tol`` of the twin's largest
    element. The four kinds of layer of the mixture cells, float32 at a
    small shape to rounding, then bfloat16 at the cells' own shapes to
    bfloat16's (the twin rounds after the norm and after the rotation,
    the kernels once): norm and RoPE at positions i mod 4096 over (2,
    8192) of 32 over 4 heads of 128; RoPE alone, and neither, over (1,
    16384) of 28 over 4; norm, RoPE on 64 of 256 lanes and the output
    gate over (1, 8192) of 16 over 2. The kernels are forced
    (``use_pallas=True``)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import kernels
    from mxnet_tpu.kernels.qk_prologue import qk_prologue

    on_chip = not cfg.rehearse
    rs = onp.random.RandomState(SEED + 5)

    def randn(dtype, *shape):
        return jnp.asarray(rs.randn(*shape).astype("f"), dtype)

    def check(dtype, b, s, h, hkv, d, norm, rope, rot, period, gated, tol):
        qkv = randn(dtype, b, s, ((2 if gated else 1) * h + 2 * hkv) * d)
        gammas = tuple(1 + 0.5 * randn(jnp.float32, d).astype(dtype)
                       for _ in range(2 * norm))
        kw = dict(heads=h, kv_heads=hkv, head_dim=d, rope=rope,
                  rotary_dim=rot, rope_theta=1e6, period=period,
                  output_gate=gated)

        def run(pallas):
            def f(qkv, cot, *gammas):
                out, vjp = jax.vjp(lambda *a: qk_prologue(
                    a[0], *(a[1:] or (None, None)), use_pallas=pallas,
                    **kw), qkv, *gammas)
                return out + vjp(cot)
            return jax.jit(f)

        shapes = jax.eval_shape(lambda *a: qk_prologue(
            a[0], *(a[1:] or (None, None)), **kw), qkv, *gammas)
        cot = tuple(None if a is None else randn(a.dtype, *a.shape)
                    for a in shapes)
        got = run(True)(qkv, cot, *gammas)
        if on_chip:
            assert "tpu_custom_call" in run(True).lower(
                qkv, cot, *gammas).as_text()
        want = run(False)(qkv, cot, *gammas)
        names = ("q", "k", "v", "gate", "dqkv", "dgamma_q", "dgamma_k")
        for name, a, w in zip(names, got, want):
            if w is None:
                continue
            a, w = (jnp.asarray(x, jnp.float32) for x in (a, w))
            err = float(jnp.abs(a - w).max() / jnp.abs(w).max())
            log(f"prologue {jnp.dtype(dtype).name} {(b, s, h, hkv, d)} "
                f"norm={norm} rope={rope}/{rot} gated={gated} {name} max "
                f"|pallas - plain| = {err:.3e} of the largest (tol {tol})")
            assert bool(jnp.isfinite(a).all()) and err < tol, (name, err)

    kinds = [(True, True, None, True, False), (False, True, None, False,
                                               False),
             (False, False, None, False, False)]
    for norm, rope, rot, half, gated in kinds:
        check(jnp.float32, 2, 384, 4, 2, 128, norm, rope, rot,
              192 if half else 384, gated, 1e-5)
    check(jnp.float32, 1, 384, 4, 2, 256, True, True, 64, 384, True, 1e-5)
    if cfg.rehearse:
        return
    check(jnp.bfloat16, 2, 8192, 32, 4, 128, True, True, None, 4096, False,
          2e-2)
    for rope in (True, False):
        check(jnp.bfloat16, 1, 16384, 28, 4, 128, False, rope, None, 16384,
              False, 2e-2)
    check(jnp.bfloat16, 1, 8192, 16, 2, 256, True, True, 64, 8192, True,
          2e-2)
    assert kernels.counters().get("qk_prologue_pallas", 0) > 0


def phase_delta_prologue(cfg):
    """The prologue of a Gated DeltaNet layer (``kernels/delta_prologue.py``:
    the causal convolution, SiLU, the l2 norms and the layout the delta
    rule reads) as its kernel pair against the plain twin: q, k, v and
    the VJP to ``qkvz`` and the convolution's weight, each to ``tol`` of
    the twin's largest element; float32 at a small shape over three
    tiles of positions to rounding, then bfloat16 over (1, 8192) of 16
    key and 32 value heads of 128, the Qwen3-Next cell's linear layer,
    to bfloat16's (the twin's backward rounds each tap's cotangent, the
    kernels once). The kernels are forced (``use_pallas=True``)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import kernels
    from mxnet_tpu.kernels.delta_prologue import delta_prologue

    on_chip = not cfg.rehearse
    rs = onp.random.RandomState(SEED + 6)

    def randn(dtype, *shape):
        return jnp.asarray(rs.randn(*shape).astype("f"), dtype)

    def check(dtype, b, s, hk, hv, d, tol):
        qkvz = randn(dtype, b, s, 2 * (hk + hv) * d)
        conv_w = 0.5 * randn(jnp.float32, 4, (2 * hk + hv) * d).astype(dtype)
        cot = (randn(dtype, b, hk, s, d), randn(dtype, b, hk, s, d),
               randn(dtype, b, hv, s, d))

        def run(pallas):
            def f(qkvz, conv_w, cot):
                out, vjp = jax.vjp(lambda *a: delta_prologue(
                    *a, hk, hv, d, d, use_pallas=pallas), qkvz, conv_w)
                return out + vjp(cot)
            return jax.jit(f)

        got = run(True)(qkvz, conv_w, cot)
        if on_chip:
            assert "tpu_custom_call" in run(True).lower(
                qkvz, conv_w, cot).as_text()
        want = run(False)(qkvz, conv_w, cot)
        for name, a, w in zip(("q", "k", "v", "dqkvz", "dconv_w"), got,
                              want):
            a, w = (jnp.asarray(x, jnp.float32) for x in (a, w))
            err = float(jnp.abs(a - w).max() / jnp.abs(w).max())
            log(f"delta prologue {jnp.dtype(dtype).name} "
                f"{(b, s, hk, hv, d)} {name} max |pallas - plain| = "
                f"{err:.3e} of the largest (tol {tol})")
            assert bool(jnp.isfinite(a).all()) and err < tol, (name, err)

    check(jnp.float32, 2, 384, 2, 4, 128, 1e-5)
    if cfg.rehearse:
        return
    check(jnp.bfloat16, 1, 8192, 16, 32, 128, 2e-2)
    assert kernels.counters().get("delta_prologue_pallas", 0) > 0


def phase_short_conv(cfg):
    """LFM2's gated short convolution (``kernels/short_conv.py``: the
    gates and the causal depthwise convolution between the B|C|x and
    the output projections) as its kernel pair against the plain twin: y
    and the VJP to ``bcx`` and the convolution's weight, each to ``tol``
    of the twin's largest element; float32 at a small shape over three
    tiles of positions to rounding, then bfloat16 at (1, 8192) of 2,048
    channels, the LFM2 cell's conv layer, to bfloat16's. There both are
    timed alone (host clock, 20 calls back to back, forward and forward
    with the VJP). The kernels are forced (``use_pallas=True``)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import kernels
    from mxnet_tpu.kernels.short_conv import short_conv

    on_chip = not cfg.rehearse
    rs = onp.random.RandomState(SEED + 7)

    def randn(dtype, *shape):
        return jnp.asarray(rs.randn(*shape).astype("f"), dtype)

    def run(pallas, with_vjp=True):
        def f(bcx, conv_w, dy):
            y, vjp = jax.vjp(lambda *a: short_conv(*a, use_pallas=pallas),
                             bcx, conv_w)
            return (y,) + vjp(dy) if with_vjp else (y,)
        return jax.jit(f)

    def check(dtype, b, s, e, tol):
        bcx = randn(dtype, b, s, 3 * e)
        conv_w = 0.5 * randn(jnp.float32, 3, e).astype(dtype)
        dy = randn(dtype, b, s, e)
        got = run(True)(bcx, conv_w, dy)
        if on_chip:
            assert "tpu_custom_call" in run(True).lower(
                bcx, conv_w, dy).as_text()
        want = run(False)(bcx, conv_w, dy)
        for name, a, w in zip(("y", "dbcx", "dconv_w"), got, want):
            a, w = (jnp.asarray(x, jnp.float32) for x in (a, w))
            err = float(jnp.abs(a - w).max() / jnp.abs(w).max())
            log(f"short conv {jnp.dtype(dtype).name} {(b, s, e)} {name} "
                f"max |pallas - plain| = {err:.3e} of the largest "
                f"(tol {tol})")
            assert bool(jnp.isfinite(a).all()) and err < tol, (name, err)
        return bcx, conv_w, dy

    check(jnp.float32, 2, 384, 256, 1e-5)
    if cfg.rehearse:
        return
    args = check(jnp.bfloat16, 1, 8192, 2048, 2e-2)
    assert kernels.counters().get("short_conv_pallas", 0) > 0
    for pallas in (True, False):
        for with_vjp in (False, True):
            fn = run(pallas, with_vjp)
            jax.block_until_ready(fn(*args))
            t0 = time.perf_counter()
            for _ in range(20):
                out = fn(*args)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / 20 * 1e3
            log(f"short conv alone, {'kernels' if pallas else 'twin'}, "
                f"{'forward + VJP' if with_vjp else 'forward'}: "
                f"{ms:.3f} ms a call")


def phase_ssm_scan(cfg):
    """The selective scan of a Mamba layer (``kernels/selective_scan.py``)
    as its kernel pair against the ``lax.scan`` twin: g and the VJP to all
    eight inputs, each to ``tol`` of the twin's largest element; float32
    at a small shape over three chunks of two channel tiles to rounding,
    then bfloat16 u, dt, z, B, C at (1, 8192) of 5,120 channels and 16
    states, the Phi-4-mini-flash cell's Mamba layer, to bfloat16's. There
    both are timed alone (host clock, 20 calls back to back, forward and
    forward with the VJP). The kernels are forced (``use_pallas=True``)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import kernels
    from mxnet_tpu.kernels.selective_scan import selective_scan

    on_chip = not cfg.rehearse
    rs = onp.random.RandomState(SEED + 8)
    names = ("g", "du", "ddt", "dA", "dB", "dC", "dD", "dz", "ddt_bias")

    def inputs(dtype, b, s, ch, n):
        """delta = softplus(dt + bias) in [1e-3, 0.1], A = -(1..n) a
        channel, as a Mamba layer's initialiser draws them."""
        def randn(*shape):
            return rs.randn(*shape).astype("f")
        delta = onp.exp(rs.uniform(onp.log(1e-3), onp.log(0.1),
                                   (b, s, ch))).astype("f")
        bias = 0.5 * randn(ch)
        dt = onp.log(onp.expm1(delta)) - bias
        a = -onp.arange(1, n + 1, dtype="f") * onp.exp(0.1 * randn(ch, n))
        low = (randn(b, s, ch), dt, a, randn(b, s, n), randn(b, s, n),
               1.0 + 0.1 * randn(ch), randn(b, s, ch), bias)
        cast = (dtype, dtype, jnp.float32, dtype, dtype, jnp.float32,
                dtype, jnp.float32)
        return tuple(jnp.asarray(x, t) for x, t in zip(low, cast)), \
            jnp.asarray(randn(b, s, ch), dtype)

    def run(pallas, with_vjp=True):
        def f(args, dg):
            g, vjp = jax.vjp(lambda *a: selective_scan(
                *a, use_pallas=pallas), *args)
            return (g,) + vjp(dg) if with_vjp else (g,)
        return jax.jit(f)

    def check(dtype, b, s, ch, n, tol):
        args, dg = inputs(dtype, b, s, ch, n)
        got = run(True)(args, dg)
        if on_chip:
            assert "tpu_custom_call" in run(True).lower(args, dg).as_text()
        want = run(False)(args, dg)
        for name, a, w in zip(names, got, want):
            a, w = (jnp.asarray(x, jnp.float32) for x in (a, w))
            err = float(jnp.abs(a - w).max() / jnp.abs(w).max())
            log(f"ssm scan {jnp.dtype(dtype).name} {(b, s, ch, n)} {name} "
                f"max |pallas - plain| = {err:.3e} of the largest "
                f"(tol {tol})")
            assert bool(jnp.isfinite(a).all()) and err < tol, (name, err)
        return args, dg

    check(jnp.float32, 1, 768, 512, 16, 1e-5)
    if cfg.rehearse:
        return
    args = check(jnp.bfloat16, 1, 8192, 5120, 16, 2e-2)
    assert kernels.counters().get("ssm_scan_pallas", 0) > 0
    for pallas in (True, False):
        for with_vjp in (False, True):
            fn = run(pallas, with_vjp)
            jax.block_until_ready(fn(*args))
            t0 = time.perf_counter()
            for _ in range(20):
                out = fn(*args)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / 20 * 1e3
            log(f"ssm scan alone, {'kernels' if pallas else 'twin'}, "
                f"{'forward + VJP' if with_vjp else 'forward'}: "
                f"{ms:.3f} ms a call")


def phase_dp4(cfg):
    """Data-parallel training over four chips against the same steps on
    one: same seed, same global batch.

    The comparison needs well-conditioned dynamics. At the train phase's
    lr 0.05 the first steps from a random init are not: on four chips
    the traces started 1.5e-3 apart (bf16 reduction order) and were
    1.1e-1 apart by step 3 (my chip run, PR 26). At lr 0.005 a rounding
    difference stays a rounding difference, so a wrong gradient sum or a
    per-shard BatchNorm would stand out."""
    import jax

    from mxnet_tpu import parallel

    devs = jax.devices()
    assert len(devs) >= 4, f"--chips 4 needs four devices, jax has {devs}"
    x, y = train_batch(cfg)
    traces = {}
    for name, n in (("one device", 1), ("dp=4", 4)):
        mesh = parallel.make_mesh({"dp": n}, devices=devs[:n])
        trainer = build_trainer(cfg, mesh, lr=0.005)
        losses, secs, later = run_steps(trainer, x, y, steps=3)
        log(f"dp4: {name}: build+compile+first step {secs[0]:.1f}s, "
            f"loss trace {[round(v, 4) for v in losses]}")
        assert all(onp.isfinite(losses)) and later == 0, (losses, later)
        traces[name] = losses
    # `trainer` is now the dp=4 one
    params = trainer.param_arrays()
    assert_on(params.items(), cfg.platform, "parameter")
    for name, a in params.items():
        assert set(a.devices()) == set(devs[:4]), \
            f"parameter {name} is on {a.devices()}, not on all four"
    xd = parallel.shard_batch(x, trainer.mesh).data
    shards = sorted((s.device.id, s.data.shape[0])
                    for s in xd.addressable_shards)
    assert len(shards) == 4 and \
        all(rows == cfg.train_batch // 4 for _, rows in shards), shards
    hlo = trainer.step_hlo(x, y)
    assert "all-reduce" in hlo, "no all-reduce in the dp=4 step"
    # bf16 compute: the four-way split changes the order of the batch
    # reductions (BN statistics, gradient sums), nothing else
    rel = max(abs(a - b) / abs(a)
              for a, b in zip(traces["one device"], traces["dp=4"]))
    log(f"dp4: {len(params)} parameters on all four devices, batch split "
        f"{shards}, all-reduce in the step, worst relative loss "
        f"difference {rel:.3e}")
    cfg.expect(rel < 2e-2, f"loss traces agree within 2e-2: {traces}")


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="walk the control flow on the CPU at tiny "
                         "size; prints no result line")
    ap.add_argument("--phases", default="",
                    help="run only these phases, comma-separated")
    args = ap.parse_args(argv)
    cfg = Cfg(args.rehearse)

    import jax

    import mxnet_tpu  # noqa: F401 — fails here in a bare directory
    from mxnet_tpu import _native
    from mxnet_tpu.utils import compile_cache as cc

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"jax {jax.__version__}, device {device}")
    if dev.platform != cfg.platform:
        print(f"chip_smoke: jax's first device is {dev.platform!r}, not "
              f"{cfg.platform!r}; refusing to run", file=sys.stderr)
        return 1
    libs = {n: getattr(_native, n, None) is not None
            for n in ("lib", "englib", "textlib")}
    log("native runtime: " + ", ".join(
        f"{n}={'built' if ok else 'pure-Python fallback'}"
        for n, ok in libs.items()))
    log(f"jax compile cache: {cc.jax_cache_dir()}")

    one_chip = {"train": phase_train, "serve": phase_serve,
                "kernels": phase_kernels, "masked": phase_masked,
                "prologue": phase_prologue,
                "delta_prologue": phase_delta_prologue,
                "short_conv": phase_short_conv,
                "ssm_scan": phase_ssm_scan}
    todo = {"dp4": phase_dp4} if args.chips == 4 else one_chip
    if args.phases:
        todo = {n: todo[n] for n in args.phases.split(",")}
    for name, phase in todo.items():
        t0 = time.perf_counter()
        log(f"phase {name} ...")
        phase(cfg)
        log(f"phase {name} passed in {time.perf_counter() - t0:.1f}s")
    if args.rehearse:
        log("passed — a rehearsal, not the chip check: no result line")
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
