"""Benchmark: ResNet-50 training throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Baseline: reference MXNet ResNet-50 training, fp32 batch 128 on 1x V100 =
363.69 img/s (BASELINE.md, docs perf.md:243-254). The full training step
(forward, backward, SGD+momentum update, BN stats) is ONE donated XLA
executable built by mxnet_tpu.parallel.SPMDTrainer over a 1-device mesh.

One process per chip: the measurement runs in THIS process (a parent
that touched JAX would hold the chip against its own child). It fails
with a non-zero exit when JAX reports no TPU — a CPU number is never
printed under a device metric's name — unless BENCH_SMOKE=1 asks for the
tiny-shape CPU plumbing check.

Env knobs:
  BENCH_BATCH   (default 128; halved on OOM)
  BENCH_SMOKE=1 tiny-shape CPU smoke for plumbing checks
  BENCH_MODE    io | rawjax | profile (see the *_main functions)
"""
from __future__ import annotations

import json
import os
import sys
import time

BASELINE_IMGS_PER_SEC = 363.69  # reference fp32 training, 1xV100
SCORE_V100_FP32 = 1233.15  # scoring, fp32 b128 (perf.md:187-197)
SCORE_V100_FP16 = 2355.04  # scoring, fp16 b128 (perf.md:199-215)
# the reference publishes no fp16 TRAINING number; its fp16/fp32 scoring
# ratio (perf.md:187-215) applied to the fp32 training baseline is the
# fairest half-precision comparison point
BASELINE_FP16_EST = BASELINE_IMGS_PER_SEC * SCORE_V100_FP16 / SCORE_V100_FP32
# ResNet-50 fwd = 4.089 GFLOP/img at 224x224 (2 FLOPs/MAC); training
# fwd+bwd ~ 3x fwd
TRAIN_GFLOPS_PER_IMG = 3 * 4.089
# bf16 MXU peak per chip by device_kind (TFLOP/s)
PEAK_TFLOPS = {"TPU v4": 275, "TPU v5": 459, "TPU v5p": 459,
               "TPU v5 lite": 197, "TPU v5e": 197,
               "TPU v6 lite": 918, "TPU v6e": 918}
SMOKE = os.environ.get("BENCH_SMOKE") == "1"


from _cpu_platform import force_cpu_platform


LAYOUT = os.environ.get("BENCH_LAYOUT", "NHWC")  # NHWC = TPU-preferred


def build_trainer(mesh, classes=1000, dtype=None, layout=None):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu import parallel

    mx.random.seed(0)
    # MLPerf-style space-to-depth stem: bit-equivalent to the 7x7/2 conv
    # (tests/test_s2d_stem.py) but MXU-friendly; BENCH_STEM_S2D=0 reverts
    net = vision.resnet50_v1(
        classes=classes, layout=layout or LAYOUT,
        stem_s2d=os.environ.get("BENCH_STEM_S2D", "1") == "1")
    net.initialize(mx.init.Xavier())
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    return parallel.SPMDTrainer(
        net, loss, optimizer="sgd",
        optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
        mesh=mesh, compute_dtype=dtype)


def setup_train(batch, image_size, classes, dtype=None):
    """One-chip trainer + synthetic batch — shared by the timed run and
    the profile capture so both measure the identical program."""
    import jax
    import numpy as onp

    from mxnet_tpu import nd, parallel

    mesh = parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = build_trainer(mesh, classes, dtype=dtype)
    rng = onp.random.RandomState(0)
    shape = ((batch, image_size, image_size, 3) if LAYOUT == "NHWC"
             else (batch, 3, image_size, image_size))
    x = nd.array(rng.rand(*shape).astype("f"))
    y = nd.array(rng.randint(0, classes, batch).astype("f"))
    return trainer, x, y


def run(batch, image_size, classes, warmup=2, iters=8, dtype=None):
    import jax

    trainer, x, y = setup_train(batch, image_size, classes, dtype)
    # sync through a host readback of the scalar loss: the window ends
    # only when the last step's result has reached the host
    for _ in range(warmup):
        lval = trainer.step(x, y)
    _ = jax.device_get(lval.data)
    t0 = time.perf_counter()
    for _ in range(iters):
        lval = trainer.step(x, y)
    loss_val = float(jax.device_get(lval.data))
    dt = time.perf_counter() - t0
    return batch * iters / dt, loss_val


def build_scoring(image_size=224):
    """Build the scoring net ONCE (deferred-init forward on the host
    CPU device) and stage params on the device; run_scoring reuses it
    across dtypes."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon.model_zoo import vision
    import numpy as onp

    mx.random.seed(0)
    net = vision.resnet50_v1(layout=LAYOUT)
    cpu = jax.devices("cpu")[0]
    shape = ((1, image_size, image_size, 3) if LAYOUT == "NHWC"
             else (1, 3, image_size, image_size))
    with jax.default_device(cpu):  # op-by-op init stays off the chip
        net.initialize(mx.init.Xavier())
        with autograd.pause(train_mode=False):
            net.forward(mx.nd.array(onp.zeros(shape, "f")))
    params = [p for _, p in sorted(net.collect_params().items())]
    pnds = [p._ndarray for p in params]
    dev = jax.devices()[0]
    vals = [jax.device_put(p._ndarray.data, dev) for p in params]
    return net, pnds, vals, shape


def run_scoring(batch, built, dtype=None, iters=30):
    """Inference ("scoring") throughput: the whole measurement is ONE
    jitted fori_loop whose carry threads an epsilon of each output back
    into the input, so there is no per-iteration host dispatch and
    XLA cannot collapse identical iterations. Reference comparison:
    perf.md:187-215 V100 scoring table."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu import autograd
    from mxnet_tpu.ndarray import NDArray
    import numpy as onp

    net, pnds, vals, shape = built
    dev = jax.devices()[0]
    cdtype = jnp.dtype(dtype) if dtype else None

    def fwd(pv, x):
        saved = [p._data for p in pnds]
        try:
            for p, v in zip(pnds, pv):
                if cdtype is not None and \
                        jnp.issubdtype(v.dtype, jnp.floating):
                    v = v.astype(cdtype)
                p._data = v
            xin = x.astype(cdtype) if cdtype is not None else x
            with autograd.pause(train_mode=False):
                out = net.forward(NDArray(xin))
            return out.data.astype(jnp.float32)
        finally:
            for p, v in zip(pnds, saved):
                p._data = v

    def loop(pv, x):
        def body(i, carry):
            xc, acc = carry
            o = fwd(pv, xc)
            s = jnp.sum(o)
            return xc + (1e-30 * s).astype(xc.dtype), acc + s

        return lax.fori_loop(0, iters, body, (x, jnp.float32(0)))

    rng = onp.random.RandomState(0)
    bshape = (batch,) + shape[1:]
    x = jax.device_put(jnp.asarray(rng.rand(*bshape).astype("f")), dev)
    jloop = jax.jit(loop)
    _, acc = jloop(vals, x)  # compile + run once
    _ = jax.device_get(acc)
    t0 = time.perf_counter()
    _, acc = jloop(vals, x)
    _ = jax.device_get(acc)
    dt = time.perf_counter() - t0
    return batch * iters / dt


def _score_with_descent(batch, built, dtype):
    """OOM-halving like the training phases."""
    while batch >= 16:
        try:
            return run_scoring(batch, built, dtype=dtype), batch
        except RuntimeError as e:
            if "RESOURCE_EXHAUSTED" in str(e) or "Out of memory" in str(e):
                batch //= 2
                continue
            raise
    raise RuntimeError("scoring failed at batch>=16")


def mfu_pct(imgs_per_sec):
    """Sustained training FLOP/s as % of the chip's bf16 MXU peak. A
    device that is not in ``PEAK_TFLOPS`` is an error, not a default."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in PEAK_TFLOPS:
        raise KeyError(f"no bf16 peak on record for device_kind {kind!r}; "
                       "add it to PEAK_TFLOPS with its source")
    return round(100.0 * imgs_per_sec * TRAIN_GFLOPS_PER_IMG
                 / (PEAK_TFLOPS[kind] * 1000.0), 2)


def measure_main():
    """The default mode: fp32 then bf16 training throughput, then
    scoring, on the one chip this process holds."""
    def measure(tag, batch, dtype):
        """OOM-halving descent; returns (imgs/s, batch) or raises
        RuntimeError (NOT SystemExit — the bf16 phase's failure must be
        catchable so a measured fp32 result still gets printed)."""
        last_err = None
        while batch >= 16:
            try:
                imgs, _ = run(batch=batch, image_size=224, classes=1000,
                              dtype=dtype)
                return imgs, batch
            except RuntimeError as e:  # OOM → halve the batch
                last_err = e
                if "RESOURCE_EXHAUSTED" in str(e) or \
                        "Out of memory" in str(e):
                    batch //= 2
                    continue
                raise
        raise RuntimeError(f"bench {tag} failed at batch>=16: {last_err}")

    fp32_batch = int(os.environ.get("BENCH_BATCH", "128"))
    # bf16 halves activation memory — start the descent high: bigger
    # batches keep the MXU fed (the OOM-halving loop finds the ceiling)
    bf16_batch = int(os.environ.get("BENCH_BF16_BATCH", "512"))
    imgs32, b32 = measure("fp32", fp32_batch, None)
    extra = {"fp32_imgs_per_sec": round(imgs32, 2), "fp32_batch": b32,
             "fp32_vs_v100_fp32_train": round(
                 imgs32 / BASELINE_IMGS_PER_SEC, 3)}
    extra["fp32_mfu_pct_of_bf16_peak"] = mfu_pct(imgs32)
    try:
        imgs16, b16 = measure("bf16", bf16_batch, "bfloat16")
    except Exception as e:
        print(f"[bench] bf16 phase failed: {e}", file=sys.stderr)
        imgs16 = None
    if imgs16 is not None:
        extra["bf16_mfu_pct_of_bf16_peak"] = mfu_pct(imgs16)
        extra["bf16_vs_v100_fp16_train_est"] = round(
            imgs16 / BASELINE_FP16_EST, 3)
        extra["bf16_speedup_over_fp32"] = round(imgs16 / imgs32, 3)
        result = {
            "metric": f"resnet50_train_imgs_per_sec_bf16_b{b16}",
            "value": round(imgs16, 2), "unit": "img/s",
            "vs_baseline": round(imgs16 / BASELINE_IMGS_PER_SEC, 3),
            "extra": extra}
    else:
        result = {
            "metric": f"resnet50_train_imgs_per_sec_fp32_b{b32}",
            "value": round(imgs32, 2), "unit": "img/s",
            "vs_baseline": round(imgs32 / BASELINE_IMGS_PER_SEC, 3),
            "extra": extra}
    # training results are printed NOW — a scoring failure can no
    # longer discard them (readers take the LAST metric line)
    print(json.dumps(result), flush=True)
    # inference scoring vs the reference's V100 table (perf.md:187-215);
    # per-dtype try so an fp32 failure doesn't take bf16 down with it
    try:
        built = build_scoring()
    except Exception as e:
        print(f"[bench] scoring build failed: {e}", file=sys.stderr)
        built = None
    if built is not None:
        for tag, dt_, base, base_name in (
                ("fp32", None, SCORE_V100_FP32, "v100"),
                ("bf16", "bfloat16", SCORE_V100_FP16, "v100_fp16")):
            try:
                sc, sb = _score_with_descent(128, built, dt_)
                extra[f"score_{tag}_imgs_per_sec_b{sb}"] = round(sc, 2)
                extra[f"score_{tag}_vs_{base_name}"] = round(sc / base, 3)
            except Exception as e:
                print(f"[bench] {tag} scoring failed: {e}",
                      file=sys.stderr)
        result["extra"] = extra
        print(json.dumps(result), flush=True)


def smoke_main():
    force_cpu_platform()
    imgs, _ = run(batch=4, image_size=32, classes=10, warmup=1, iters=2)
    print(json.dumps({"metric": "resnet50_train_smoke",
                      "value": round(imgs, 2), "unit": "img/s",
                      "vs_baseline": 0.0}))


def profile_main():
    """BENCH_MODE=profile: capture an XPlane trace of a few training
    steps for the MFU breakdown (the VERDICT's 'profile a step and
    attack the top time sinks' loop). Writes to BENCH_PROFILE_DIR
    (default ./bench_profile) — open in TensorBoard/Perfetto, or read
    the top self-time ops from the .trace.json.gz inside."""
    import jax

    outdir = os.environ.get("BENCH_PROFILE_DIR", "bench_profile")
    batch = int(os.environ.get("BENCH_BATCH", "128"))
    dtype = os.environ.get("BENCH_PROFILE_DTYPE", "bfloat16")
    image_size = int(os.environ.get("BENCH_PROFILE_IMAGE", "224"))
    trainer, x, y = setup_train(batch, image_size, 1000, dtype)
    lval = trainer.step(x, y)  # compile OUTSIDE the trace
    _ = jax.device_get(lval.data)
    with jax.profiler.trace(outdir):
        for _ in range(int(os.environ.get("BENCH_PROFILE_STEPS", "5"))):
            lval = trainer.step(x, y)
        _ = jax.device_get(lval.data)
    # fold the top self-time table straight into the artifact so one
    # command yields the attack-the-sinks breakdown
    top = []
    try:
        from mxnet_tpu.tools import trace_top

        trace = trace_top.find_trace(outdir)
        events = trace_top.device_op_events(trace_top.load_events(trace))
        tot, cnt = trace_top.summarize(events)
        grand = sum(tot.values()) or 1
        top = [{"op": k, "self_ms": round(us / 1e3, 3),
                "pct": round(100.0 * us / grand, 2), "count": cnt[k]}
               for k, us in tot.most_common(12)]
    except Exception as e:  # trace parse must not discard the capture
        print(f"[bench] trace summary failed: {e}", file=sys.stderr)
    print(json.dumps({
        "metric": "profile_trace_written", "value": 1.0, "unit": "trace",
        "vs_baseline": 0.0,
        "extra": {"dir": os.path.abspath(outdir), "batch": batch,
                  "dtype": dtype,
                  "device": jax.devices()[0].device_kind,
                  "top_self_time": top}}))


def rawjax_main():
    """BENCH_MODE=rawjax: a hand-written ResNet-50 bf16 training step in
    bare JAX (no framework) — the platform ceiling for this model+chip.
    Comparing its img/s against the default bench isolates framework
    overhead from XLA/hardware limits."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    import numpy as onp

    batch = int(os.environ.get("BENCH_BATCH", "512"))
    rng = onp.random.RandomState(0)
    cdt = jnp.bfloat16

    # ---- parameters (fp32 masters), NHWC, bottleneck v1 ----
    params = {}

    def conv_p(name, cin, cout, k):
        params[name + ":w"] = jnp.asarray(
            rng.randn(cout, k, k, cin).astype("f") * (2.0 / (k * k * cin)) ** 0.5)

    def bn_p(name, c):
        params[name + ":g"] = jnp.ones((c,), jnp.float32)
        params[name + ":b"] = jnp.zeros((c,), jnp.float32)

    stages = [(64, 256, 3), (128, 512, 4), (256, 1024, 6), (512, 2048, 3)]
    conv_p("stem", 3, 64, 7)
    bn_p("stem", 64)
    cin = 64
    for si, (mid, out, n) in enumerate(stages):
        for bi in range(n):
            pre = f"s{si}b{bi}"
            conv_p(pre + "c1", cin, mid, 1)
            bn_p(pre + "c1", mid)
            conv_p(pre + "c2", mid, mid, 3)
            bn_p(pre + "c2", mid)
            conv_p(pre + "c3", mid, out, 1)
            bn_p(pre + "c3", out)
            if bi == 0:
                conv_p(pre + "ds", cin, out, 1)
                bn_p(pre + "ds", out)
            cin = out
    params["fc:w"] = jnp.asarray(rng.randn(2048, 1000).astype("f") * 0.02)
    params["fc:b"] = jnp.zeros((1000,), jnp.float32)

    def conv(x, w, stride=1):
        return lax.conv_general_dilated(
            x, jnp.transpose(w, (1, 2, 3, 0)).astype(cdt),
            (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def bn_relu(x, g, b, relu=True):
        xf = x.astype(jnp.float32)
        m = jnp.mean(xf, axis=(0, 1, 2))
        v = jnp.var(xf, axis=(0, 1, 2))
        y = (xf - m) * lax.rsqrt(v + 1e-5) * g + b
        if relu:
            y = jnp.maximum(y, 0.0)
        return y.astype(cdt)

    def fwd(p, x, y):
        h = conv(x, p["stem:w"], 2)
        h = bn_relu(h, p["stem:g"], p["stem:b"])
        h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
        for si, (mid, out, n) in enumerate(stages):
            for bi in range(n):
                pre = f"s{si}b{bi}"
                stride = 2 if (bi == 0 and si > 0) else 1
                r = h
                h2 = bn_relu(conv(h, p[pre + "c1:w"], stride),
                             p[pre + "c1:g"], p[pre + "c1:b"])
                h2 = bn_relu(conv(h2, p[pre + "c2:w"]),
                             p[pre + "c2:g"], p[pre + "c2:b"])
                h2 = bn_relu(conv(h2, p[pre + "c3:w"]),
                             p[pre + "c3:g"], p[pre + "c3:b"], relu=False)
                if bi == 0:
                    r = bn_relu(conv(r, p[pre + "ds:w"], stride),
                                p[pre + "ds:g"], p[pre + "ds:b"],
                                relu=False)
                h = jnp.maximum(h2 + r, 0.0).astype(cdt)
        h = jnp.mean(h.astype(jnp.float32), axis=(1, 2))
        logits = h @ p["fc:w"] + p["fc:b"]
        logp = jax.nn.log_softmax(logits)
        oh = jax.nn.one_hot(y, 1000)
        return -jnp.mean(jnp.sum(logp * oh, axis=-1))

    def step(p, mom, x, y):
        loss, g = jax.value_and_grad(fwd)(p, x, y)
        mom = {k: 0.9 * mom[k] - 0.05 * g[k] for k in p}
        p = {k: p[k] + mom[k] for k in p}
        return loss, p, mom

    jstep = jax.jit(step, donate_argnums=(0, 1))
    mom = {k: jnp.zeros_like(v) for k, v in params.items()}
    x = jnp.asarray(rng.rand(batch, 224, 224, 3).astype("f")).astype(cdt)
    y = jnp.asarray(rng.randint(0, 1000, batch))
    loss, params, mom = jstep(params, mom, x, y)
    _ = jax.device_get(loss)
    iters = 8
    t0 = time.perf_counter()
    for _ in range(iters):
        loss, params, mom = jstep(params, mom, x, y)
    lv = float(jax.device_get(loss))
    dt = time.perf_counter() - t0
    imgs = batch * iters / dt
    print(json.dumps({
        "metric": "rawjax_resnet50_train_imgs_per_sec_bf16",
        "value": round(imgs, 2), "unit": "img/s",
        "vs_baseline": round(imgs / BASELINE_IMGS_PER_SEC, 3),
        "extra": {"batch": batch, "loss": round(lv, 3),
                  "mfu_pct": mfu_pct(imgs),
                  "note": "no-framework ceiling for the same model"}}))


def io_main():
    """BENCH_MODE=io: input-pipeline throughput — synthetic ImageNet-ish
    .rec -> ImageRecordIter decode + random-crop/mirror + batch, host
    only (no TPU). The number to beat is the chip's consumption rate
    from the training bench (reference: iter_image_recordio_2.cc is
    sized to feed multiple GPUs)."""
    import tempfile

    force_cpu_platform()  # host-only bench: jnp math (normalize) on CPU
    import numpy as onp

    from mxnet_tpu import io as mxio, recordio

    n = int(os.environ.get("BENCH_IO_IMAGES", "1024"))
    batch = int(os.environ.get("BENCH_IO_BATCH", "128"))
    threads = int(os.environ.get("BENCH_IO_THREADS",
                                 str(os.cpu_count() or 4)))
    side = 256  # stored size; decode crops to 224
    rec = os.path.join(tempfile.mkdtemp(prefix="bench_io_"), "syn.rec")
    from PIL import Image
    from io import BytesIO

    rng = onp.random.RandomState(0)
    w = recordio.MXIndexedRecordIO(rec + ".idx", rec, "w")
    # a handful of distinct JPEGs cycled n times: realistic decode cost
    # without minutes of synthetic-data generation
    blobs = []
    for i in range(32):
        img = Image.fromarray(
            rng.randint(0, 255, (side, side, 3), "uint8"))
        buf = BytesIO()
        img.save(buf, format="JPEG", quality=90)
        blobs.append(buf.getvalue())
    for i in range(n):
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i % 1000), i, 0),
            blobs[i % len(blobs)]))
    w.close()

    it = mxio.ImageRecordIter(
        rec, data_shape=(3, 224, 224), batch_size=batch,
        path_imgidx=rec + ".idx", shuffle=True, rand_crop=True,
        rand_mirror=True, mean_r=123.68, mean_g=116.78, mean_b=103.94,
        std_r=58.4, std_g=57.1, std_b=57.4,
        preprocess_threads=threads, prefetch_buffer=4)
    seen = 0
    for b in it:  # warmup epoch (JIT of the normalize, page cache)
        seen += b.data[0].shape[0]
    it.reset()
    t0 = time.perf_counter()
    seen = 0
    for b in it:
        b.data[0].wait_to_read()
        seen += b.data[0].shape[0]
    dt = time.perf_counter() - t0
    imgs = seen / dt
    print(json.dumps({
        "metric": "image_record_iter_imgs_per_sec",
        "value": round(imgs, 2), "unit": "img/s", "vs_baseline": 0.0,
        "extra": {"images": seen, "batch": batch,
                  "preprocess_threads": threads,
                  "host_cpus": os.cpu_count(),
                  "imgs_per_sec_per_core": round(imgs / max(
                      1, os.cpu_count() or 1), 2),
                  "decode": "jpeg 256->224 rand-crop+mirror+normalize",
                  "note": "decode scales ~linearly in the native thread "
                          "pool; a real TPU-vM host has ~100+ cores vs "
                          "this box"}}))


def main():
    if SMOKE:
        smoke_main()
        return
    mode = os.environ.get("BENCH_MODE")
    if mode == "io":
        io_main()
        return
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[bench] no TPU: jax reports {dev.platform!r} "
              f"({dev.device_kind}); refusing to measure (BENCH_SMOKE=1 "
              "runs the CPU plumbing check)", file=sys.stderr)
        raise SystemExit(1)
    if mode == "rawjax":
        rawjax_main()
    elif mode == "profile":
        profile_main()
    else:
        measure_main()


if __name__ == "__main__":
    main()
