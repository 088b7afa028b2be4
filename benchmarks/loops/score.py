"""Traffic kind ``score``: bulk scoring in a closed loop. One caller sends
host batches from a seeded pool to ``serving.InferenceSession.predict``
and reads the logits back to the host before it sends the next.

``correct`` compares the logits that the window's own calls returned (all
of them, or a sample drawn from the seed once there are more than
``compare_calls``, the last call always among them) with the plain
reference's logits for the same rows.

Parameters of the traffic file: ``batch_per_chip``, ``pool``,
``compare_calls``, ``trace_seconds``.
"""
from __future__ import annotations

import time

import numpy as onp

import binding
import correct
import refcommon

REF_ROWS = 128      # the reference scores the batch in blocks of rows


def reference_logits(ctx, x, precision="float32"):
    import jax

    ref, cfg = ctx.ref, ctx.cfg
    params, aux = jax.jit(lambda k: ref.init(cfg, k))(
        refcommon.key_from_seed(ctx.seed))
    fn = jax.jit(lambda p, a, rows: ref.score(cfg, p, a, rows, precision))
    rows = min(REF_ROWS, len(x))
    out = [onp.asarray(fn(params, aux, x[i:i + rows]))
           for i in range(0, len(x), rows)]
    return onp.concatenate(out, axis=0)


class Loop:
    def __init__(self, ctx):
        self.ctx = ctx

    def setup(self):
        import mxnet_tpu as mx
        from mxnet_tpu import serving

        ctx = self.ctx
        cfg, tr, model = ctx.cfg, ctx.traffic, ctx.model
        self.batch = int(tr["batch_per_chip"]) * ctx.chips
        rng = ctx.rng(1)
        self.pool = [model.make_batch(cfg, tr, self.batch, rng)[0]
                     for _ in range(int(tr["pool"]))]
        self.order = [int(i) for i in ctx.rng(2).permutation(len(self.pool))]

        mx.random.seed(ctx.seed & 0x7FFFFFFF)
        net = model.build_net(cfg)
        net.initialize()
        binding.seed_weights(ctx, net)
        net.hybridize()
        self.sess = serving.InferenceSession(
            net, input_shapes=[model.example_input(cfg, tr).shape],
            buckets=[self.batch], max_batch=self.batch, warm=False)
        self.sess.warmup()
        self._call(0)                   # the timed shape, once, untimed
        self.net = net

    def _call(self, i):
        x = self.pool[self.order[i % len(self.order)]]
        out = self.sess.predict(x).asnumpy()
        if self.ctx.fault == "answer_altered" and i % 3 == 1:
            out = out.copy()
            out[len(out) // 2, 0] += 0.5 * onp.abs(out).max()
        return out

    def window(self, seconds):
        from mxnet_tpu.utils import compile_cache as cc

        ctx, tracer, now = self.ctx, self.ctx.tracer, time.perf_counter
        cap = int(ctx.traffic["compare_calls"])
        pick = ctx.rng(3)
        kept = {}                       # call index -> logits
        r0 = cc.compile_cache_stats()["retraces"]
        calls, last, longest = 0, None, 0.0
        t_start = now()
        while True:
            elapsed = now() - t_start
            tracer.tick(elapsed)
            if elapsed >= seconds:
                break
            t_call = now()
            with ctx.span("bench.predict"):
                out = self._call(calls)
            longest = max(longest, now() - t_call)
            # reservoir sample of the calls, drawn from the seed
            if len(kept) < cap:
                kept[calls] = out
            else:
                j = int(pick.integers(0, calls + 1))
                if j < cap:
                    kept.pop(sorted(kept)[j])
                    kept[calls] = out
            last = (calls, out)
            calls += 1
        wall = now() - t_start
        if last is not None:
            kept[last[0]] = last[1]     # the last call is always compared
        self.kept = kept
        m = ctx.measured
        m["attempted"], m["failed"] = calls, 0
        m["steps"], m["wall_s"] = calls, wall
        m["items_per_step"] = self.batch
        m["longest_ms"] = longest * 1e3
        m["items"] = calls * self.batch
        m[ctx.traffic["rate_metric"]] = m["items"] / wall
        m["retraces"] = cc.compile_cache_stats()["retraces"] - r0
        m["traced_rate"] = m["items"] / max(wall - tracer.stall_s, 1e-9)

    def temp_bytes(self):
        """Bytes of temporaries of the bucket's executable (see the train
        loop's note)."""
        fn = self.sess._entry(self.batch).fn
        return int(fn._compiled.memory_analysis().temp_size_in_bytes)

    def release(self):
        self.sess.close()
        self.used = {i: self.pool[i] for i in
                     {self.order[c % len(self.order)] for c in self.kept}}
        self.sess = self.net = self.pool = None

    def verify(self):
        ctx = self.ctx
        refs = {i: reference_logits(ctx, x) for i, x in self.used.items()}
        self.refs = refs
        worst = 0.0
        for call, served in self.kept.items():
            gap = correct.logit_gap(
                served, refs[self.order[call % len(self.order)]])
            worst = max(worst, gap)
        ctx.measured["compared_calls"] = len(self.kept)
        values = {"logit_gap": worst if self.kept else None}
        ctx.measured["all_numbers"] = {k: v for k, v in values.items()
                                       if v is not None}
        return correct.with_limits(values, ctx.limits)

    def control(self):
        """The control's number: the reference one precision below what
        the configuration states, at every position of the same rows,
        against the float32 reference (``verify`` has to have run)."""
        ctx = self.ctx
        low = ctx.cfg["score"]["control_precision"]
        return {"logit_gap": max(
            correct.logit_gap(reference_logits(ctx, x, low), self.refs[i])
            for i, x in self.used.items())}
