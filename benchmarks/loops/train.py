"""Traffic kind ``train``: a pool of host batches through
``pipeline.DeviceFeed`` into ``parallel.SPMDTrainer.step``, with the loss
of step i read back after step i+1 is dispatched.

Set-up builds ONE trainer, drives it from the seed through its first
three steps by the window's own feed and call, keeps what ``correct``
needs of them (each loss; the optimizer's state after step 1, which is
the first gradient, as per-leaf norms and as a sample of each leaf's
elements; the parameters' change after step 3 as per-leaf norms; the
state leaves after each step) and hands that same trainer to the window. After the window the program's state is
freed and the plain reference follows the same three steps.

Parameters of the traffic file: ``batch_per_chip``, ``pool`` (distinct
host batches), ``feed_depth``, ``span_steps`` (steps to a host-clock
reading of at least 250 ms), ``trace_seconds``, and what the
configuration's builder reads (``seq`` for token batches).
"""
from __future__ import annotations

import functools
import time

import numpy as onp

import binding
import correct
import refcommon

CHECKED_STEPS = 3


def reference_step(ctx, precision):
    """One jitted step of the reference: (loss, per-leaf norms of the
    gradient, its sampled elements in the program's layout, new
    parameters, new state leaves, new optimizer state)."""
    import jax
    import jax.numpy as jnp

    ref, cfg, to_program = ctx.ref, ctx.cfg, ctx.model.to_program
    spec = cfg["train"]["optimizer"]

    # the old parameters and state are donated: float32 masters, gradients
    # and Adam's moments of the timed size do not fit the chip twice
    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def step(params, aux, state, t, x, y):
        (lval, new_aux), grads = jax.value_and_grad(
            lambda p: ref.loss(cfg, p, aux, (x, y), precision),
            has_aux=True)(params)
        grads = {k: g.astype(jnp.float32) for k, g in grads.items()}
        picked = {k: refcommon.sample(to_program(k, g))
                  for k, g in grads.items()}
        new_p, new_s = refcommon.opt_update(spec, params, grads, state, t)
        return lval, _norms(grads), picked, new_p, new_aux, new_s

    return step


def _norms(tree):
    import jax.numpy as jnp

    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def reference_steps(ctx, batches, precision="float32"):
    """The reference's readings over ``batches``: plain steps of the
    configuration's optimizer from the seed's weights, float32 or, for
    the control, one precision below what the configuration states."""
    import jax

    ref, cfg = ctx.ref, ctx.cfg
    make = jax.jit(lambda k: ref.init(cfg, k))
    params, aux = make(refcommon.key_from_seed(ctx.seed))
    state = refcommon.opt_init(cfg["train"]["optimizer"], params)
    aux0 = {k: onp.asarray(v) for k, v in aux.items()}
    step = reference_step(ctx, precision)

    out = {"loss": [], "grad": None}
    aux_seq = []
    for i, (x, y) in enumerate(batches):
        lval, gn, picked, params, aux, state = step(params, aux, state,
                                                    i + 1, x, y)
        out["loss"].append(float(lval))
        if i == 0:
            out["grad"] = {k: float(v) for k, v in gn.items()}
            out["grad_sample"] = {k: onp.asarray(v)
                                  for k, v in picked.items()}
        aux_seq.append({k: onp.asarray(v) for k, v in aux.items()})
    del state
    delta = jax.jit(lambda a, k: _norms(
        {n: a[n] - w for n, w in ref.init(cfg, k)[0].items()}))(
        params, refcommon.key_from_seed(ctx.seed))
    out["delta"] = {k: float(v) for k, v in delta.items()}
    out["stat"] = stat_change_norms(aux0, aux_seq)
    out["axes"] = {k: len(shape)
                   for k, (shape, _) in ref.leaf_shapes(cfg).items()}
    return out


def stat_change_norms(aux0, aux_seq):
    """Per state leaf (BatchNorm's running statistics), the norm of its
    change from the seed's state after each checked step, as one vector:
    the statistics themselves, step by step."""
    return {k: float(onp.sqrt(sum(
        onp.sum(onp.square(onp.asarray(a[k], onp.float64)
                           - onp.asarray(a0, onp.float64)))
        for a in aux_seq))) for k, a0 in aux0.items()}


class Loop:
    def __init__(self, ctx):
        self.ctx = ctx
        self.prog = None        # the program's readings of the checked steps

    # -- set-up ---------------------------------------------------------------
    def setup(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        import mxnet_tpu as mx
        from mxnet_tpu import parallel
        from mxnet_tpu.pipeline import DeviceFeed

        ctx = self.ctx
        t_setup = time.perf_counter()
        parts = ctx.measured.setdefault("setup_parts_s", {})

        def lap(name):
            nonlocal t_setup
            now = time.perf_counter()
            parts[name] = round(now - t_setup, 3)
            t_setup = now

        cfg, tr, model, ref = ctx.cfg, ctx.traffic, ctx.model, ctx.ref
        self.spec = dict(cfg["train"]["optimizer"])
        self.batch = int(tr["batch_per_chip"]) * ctx.chips
        self.items_per_step = model.items_per_batch(cfg, tr, self.batch)

        # the pool of host batches, from the seed: rows that all differ
        rng = ctx.rng(1)
        self.pool = [model.make_batch(cfg, tr, self.batch, rng)
                     for _ in range(int(tr["pool"]))]
        self.order = [int(i) for i in ctx.rng(2).permutation(len(self.pool))]

        def source():
            while True:
                for i in self.order:
                    yield self.pool[i]

        lap("pool")
        # the net through the program's public API; the weights are the
        # benchmark's, made on the device in one jitted call from the seed
        mx.random.seed(ctx.seed & 0x7FFFFFFF)
        net = model.build_net(cfg)
        net.initialize()
        self.leaf_of, program_weights = binding.seed_weights(ctx, net)

        lap("net_and_weights")
        self.mesh = parallel.make_mesh({"dp": ctx.chips}, devices=ctx.devices)
        opt = {k: v for k, v in self.spec.items() if k != "kind"}
        if ctx.fault == "state_unchanged":
            opt["learning_rate"] = 0.0
        kind = {"sgd": "sgd", "adam": "adamw"}[self.spec["kind"]]
        self.trainer = parallel.SPMDTrainer(
            net, model.loss_block(cfg), optimizer=kind, optimizer_params=opt,
            mesh=self.mesh, compute_dtype=cfg["train"]["compute_dtype"])
        self.shard = NamedSharding(self.mesh, P("dp"))
        self.feed = DeviceFeed(source(), depth=int(tr["feed_depth"]),
                               device=self.shard)
        self.net = net

        # per-leaf readings, computed on the device from what the step left
        def sq_norm(a):
            return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))

        first_readings = jax.jit(lambda arrs: (
            [sq_norm(a) for a in arrs],
            [refcommon.sample(a) for a in arrs]))

        def delta_norms(named, key):
            w0 = program_weights(key)
            return {k: sq_norm(v - w0[k]) for k, v in named.items()}

        delta_norms = jax.jit(delta_norms)
        aux_leaves = sorted(jax.eval_shape(
            lambda k: ref.init(cfg, k), refcommon.key_from_seed(ctx.seed))[1])
        copy = jax.jit(lambda arrs: [a + 0 for a in arrs])
        aux0 = {}
        if aux_leaves:
            w0 = jax.jit(lambda key: {
                k: v for k, v in program_weights(key).items()
                if k in aux_leaves})(refcommon.key_from_seed(ctx.seed))
            aux0 = {k: onp.asarray(v) for k, v in w0.items()}
        aux_seq = []

        # the first steps: the window's own feed and call
        prog = {"loss": [], "grad": {}, "delta": {}}
        scale = refcommon.grad_scale_from_state(self.spec)
        for i in range(CHECKED_STEPS):
            loss = self._step(*next(self.feed))
            if i == 0:      # the optimizer's state is the first gradient's
                names = [p.name for p in self.trainer._params]
                moments = {
                    self.leaf_of[n][1]: refcommon.first_moment(self.spec, st)
                    for n, st in zip(names, self.trainer._states)
                    if st is not None}
                got, picked = jax.device_get(
                    first_readings(list(moments.values())))
                prog["grad"] = {k: float(v) / abs(scale)
                                for k, v in zip(moments, got)}
                prog["grad_sample"] = {k: onp.asarray(v) / scale
                                       for k, v in zip(moments, picked)}
                del moments
            if aux_leaves:      # state leaves, copied before the next
                # step donates them
                live = {self.leaf_of[n][1]: a for n, a in
                        self.trainer.param_arrays().items()}
                got = jax.device_get(copy([live[k] for k in aux_leaves]))
                aux_seq.append(dict(zip(aux_leaves, got)))
                del live
            prog["loss"].append(float(jax.device_get(loss.data)))
            lap(f"step{i + 1}")
        named = {self.leaf_of[n][1]: a
                 for n, a in self.trainer.param_arrays().items()
                 if self.leaf_of[n][1] not in aux0}
        prog["delta"] = {k: float(v) for k, v in delta_norms(
            named, refcommon.key_from_seed(ctx.seed)).items()}
        del named
        prog["stat"] = stat_change_norms(aux0, aux_seq)
        self.prog = prog
        # one more lagged pair, so the window starts on a full queue path
        self._drain(self._step(*next(self.feed)))
        lap("readings_and_step4")

    def _step(self, x, y):
        fault = self.ctx.fault
        if fault == "half_batch":
            half = self.batch // 2
            x, y = x[:half], y[:half]
        return self.trainer.step(x, y)

    @staticmethod
    def _drain(loss):
        import jax

        return float(jax.device_get(loss.data))

    # -- the measured window --------------------------------------------------
    def window(self, seconds):
        from mxnet_tpu.pipeline import pipeline_counters
        from mxnet_tpu.utils import compile_cache as cc

        ctx, tracer = self.ctx, self.ctx.tracer
        feed, now = self.feed, time.perf_counter
        c0 = pipeline_counters()
        r0 = cc.compile_cache_stats()["retraces"]
        done, dispatch_s = [], 0.0
        prev = None
        sync = lambda: prev is not None and self._drain(prev)  # noqa: E731
        t_start = now()
        while True:
            elapsed = now() - t_start
            tracer.tick(elapsed, sync)
            if elapsed >= seconds:
                break
            with ctx.span("bench.feed_next"):
                x, y = next(feed)
            t = now()
            with ctx.span("bench.step_dispatch"):
                loss = self._step(x, y)
            dispatch_s += now() - t
            if prev is not None:
                with ctx.span("bench.loss_readback"):
                    self._drain(prev)
                done.append(now())
            prev = loss
        self._drain(prev)
        done.append(now())
        t_end = done[-1]
        c1 = pipeline_counters()
        steps = len(done)
        wall = t_end - t_start
        k = max(1, int(ctx.traffic["span_steps"]))
        marks = [t_start] + done
        spans = sorted((marks[i + k] - marks[i]) / k * 1e3
                       for i in range(len(marks) - k))
        m = ctx.measured
        m["attempted"], m["failed"] = steps, 0
        m["steps"], m["wall_s"] = steps, wall
        m["items"] = steps * self.items_per_step
        m["items_per_step"] = self.items_per_step
        m[ctx.traffic["rate_metric"]] = m["items"] / wall
        m["step_ms_p90"] = spans[min(len(spans) - 1,
                                     int(0.9 * len(spans)))]
        m["span_samples"] = len(spans)
        single = sorted((b - a) * 1e3 for a, b in zip(marks, marks[1:]))
        m["single_step_ms_p90"] = single[min(len(single) - 1,
                                             int(0.9 * len(single)))]
        m["longest_ms"] = max(b - a for a, b in zip(marks, marks[1:])) * 1e3
        m["dispatch_s"] = dispatch_s
        m["feed_stall_s"] = c1["prefetch_stall_s"] - c0["prefetch_stall_s"]
        m["feed_stalls"] = c1["prefetch_stalls"] - c0["prefetch_stalls"]
        m["retraces"] = cc.compile_cache_stats()["retraces"] - r0
        m["traced_rate"] = m["items"] / max(wall - tracer.stall_s, 1e-9)

    # -- afterwards -----------------------------------------------------------
    def temp_bytes(self):
        """Bytes of temporaries the compiled step needs on one device,
        from the executable's own memory analysis: the runtime's
        ``peak_bytes_in_use`` does not count them (PERF.md, PR 27)."""
        import jax

        t = self.trainer
        x, y = (jax.device_put(a, self.shard) for a in self.pool[0])
        compiled = t._compiled.lower(t._param_vals, t._states, t._aux,
                                     x, y).compile()
        return int(compiled.memory_analysis().temp_size_in_bytes)

    def release(self):
        self.feed.close()
        self.first_batches = [self.pool[i] for i in
                              (self.order * CHECKED_STEPS)[:CHECKED_STEPS]]
        self.feed = self.trainer = self.net = self.pool = None
        self.leaf_of = None

    def verify(self):
        ctx = self.ctx
        ref = reference_steps(ctx, self.first_batches, "float32")
        self.ref_readings = ref
        values, at = correct.train_numbers(self.prog, ref)
        ctx.measured["compared_at"] = at
        ctx.measured["readings"] = {"program": self.prog["loss"],
                                    "reference": ref["loss"]}
        ctx.measured["all_numbers"] = {k: v for k, v in values.items()
                                       if v is not None}
        return correct.with_limits(values, ctx.limits)

    def control(self, precision=None):
        """The control's numbers: the reference put in the program's place
        one precision below what the configuration states, against the
        float32 reference (``verify`` has to have run)."""
        ctx = self.ctx
        low = self.control_readings = reference_steps(
            ctx, self.first_batches,
            precision or ctx.cfg["train"]["control_precision"])
        return correct.train_numbers(low, self.ref_readings)[0]
