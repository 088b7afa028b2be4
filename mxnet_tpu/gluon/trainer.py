"""Gluon Trainer.

TPU-native equivalent of python/mxnet/gluon/trainer.py (reference:
Trainer:27, kvstore wiring :169-217, step/allreduce_grads/update). The
reference pushes grads through kvstore (CPU/GPU reduce or ps-lite); here
single-host aggregation is implicit (one logical grad per param) and
multi-host runs ride `mxnet_tpu.parallel` collectives.

``step`` runs through the compiled fused train-step by default
(gluon/fused_step.py): ONE jit-compiled, buffer-donated XLA executable
per parameter-group signature covering the bucketed gradient allreduce,
the device-side AMP overflow check with ``lax.cond`` skip-step
semantics, rescale, and the multi-tensor optimizer update — the analog
of the reference's multi-tensor fused update ops
(src/operator/contrib/preloaded_multi_sgd.cc) extended to the whole
weight-update phase. ``MXNET_FUSED_STEP=0``, optimizers without a fused
kernel, and sparse gradients fall back to the eager per-param loop.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..base import MXNetError
from .. import optimizer as opt
from .. import kvstore as kvs
from . import fused_step as _fs
from .parameter import Parameter

__all__ = ["Trainer"]


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None):
        if isinstance(params, (dict,)) or hasattr(params, "values"):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise ValueError(
                "First argument must be a list or dict of Parameters, "
                f"got {type(params)}.")
        self._params = []
        self._param2idx = {}
        for i, param in enumerate(params):
            if not isinstance(param, Parameter):
                raise ValueError(
                    "First argument must be a list or dict of Parameters, "
                    f"got list of {type(param)}.")
            self._param2idx[param.name] = i
            self._params.append(param)
        self._compression_params = compression_params
        self._contexts = None
        optimizer_params = optimizer_params or {}
        self._init_optimizer(optimizer, optimizer_params)
        self._scale = self._optimizer.rescale_grad
        self._kvstore_type = kvstore if isinstance(kvstore, str) else \
            getattr(kvstore, "type", "device")
        self._kvstore = kvstore if isinstance(kvstore, kvs.KVStore) else None
        self._kv_initialized = False
        self._update_on_kvstore = update_on_kvstore
        self._distributed = self._kvstore_type.startswith("dist")
        self._states_created = False
        self._fused = None           # cached (key, executable) for this trainer
        self._fused_state = None     # device-resident (t[, scale, unsk, skips])
        self._fused_broken = False   # compiled step raised once; stay eager
        self._fused_skips_host = 0   # skip total carried across re-seeds
        self._grad_reducer = None    # dispatch-as-ready bucketed allreduce

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            if optimizer_params:
                raise ValueError(
                    "optimizer_params must be None if optimizer is an "
                    "instance of Optimizer instead of str")
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._updaters = [opt.get_updater(self._optimizer)]

    def _create_states(self):
        self._states = [
            self._optimizer.create_state_multi_precision(i, p.data())
            for i, p in enumerate(self._params)]
        self._states_created = True

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        """Takes effect on the very next step: the fused executable reads
        lr as a dynamic scalar argument, so no recompilation happens."""
        self._optimizer.set_learning_rate(lr)

    def allreduce_grads(self):
        """Cross-worker gradient all-reduce (reference: trainer.py
        _allreduce_grads via kvstore push/pull). Single host: no-op (one
        logical grad); dist: dense gradients are coalesced into
        dtype-bucketed flattened collectives
        (parallel.all_reduce_coalesced) instead of one psum per
        parameter; sparse gradients keep the per-tensor path.

        With ``MXNET_ASYNC_GRAD_SYNC`` (default on) the dense buckets
        are dispatched AS BACKWARD PRODUCES THEM via the grad-ready
        hook (pipeline/grad_sync.py) — this call then only flushes the
        partial buckets and binds the already-reduced results, so the
        collectives overlap the backward instead of serializing after
        it. Values are bit-identical on both paths."""
        if not self._distributed:
            return
        from .. import parallel
        from ..ndarray import sparse as _sp

        grads = [p.grad() for p in self._params if p.grad_req != "null"]
        dense = [g for g in grads
                 if not isinstance(g, _sp.BaseSparseNDArray)]
        reducer = self._async_reducer()
        if dense and reducer is not None:
            reducer.flush(dense)
        elif dense:
            for g, r in zip(dense, parallel.all_reduce_coalesced(dense)):
                g._data = r.data
        for g in grads:
            if isinstance(g, _sp.BaseSparseNDArray):
                g._data = parallel.all_reduce(g).data

    def _async_reducer(self):
        """The dispatch-as-ready bucketed reducer, created and hooked
        into autograd once per trainer while MXNET_ASYNC_GRAD_SYNC is
        on (the hook itself no-ops per round when toggled off, so the
        knob stays a pure fallback switch)."""
        from .. import pipeline as _pl

        if not _pl.async_grad_sync_enabled():
            if self._grad_reducer is not None:
                # knob flipped off between backward and step: discard
                # this round's speculation and re-arm the hook's
                # per-round knob read, else it keeps dispatching
                self._grad_reducer.abandon()
            return None
        if self._grad_reducer is None:
            self._grad_reducer = _pl.AsyncGradReducer(
                self._params).attach()
        return self._grad_reducer

    def _abandon_speculation(self):
        """Discard any in-flight MXNET_ASYNC_GRAD_SYNC speculation
        (pending buckets + speculative reductions) without binding it.
        State capture/restore boundaries — ``save_states``,
        ``load_states``, CheckpointManager snapshots — must call this:
        a speculative reduction captured before the boundary would
        otherwise be bound into the first step AFTER it, mixing
        pre-restore gradient values into post-restore math."""
        if self._grad_reducer is not None:
            self._grad_reducer.abandon()

    # -- fused compiled step ------------------------------------------------

    def _fused_skipped_steps(self):
        """AMP skip-step total (host carry + live device counter)."""
        st = self._fused_state
        if st is not None and len(st["vals"]) == 4:
            return int(st["vals"][3])
        return self._fused_skips_host

    def _invalidate_fused_state(self):
        st = self._fused_state
        if st is not None and len(st["vals"]) == 4:
            try:
                self._fused_skips_host = int(st["vals"][3])
            except Exception:  # graft-lint: allow(L501)
                # the state tuple was donated to an executable that then
                # failed at execution — the buffers are gone; keep the
                # last host carry rather than crash the eager fallback
                pass
        self._fused_state = None

    def _sync_fused_state(self):
        """Pull the device-resident step state back into the host
        mirrors: optimizer.num_update (authoritative update count — the
        host mirror drifts by the number of AMP-skipped steps) and the
        loss scaler's scale/window counter. Called by save_states and by
        ``LossScaler.loss_scale`` property reads; a no-op unless a fused
        step ran since the last sync, so repeated reads (one
        ``amp.scale_loss`` per iteration) cost at most one scalar
        device read per step."""
        st = self._fused_state
        if st is None or not st.get("dirty", True):
            return
        vals = st["vals"]
        t = int(vals[0])
        self._optimizer.num_update = t
        for k in self._optimizer._index_update_count:
            self._optimizer._index_update_count[k] = t
        st["expected_num_update"] = t
        if len(vals) == 4:
            scaler = getattr(self, "_amp_loss_scaler", None)
            if scaler is not None:
                scaler._loss_scale = float(vals[1])
                scaler._unskipped = int(vals[2])
                st["scaler_mirror"] = (scaler._loss_scale,
                                       scaler._unskipped)
            self._fused_skips_host = int(vals[3])
        st["dirty"] = False

    def _ensure_fused_state(self, scaler):
        """(Re)seed the donated device step-state when absent or when the
        host-side sources changed externally (load_states, a user write
        to scaler.loss_scale / optimizer.num_update)."""
        optim = self._optimizer
        st = self._fused_state
        mode = 4 if scaler is not None else 1
        if st is not None and len(st["vals"]) == mode:
            if st["expected_num_update"] == optim.num_update and (
                    scaler is None or st["scaler_mirror"] ==
                    (scaler._loss_scale, scaler._unskipped)):
                return st
        self._invalidate_fused_state()
        vals = (jnp.int32(optim.num_update),)
        mirror = None
        if scaler is not None:
            vals = vals + (jnp.float32(scaler._loss_scale),
                           jnp.int32(scaler._unskipped),
                           jnp.int32(self._fused_skips_host))
            mirror = (scaler._loss_scale, scaler._unskipped)
            scaler._device_sync = self._sync_fused_state
        st = {"vals": vals, "expected_num_update": optim.num_update,
              "scaler_mirror": mirror, "dirty": True}
        self._fused_state = st
        _fs.register_trainer(self)
        return st

    def _fused_step(self, batch_size, scaler):
        """One compiled-executable step; False = bypass to the eager
        path (unsupported optimizer, sparse grads, tracers). The full
        aval signature / LRU key is only rebuilt when cheap identity
        tokens change (param buffers rebound by cast(), states replaced
        by load_states, grad_req edits, hyperparameter statics) — the
        steady-state per-step host work is gathering buffers and the
        dynamic lr/wd/rescale scalars. A stale token is a perf miss, not
        a correctness hazard: the inner jax.jit re-specializes on avals
        anyway."""
        from ..ndarray import sparse as _sp

        optim = self._optimizer
        kern = optim._fused_kernel()
        if kern is None:
            _fs._CACHE.note_bypass()
            return False
        if not self._states_created:
            self._create_states()
        kernel_key, kernel = kern
        scaler_cfg = None if scaler is None else \
            (float(scaler._scale_factor), int(scaler._scale_window))
        donate_params = _fs.donate_params_enabled()
        from ..ndarray import registry as _registry

        token = (kernel_key, scaler_cfg, donate_params,
                 _registry.amp_version(), self._shard_token(),
                 tuple(p._grad_req for p in self._params))
        cache = self._fused
        if cache is not None and cache["token"] == token and \
                cache["states"] is self._states and \
                cache["nd_ids"] == tuple(
                    (id(p._ndarray), id(p._ndarray._grad))
                    for p in cache["params"]):
            params, grads = cache["params"], cache["grads"]
            states, entry = cache["work_states"], cache["entry"]
            if any(isinstance(g, _sp.BaseSparseNDArray) for g in grads) \
                    or _fs.has_tracer([g.data for g in grads]):
                _fs._CACHE.note_bypass()
                return False
            _fs._CACHE.note_hit()
        else:
            group = self._fused_group(kernel_key, scaler_cfg,
                                      donate_params)
            if group == "empty":
                return True  # nothing to update; eager loop no-ops too
            if group is None:
                _fs._CACHE.note_bypass()
                return False
            work, params = group["work"], group["params"]
            grads, states = group["grads"], group["states"]
            entry = self._fused_entry(group, kernel, scaler_cfg,
                                      donate_params)
            self._fused = cache = {
                "token": token, "states": self._states,
                "nd_ids": tuple((id(p._ndarray), id(p._ndarray._grad))
                                for p in params),
                "params": params, "grads": grads, "work_states": states,
                "work": work, "entry": entry,
                "shard_cfg": group.get("shard_cfg"),
                "lr_host": None, "lr_dev": None,
                "wd_host": None, "wd_dev": None,
                "rescale_host": None, "rescale_dev": None}
        work = cache["work"]
        if donate_params:
            # MXNET_GRAPH_VERIFY-gated: donating parameter buffers while
            # a tape node still holds them as saved primals means the
            # next backward reads deleted memory (analysis/donation.py).
            # Checked before the host count mirror advances so an
            # =error raise leaves the optimizer state untouched.
            from ..analysis import check_param_donation

            check_param_donation(
                [(p.name, p._ndarray._data) for p in params])
        st = self._ensure_fused_state(scaler)

        # host update-count mirror advances like the eager path (on AMP
        # overflow the device t stays put and the mirror drifts until
        # _sync_fused_state); lr/wd computed AFTER the bump so an
        # attached lr_scheduler sees the same num_update as eager
        snap = (optim.num_update, dict(optim._index_update_count))
        for i in work:
            optim._update_count(i)
        lr_host = [optim._get_lr(i) for i in work]
        if lr_host != cache["lr_host"]:
            cache["lr_host"] = lr_host
            cache["lr_dev"] = jnp.asarray(lr_host, jnp.float32)
        lrs = cache["lr_dev"]
        wd_host = [optim._get_wd(i) for i in work]
        if wd_host != cache["wd_host"]:
            cache["wd_host"] = wd_host
            cache["wd_dev"] = jnp.asarray(wd_host, jnp.float32)
        wds = cache["wd_dev"]
        rescale_host = self._scale / batch_size
        if rescale_host != cache["rescale_host"]:
            cache["rescale_host"] = rescale_host
            cache["rescale_dev"] = jnp.float32(rescale_host)
        rescale = cache["rescale_dev"]
        pv = tuple(p._ndarray._data for p in params)
        gv = tuple(g._data for g in grads)
        sv = tuple(_fs.state_data(s) for s in states)
        shard_cfg = cache.get("shard_cfg")
        if shard_cfg is not None:
            # jit with in_shardings rejects committed buffers at another
            # layout — place (and launder donated) inputs; identity at
            # steady state
            pv, gv, sv = shard_cfg.place_args(pv, gv, sv, donate_params)
        try:
            new_p, new_s, vals2 = entry(pv, gv, sv, st["vals"], lrs, wds,
                                        rescale)
        except Exception:
            # roll the count mirror back; the eager path re-counts
            optim.num_update, optim._index_update_count = snap[0], snap[1]
            _fs._CACHE.note_fallback()
            self._fused_broken = True
            self._fused = None
            self._invalidate_fused_state()
            return False
        st["vals"] = vals2
        st["expected_num_update"] = optim.num_update
        st["dirty"] = True
        for p, w2 in zip(params, new_p):
            p.data()._data = w2
        for s, s2 in zip(states, new_s):
            _fs.rebind_state(s, s2)
        return True

    def _shard_token(self):
        """Cheap identity token for the active sharding declaration —
        part of the per-step cache token so entering/leaving a
        ``sharding.plan_scope`` (or toggling ZeRO-1) rebuilds the fused
        group instead of reusing the other layout's executable."""
        from .. import sharding as _shard

        ctx = _shard.current_plan()
        if ctx is None:
            return None
        return (id(ctx[0]), id(ctx[1]), _shard.zero1_enabled())

    def _fused_group(self, kernel_key, scaler_cfg, donate_params):
        """Work set + LRU cache key for a fused step over the current
        parameter group: a dict, the sentinel ``"empty"`` (nothing has
        grad_req != null — the step is a no-op), or None (sparse or
        tracer gradients force the eager path)."""
        from ..ndarray import sparse as _sp
        from ..ndarray import registry as _registry

        optim = self._optimizer
        work = [i for i, p in enumerate(self._params)
                if p.grad_req != "null"]
        if not work:
            return "empty"
        params = [self._params[i] for i in work]
        grads = [p.grad() for p in params]
        if any(isinstance(g, _sp.BaseSparseNDArray) for g in grads) \
                or _fs.has_tracer([g.data for g in grads]):
            return None
        mp_flags = tuple(
            bool(optim.multi_precision and optim._is_half(p.data()))
            for p in params)
        states = [self._states[i] for i in work]
        sig = tuple(
            (tuple(p.shape), str(p.data().data.dtype),
             str(g.data.dtype), _fs.state_sig(s))
            for p, g, s in zip(params, grads, states))
        from .. import sharding as _shard

        shard_cfg = _shard.fused_shard_cfg(
            [(p.name, tuple(p.shape)) for p in params],
            [_fs.state_sig(s) for s in states]) \
            if self._shard_token() is not None else None
        key = (type(optim).__name__, kernel_key, mp_flags, sig,
               scaler_cfg, self._distributed, donate_params,
               _registry.amp_version(),
               None if shard_cfg is None else shard_cfg.salt)
        return {"work": work, "params": params, "grads": grads,
                "states": states, "mp_flags": mp_flags, "key": key,
                "shard_cfg": shard_cfg}

    def _fused_entry(self, group, kernel, scaler_cfg, donate_params):
        """The cached fused-step executable for a ``_fused_group`` —
        ONE construction site shared by the step loop and warmup, so
        both always build identical entries for a key."""
        key = group["key"]
        entry = _fs._CACHE.lookup(key)
        if entry is None:
            entry = _fs.build_executable(kernel, group["mp_flags"],
                                         scaler_cfg, donate_params,
                                         cache_key=key,
                                         shard_cfg=group.get("shard_cfg"))
            _fs._CACHE.insert(key, entry)
        return entry

    # -- AOT warmup ---------------------------------------------------------

    def warmup(self, shapes=None, block=None):
        """Precompile the training-path executables up front, so no
        compile stall (or retrace storm) lands mid-epoch — with the
        persistent compile cache armed (``MXNET_COMPILE_CACHE``), warm
        processes pull the executables straight off disk instead of
        compiling at all.

        Without arguments: resolves the fused train-step executable for
        the current parameter group via ``lower()``/``compile()`` only —
        nothing executes, no state changes.

        With ``block`` and ``shapes`` (an iterable of input shapes, one
        per expected batch signature/bucket): additionally runs one full
        forward/backward/``step`` per shape on zero inputs to warm every
        executable on the training path (eager-dispatch entries,
        hybridized CachedOp traces, the fused step), then restores
        parameters, gradients, optimizer state, AMP loss-scale state and
        the PRNG stream bit-for-bit, so training after ``warmup`` is
        byte-identical to training without it. Two caveats: (1) when
        deferred-init params materialize during warmup AND the forward
        draws stochastic keys (dropout), the cold run would interleave
        init and mask draws in one stream — that interleave cannot be
        reproduced ahead of time, so initialize shapes (or run one
        inference forward) first for strict parity; (2) warming shifts
        which step is the first *compiled* execution of each recording
        entry, which on fusion-sensitive graphs can differ from the
        uncached first run by an ulp. Best effort by design: executables keyed off
        the real loss head still compile on first use. Returns the
        number of shapes warmed."""
        if (block is None) != (shapes is None):
            # a half-specified call would silently warm NOTHING the
            # caller asked for — the mid-epoch stall this API exists to
            # prevent would land anyway
            raise ValueError(
                "Trainer.warmup needs BOTH shapes and block for the "
                "full forward/backward/step warmup (got only "
                f"{'shapes' if shapes is not None else 'block'}); call "
                "warmup() with neither to AOT-resolve just the fused "
                "step")
        if block is None:
            from .parameter import DeferredInitializationError

            try:
                self._warmup_fused()
            except DeferredInitializationError:
                pass  # shapes unknown until first forward: nothing to AOT
            return 0
        from .. import autograd, ndarray as nd, random as _mxrandom

        shapes = [tuple(s) for s in shapes]
        params = list(block.collect_params().values())
        if shapes and any(p._ndarray is None for p in params):
            # deferred-init params materialize on the first forward,
            # drawing initializer keys from the global stream — run that
            # forward NOW (grad/train modes off: no dropout draws, no BN
            # stat updates) so the snapshot below lands post-init, the
            # same stream position the first real forward would leave
            with autograd.pause(train_mode=False):
                block(nd.zeros(shapes[0]))
            params = list(block.collect_params().values())
        for p in self._params:
            if p not in params:
                params.append(p)
        # device step-state is authoritative while fused stepping (loss
        # scale, skip-drifted update count): pull it into the host
        # mirrors FIRST, or the snapshots below would capture — and the
        # restore would resurrect — stale pre-sync values
        self._sync_fused_state()
        self._invalidate_fused_state()
        # param buffers are donated only under MXNET_FUSED_STEP_DONATE —
        # copy then; refs suffice otherwise (jax arrays are immutable).
        # Optimizer-state buffers are ALWAYS donated by the fused step,
        # so their snapshot must be device copies (state_copy).
        copy_params = _fs.donate_params_enabled()
        snap_params = [(p,
                        jnp.array(p._ndarray._data, copy=True)
                        if copy_params else p._ndarray._data,
                        None if p._ndarray._grad is None
                        else p._ndarray._grad._data) for p in params
                       if getattr(p, "_ndarray", None) is not None]
        optim = self._optimizer
        snap_optim = (optim.num_update, optim.begin_num_update,
                      dict(optim._index_update_count))
        if not self._states_created:
            self._create_states()
        snap_states = [_fs.state_copy(s) for s in self._states]
        scaler = getattr(self, "_amp_loss_scaler", None)
        snap_scaler = None if scaler is None else \
            (scaler._loss_scale, scaler._unskipped)
        snap_skips = self._fused_skips_host
        snap_key = _mxrandom._STATE.key
        count = 0
        try:
            for shape in shapes:
                x = nd.zeros(tuple(shape))
                with autograd.record():
                    y = block(x)
                    outs = y if isinstance(y, (list, tuple)) else [y]
                    loss = outs[0].sum()
                    for o in outs[1:]:
                        loss = loss + o.sum()
                loss.backward()
                self.step(batch_size=max(int(shape[0]), 1)
                          if shape else 1)
                count += 1
        finally:
            for p, data, grad in snap_params:
                p._ndarray._data = data
                if grad is not None and p._ndarray._grad is not None:
                    p._ndarray._grad._data = grad
            (optim.num_update, optim.begin_num_update, counts) = snap_optim
            optim._index_update_count = counts
            for s, data in zip(self._states, snap_states):
                _fs.rebind_state(s, data)
            if scaler is not None:
                scaler._loss_scale, scaler._unskipped = snap_scaler
            self._invalidate_fused_state()
            self._fused_skips_host = snap_skips
            _mxrandom._STATE.key = snap_key
        return count

    def _warmup_fused(self):
        """Resolve (disk-load or AOT-compile) the fused-step executable
        without executing it. No-op when the fused path cannot serve the
        current parameter group."""
        if not _fs.fused_step_enabled() or self._fused_broken:
            return False
        kern = self._optimizer._fused_kernel()
        if kern is None:
            return False
        if not self._states_created:
            self._create_states()
        kernel_key, kernel = kern
        scaler = getattr(self, "_amp_loss_scaler", None)
        scaler_cfg = None if scaler is None else \
            (float(scaler._scale_factor), int(scaler._scale_window))
        donate_params = _fs.donate_params_enabled()
        group = self._fused_group(kernel_key, scaler_cfg, donate_params)
        if group == "empty" or group is None:
            return False
        entry = self._fused_entry(group, kernel, scaler_cfg,
                                  donate_params)
        st = self._ensure_fused_state(scaler)
        pv = tuple(p._ndarray._data for p in group["params"])
        gv = tuple(g._data for g in group["grads"])
        sv = tuple(_fs.state_data(s) for s in group["states"])
        if group.get("shard_cfg") is not None:
            pv, gv, sv = group["shard_cfg"].place_args(
                pv, gv, sv, donate_params)
        n = len(group["work"])
        entry.prepare((pv, gv, sv, st["vals"],
                       jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.float32),
                       jnp.float32(1.0)))
        return True

    # -- stepping -----------------------------------------------------------

    def step(self, batch_size, ignore_stale_grad=False):
        """Rescale by 1/batch_size, allreduce, overflow-check, update —
        as ONE compiled donated executable on the fused path (reference:
        trainer.py step + amp/loss_scaler.py skip-step via
        multi_all_finite). With an AMP loss scaler attached
        (amp.init_trainer), gradients are additionally divided by the
        loss scale and the whole step is skipped on overflow; the
        scale's grow/backoff state lives on device (no host round-trip)
        and is synced back on ``scaler.loss_scale`` reads/save_states."""
        scaler = getattr(self, "_amp_loss_scaler", None)
        # allreduce BEFORE the overflow check — for the eager AND fused
        # paths alike: every worker then sees the same reduced gradients
        # and takes the same skip/apply branch (a local check would
        # desync workers and hang the next collective). It runs HERE,
        # once, so a fused executable that fails mid-flight cannot lead
        # to a second reduction on the eager fallback. Multi-process
        # host_local<->global array conversion can't live inside jit, so
        # the collective runs as its own compiled program between
        # backward and the fused update; single process it is a no-op.
        self.allreduce_grads()
        if _fs.fused_step_enabled() and not self._fused_broken and \
                self._fused_step(batch_size, scaler):
            return
        if self._fused_state is not None:
            # fused was active earlier (env toggle / bypass): device
            # state is authoritative — pull it back before eager math
            self._sync_fused_state()
            self._invalidate_fused_state()
        rescale = self._scale / batch_size
        if scaler is not None:
            if scaler.has_overflow(self._params):
                scaler.update_scale(True)
                return  # skip the update entirely
            # divide by the CURRENT scale (the one the loss was multiplied
            # by); grow the scale only after the step is applied
            rescale = rescale / scaler.loss_scale
        self._optimizer.rescale_grad = rescale
        self.update(batch_size, ignore_stale_grad=ignore_stale_grad,
                    _skip_rescale=True)
        self._optimizer.rescale_grad = self._scale
        if scaler is not None:
            scaler.update_scale(False)

    def update(self, batch_size, ignore_stale_grad=False,
               _skip_rescale=False):
        if not _skip_rescale:
            self._optimizer.rescale_grad = self._scale / batch_size
        if not self._states_created:
            self._create_states()
        for i, p in enumerate(self._params):
            if p.grad_req == "null":
                continue
            self._optimizer.update_multi_precision(i, p.data(), p.grad(),
                                                   self._states[i])

    def zero_grad(self):
        for p in self._params:
            p.zero_grad()

    def save_states(self, fname):
        """Reference: trainer.py save_states (optimizer state incl. kvstore
        resident state). The AMP loss-scaler state rides along, and any
        device-resident fused-step state is synced into the host mirrors
        first."""
        assert self._optimizer is not None
        if not self._states_created:
            self._create_states()
        # speculation from a backward that already ran must not
        # straddle the capture boundary (see _abandon_speculation)
        self._abandon_speculation()
        self._sync_fused_state()
        import pickle

        from .. import ndarray as nd

        def dump(v):
            if isinstance(v, nd.NDArray):
                # checkpointing is an intentional full sync, off the
                # step loop's hot path
                return ("nd", v.asnumpy())  # graft-lint: allow(L401)
            if isinstance(v, tuple):
                return ("tuple", tuple(dump(s) for s in v))
            return ("raw", v)

        payload = {"num_update": self._optimizer.num_update,
                   "states": [dump(s) for s in self._states]}
        scaler = getattr(self, "_amp_loss_scaler", None)
        if scaler is not None:
            payload["loss_scaler"] = {"loss_scale": scaler._loss_scale,
                                      "unskipped": scaler._unskipped}
        with open(fname, "wb") as f:
            pickle.dump(payload, f)

    def load_states(self, fname):
        import pickle

        # restoring over a round whose backward already dispatched
        # speculative reductions: drop them, or the next step() flush
        # would bind pre-restore gradient math into the restored state
        self._abandon_speculation()
        with open(fname, "rb") as f:
            payload = pickle.load(f)

        # shared walk (fused_step.state_tree_restore): rebuilds the
        # tagged tree AND launders every buffer through state_adopt —
        # the fused step donates state buffers, and only computation
        # outputs (not raw device_put uploads) donate safely
        self._states = [_fs.state_tree_restore(s)
                        for s in payload["states"]]
        self._states_created = True
        self._optimizer.num_update = payload["num_update"]
        self._optimizer.begin_num_update = payload["num_update"]
        scaler_state = payload.get("loss_scaler")
        scaler = getattr(self, "_amp_loss_scaler", None)
        if scaler_state is not None and scaler is not None:
            scaler._loss_scale = float(scaler_state["loss_scale"])
            scaler._unskipped = int(scaler_state["unskipped"])
        # device step-state is stale now; re-seed from the restored host
        # values on the next fused step
        self._invalidate_fused_state()
