"""InferenceSession: an exported/hybridizable Block as a serving engine.

Turns a model — a hybridizable ``gluon.Block``, or an exported
``*-symbol.json`` + ``*.params`` pair via :meth:`InferenceSession.load`
(reference analog: the MXNet model-server loading ``SymbolBlock.imports``
artifacts) — into a fixed set of **bucket executables**: one AOT-compiled
XLA program per configured batch size. Requests of any batch size are
padded up to the smallest covering bucket and outputs sliced back, the
``MXNET_SHAPE_BUCKETS`` discipline (round 9) applied to whole-model
inference, so a variable request stream never retraces.

Eval-mode contract: forward runs under ``autograd.pause
(train_mode=False)`` — no tape, no BatchNorm stat updates, dropout off —
and parameter mutation during the trace is dropped with a one-time
warning (a serving forward must be side-effect free). Outputs must be
batch-major and row-independent (output row i depends on input row i
only), which every standard inference head satisfies; padding is
zero-fill and padded rows are sliced off before anyone reads them.

Warm start: each bucket executable is resolved through the persistent
compile cache (``utils/compile_cache.py``) under a fingerprint of the
model's symbol-graph JSON + parameter/input avals + AMP version. A warm
process deserializes every bucket at :meth:`warmup` — **zero traces,
zero XLA compiles** before the first request, verifiable via
``profiler.compile_cache_counters()['retraces']``. Models that cannot
symbol-trace fall back to memory-only executables (first process pays
the compile; correctness unchanged).

Round 16 — stateful incremental decode: a session constructed with
``state_shapes=`` compiles a **step executable** instead, the pure
function ``(params, key, inputs, states) -> (outputs, new_states)``
with the state arguments DONATED (state-in/state-out at zero copies)
and bucketed on **batch occupancy** — how many live sequences ride
this step — so one AOT program serves any batch membership of the
continuous batcher. Step executables are fingerprinted with a
state-shape salt (kind ``serving_step``), so stateless and stateful
artifacts of the same graph never collide on disk. The block contract:
``forward(*inputs, *states)`` returns the flat tuple
``(*outputs, *new_states)`` — exactly what ``RecurrentCell``-style
cells emit. :meth:`step` is the single-process API;
``DynamicBatcher`` drives :meth:`_run_step` directly with slots
gathered from the session's :class:`~.state.SessionStateStore`.
"""
from __future__ import annotations

import hashlib
import logging
import threading
import time

import numpy as onp

from .. import autograd
from .. import ndarray as nd
from ..base import MXNetError
from ..ndarray import NDArray
from .. import random as mxrandom
from ..artifact import CompiledArtifact
from ..utils import compile_cache as cc
from ..utils import locks as _locks
from .metrics import METRICS

__all__ = ["InferenceSession", "parse_buckets"]


def parse_buckets(raw, max_batch):
    """Batch-size buckets from an ``MXNET_SERVING_BUCKETS``-style spec:
    ``pow2`` (default) — powers of two up to ``max_batch``; ``mult:N`` —
    multiples of N up to ``max_batch``; or an explicit comma list
    ("1,4,16,64"). Always includes ``max_batch`` itself and is returned
    sorted ascending."""
    raw = (raw or "pow2").strip()
    buckets = set()
    if raw == "pow2":
        b = 1
        while b < max_batch:
            buckets.add(b)
            b <<= 1
    elif raw.startswith("mult:"):
        try:
            n = int(raw.split(":", 1)[1])
        except ValueError:
            n = 0
        if n < 1:
            raise MXNetError(
                f"invalid bucket spec {raw!r} (expected mult:N, N >= 1)")
        buckets.update(range(n, max_batch, n))
    else:
        try:
            buckets.update(int(tok) for tok in raw.split(",") if tok.strip())
        except ValueError:
            raise MXNetError(
                f"invalid bucket spec {raw!r} (expected pow2 | mult:N | "
                "comma list)") from None
        if any(b < 1 for b in buckets):
            raise MXNetError(f"bucket sizes must be >= 1 (got {raw!r})")
        # explicit lists fail fast instead of silently dropping
        # entries the operator configured (generated specs cap quietly)
        too_big = sorted(b for b in buckets if b > max_batch)
        if too_big:
            raise MXNetError(
                f"explicit bucket(s) {too_big} exceed max_batch "
                f"{max_batch}; raise MXNET_SERVING_MAX_BATCH or drop "
                "them")
    buckets.add(int(max_batch))
    return sorted(b for b in buckets if b <= max_batch)


class _InputSpec:
    """One data input: name + per-row (batch-less) shape + dtype."""

    __slots__ = ("name", "row_shape", "dtype")

    def __init__(self, name, row_shape, dtype):
        self.name = name
        self.row_shape = tuple(int(d) for d in row_shape)
        self.dtype = onp.dtype(dtype)

    def __repr__(self):
        return (f"_InputSpec({self.name!r}, (N, "
                f"{', '.join(map(str, self.row_shape))}), {self.dtype})")


class _BucketEntry:
    """One resolved bucket: the executable + its provenance."""

    __slots__ = ("bucket", "amp_ver", "fn", "num_outputs", "from_disk")

    def __init__(self, bucket, amp_ver, fn, num_outputs, from_disk):
        self.bucket = bucket
        self.amp_ver = amp_ver
        self.fn = fn
        self.num_outputs = num_outputs
        self.from_disk = from_disk


class InferenceSession:
    """Eval-mode, no-tape, bucket-compiled forward over a Block.

    Parameters
    ----------
    block : gluon.Block
        The model. Parameters must be initialized, or initializable
        from one eager forward over a zeros example.
    example : NDArray / numpy array / tuple of them, optional
        Example input(s) — batch axis first — from which per-input row
        shapes and dtypes are taken. Exactly one of ``example`` /
        ``input_shapes`` is required.
    input_shapes : sequence of shape tuples, optional
        Full input shapes INCLUDING a (placeholder) batch axis, e.g.
        ``[(1, 784)]``; dtype float32 unless ``input_dtypes`` is given.
    input_dtypes : sequence of dtypes, optional
    buckets : sequence of int, optional
        Batch-size buckets to compile. Default: the
        ``MXNET_SERVING_BUCKETS`` policy over ``MXNET_SERVING_MAX_BATCH``.
    max_batch : int, optional
        Upper bucket bound (default ``MXNET_SERVING_MAX_BATCH``).
        Larger requests are chunked.
    warm : bool
        Resolve every bucket executable in the constructor (AOT compile
        or disk deserialize). ``warm=False`` defers each bucket to its
        first request.
    state_shapes : sequence of shape tuples, optional
        Per-state ROW shapes (no batch axis) the block threads —
        ``RecurrentCell.state_row_shapes()`` emits them. Makes the
        session STATEFUL: it compiles occupancy-bucketed step
        executables and owns a :class:`~.state.SessionStateStore`
        (see :meth:`step`); :meth:`predict` is disabled.
    state_dtypes : sequence of dtypes, optional (default float32)
    state_store : SessionStateStore, optional
        Share an existing store instead of constructing one (canary
        versions of one model each get their own by default).
    """

    def __init__(self, block, example=None, input_shapes=None,
                 input_dtypes=None, buckets=None, max_batch=None,
                 warm=True, label=None, state_shapes=None,
                 state_dtypes=None, state_store=None):
        from .. import env as _env

        self._block = block
        # display label for breaker names / repository healthz (the
        # ModelRepository passes "name@vN" so operators can tell WHICH
        # model's bucket degraded)
        self.label = label
        # guards: _entries, _breakers, _demoted, _artifact_fps, _num_outputs
        self._lock = _locks.RankedLock("serving.session")
        self._entries = {}  # (bucket, amp_ver) -> _BucketEntry
        self._breakers = {}  # (bucket, amp_ver) -> CircuitBreaker
        self._demoted = set()  # (bucket, amp_ver) forced to the jit path
        self._artifact_fps = set()  # fingerprints resolved this process
        self._num_outputs = None
        self._mutation_warned = False
        max_batch = int(max_batch or _env.get_int(
            "MXNET_SERVING_MAX_BATCH", 32))
        if buckets is None:
            buckets = parse_buckets(
                _env.get_str("MXNET_SERVING_BUCKETS"), max_batch)
        self.buckets = sorted(int(b) for b in set(buckets))
        if not self.buckets or self.buckets[0] < 1:
            raise MXNetError("buckets must be a non-empty set of "
                             f"positive batch sizes (got {buckets})")
        self._input_specs = self._resolve_input_specs(
            example, input_shapes, input_dtypes)
        self._state_specs = []
        self.state_store = None
        self._owns_store = False
        self._step_entries = {}  # (occupancy, amp_ver) -> _BucketEntry
        self._step_jitted_by_ver = {}
        if state_store is not None or state_shapes is not None:
            from .state import SessionStateStore

            if state_store is not None:
                self.state_store = state_store
                state_shapes = state_store.state_shapes
                if state_dtypes is None:
                    state_dtypes = [str(dt)
                                    for dt in state_store.state_dtypes]
            dts = state_dtypes or ["float32"] * len(state_shapes)
            self._state_specs = [
                _InputSpec(f"state{i}", s, dt)
                for i, (s, dt) in enumerate(zip(state_shapes, dts))]
            if self.state_store is None:
                # blocks that declare KV-cache rows (state_row_pageable)
                # opt those rows into paged storage — active only when
                # MXNET_SERVING_STATE_PAGE_TOKENS is set
                pageable = None
                proto = getattr(block, "state_row_pageable", None)
                if callable(proto):
                    flags = list(proto())
                    if len(flags) == len(state_shapes):
                        pageable = flags
                self.state_store = SessionStateStore(
                    state_shapes, dts, pageable=pageable, label=label)
                self._owns_store = True
        self._ensure_initialized()
        self._param_list = [p for _, p in
                            sorted(block.collect_params().items())]
        self._param_names = [name for name, _ in
                             sorted(block.collect_params().items())]
        self._param_vals = [p._ndarray._data for p in self._param_list]
        self._graph_sig = self._graph_signature()
        self._jitted_by_ver = {}
        self._shard = None  # set by shard_params(): tensor-parallel mode
        if warm:
            self.warmup()

    # -- construction helpers -----------------------------------------

    @classmethod
    def load(cls, prefix, input_names=None, epoch=0, input_shapes=None,
             **kwargs):
        """Build a session from an exported model: ``{prefix}-symbol.json``
        + ``{prefix}-{epoch:04d}.params`` (the ``Block.export`` layout).
        ``input_names=None`` infers the data inputs as the graph
        variables not present in the params file (SymbolBlock.imports
        loader glue)."""
        import os

        from ..gluon.block import SymbolBlock

        symbol_file = f"{prefix}-symbol.json"
        param_file = f"{prefix}-{epoch:04d}.params"
        if not os.path.exists(param_file):
            # a session over uninitialized params can only serve
            # garbage or die later with a cryptic deferred-init error —
            # name the operator's actual mistake (prefix/epoch) here
            raise MXNetError(
                f"params file {param_file!r} not found (Block.export "
                "writes {prefix}-{epoch:04d}.params; check prefix and "
                "epoch)")
        block = SymbolBlock.imports(symbol_file, input_names, param_file)
        return cls(block, input_shapes=input_shapes, **kwargs)

    def _resolve_input_specs(self, example, input_shapes, input_dtypes):
        if (example is None) == (input_shapes is None):
            raise MXNetError("exactly one of example= / input_shapes= "
                             "is required")
        names = [getattr(i, "name", f"data{k}") for k, i in
                 enumerate(getattr(self._block, "_inputs", []))] or None
        specs = []
        if example is not None:
            if not isinstance(example, (list, tuple)):
                example = [example]
            for k, ex in enumerate(example):
                arr = ex.asnumpy() if isinstance(ex, NDArray) else \
                    onp.asarray(ex)
                if arr.ndim < 1:
                    raise MXNetError("example inputs must carry a batch "
                                     "axis")
                name = names[k] if names and k < len(names) else f"data{k}"
                specs.append(_InputSpec(name, arr.shape[1:], arr.dtype))
        else:
            input_dtypes = input_dtypes or ["float32"] * len(input_shapes)
            for k, (shape, dt) in enumerate(zip(input_shapes,
                                                input_dtypes)):
                if len(shape) < 1:
                    raise MXNetError("input_shapes entries must include "
                                     "the batch axis")
                name = names[k] if names and k < len(names) else f"data{k}"
                specs.append(_InputSpec(name, tuple(shape)[1:], dt))
        return specs

    def _ensure_initialized(self):
        params = self._block.collect_params()
        if all(p._ndarray is not None for p in params.values()):
            return
        # one throwaway eager forward over zeros finishes deferred init
        # (a stateful block's forward also takes its state tensors)
        zeros = [nd.zeros((1,) + s.row_shape, dtype=str(s.dtype))
                 for s in self._input_specs + self._state_specs]
        with autograd.pause(train_mode=False):
            self._block.forward(*zeros)

    def _graph_signature(self):
        """Process-stable model identity for the disk fingerprint: the
        nnvm JSON of the model's symbol graph (SymbolBlock carries it;
        other blocks are traced through the F=sym namespace, the
        ``export`` path). None when the block cannot symbol-trace —
        those sessions compile per process (memory-only executables)."""
        from .. import name as _name_mod
        from .. import symbol as sym
        from ..gluon.block import SymbolBlock

        try:
            if isinstance(self._block, SymbolBlock):
                return self._block._outputs.tojson()
            # a FRESH NameManager makes op-node names deterministic
            # (counter starts at zero per trace): the same model yields
            # the same JSON in every process — and on every re-trace —
            # so warm starts actually hit. Explicit names (param/input
            # variables) pass through untouched.
            with _name_mod.NameManager():
                out = self._block(*[sym.var(s.name)
                                    for s in self._input_specs
                                    + self._state_specs])
            if isinstance(out, (list, tuple)):
                out = sym.Group(list(out))
            return out.tojson()
        except Exception:
            return None

    # -- the pure function every bucket compiles ----------------------

    def _pure(self, param_vals, key, input_datas):
        """(param values, PRNG key, input arrays) -> tuple of output
        arrays; eval mode, no tape. The CachedOp._pure pattern without
        the mutation return path: serving forwards must be side-effect
        free, so trace-time parameter mutation is dropped (warned
        once)."""
        pnds = [p._ndarray for p in self._param_list]
        saved = [p._data for p in pnds]
        try:
            for p, v in zip(pnds, param_vals):
                p._data = v
            with autograd.pause(train_mode=False), \
                    mxrandom.key_provider(key):
                args = [NDArray(d) for d in input_datas]
                outs = self._block.forward(*args)
            if isinstance(outs, NDArray):
                flat = [outs]
            else:
                flat = [o for o in outs]
            # runs only while tracing, which _entry does under _lock
            self._num_outputs = len(flat)  # graft-lint: allow(L1102)
            if not self._mutation_warned and any(
                    p._data is not v
                    for p, v in zip(pnds, param_vals)):
                self._mutation_warned = True
                logging.warning(
                    "InferenceSession: forward mutated parameters "
                    "during the eval-mode trace; serving drops the "
                    "mutation (side-effect-free contract)")
            return tuple(o.data for o in flat)
        finally:
            for p, v in zip(pnds, saved):
                p._data = v

    def _pure_step(self, param_vals, key, input_datas, state_datas):
        """The stateful decode step :meth:`_pure` — ``(params, key,
        inputs, states) -> (*outputs, *new_states)`` flat. The state
        argument is DONATED by the compiled wrapper, so the block's
        new states reuse the old states' device buffers (state-in/
        state-out at zero copies); callers must hand in computation
        outputs, never device_put uploads (the fused_step.state_adopt
        laundering rule)."""
        pnds = [p._ndarray for p in self._param_list]
        saved = [p._data for p in pnds]
        try:
            for p, v in zip(pnds, param_vals):
                p._data = v
            with autograd.pause(train_mode=False), \
                    mxrandom.key_provider(key):
                args = [NDArray(d) for d in input_datas]
                sargs = [NDArray(d) for d in state_datas]
                outs = self._block.forward(*args, *sargs)
            flat = [outs] if isinstance(outs, NDArray) else list(outs)
            n_states = len(self._state_specs)
            if len(flat) <= n_states:
                raise MXNetError(
                    f"stateful forward returned {len(flat)} value(s); "
                    f"expected outputs followed by {n_states} new "
                    "state(s)")
            # runs only while tracing, under _step_entry's lock
            self._num_outputs = len(flat) - n_states  # graft-lint: allow(L1102)
            return tuple(o.data for o in flat)
        finally:
            for p, v in zip(pnds, saved):
                p._data = v

    # -- bucket resolution --------------------------------------------

    def _amp_version(self):
        from ..ndarray import registry as _op_registry

        return _op_registry.amp_version()

    def _jitted_for(self, amp_ver):
        """One jitted object PER AMP VERSION: ``jit(...).lower`` caches
        traces by aval, so re-lowering one shared jitted function after
        an ``amp.init()``/``disable()`` flip would replay the stale
        jaxpr — old casts baked in. A fresh function object per version
        gets a fresh trace cache (the CachedOp static-amp_ver pattern,
        without changing the executable's call signature)."""
        jf = self._jitted_by_ver.get(amp_ver)
        if jf is None:
            def pure(param_vals, key, input_datas):
                """Serving forward (AMP policy version %d)."""
                return self._pure(param_vals, key, input_datas)

            pure.__doc__ = pure.__doc__ % amp_ver
            jf = cc.counting_jit(pure, label="serving")
            self._jitted_by_ver[amp_ver] = jf
        return jf

    def _step_jitted_for(self, amp_ver):
        """The step-executable analog of :meth:`_jitted_for`, with the
        state argument donated: each decode step's new states reuse
        the previous states' buffers instead of growing the pool's
        working set per step."""
        jf = self._step_jitted_by_ver.get(amp_ver)
        if jf is None:
            def pure_step(param_vals, key, input_datas, state_datas):
                """Serving decode step (AMP policy version %d)."""
                return self._pure_step(param_vals, key, input_datas,
                                       state_datas)

            pure_step.__doc__ = pure_step.__doc__ % amp_ver
            jf = cc.counting_jit(pure_step, label="serving_step",
                                 donate_argnums=(3,))
            self._step_jitted_by_ver[amp_ver] = jf
        return jf

    def _graph_op_bodies(self):
        """The registered op functions the graph's nodes dispatch to —
        their bytecode digests salt the fingerprint (the round-9 rule:
        editing an op implementation must invalidate disk entries, not
        silently serve the old math)."""
        import json as _json

        from ..ndarray import _CAMEL_ALIASES
        from ..ndarray.registry import get_op

        bodies = []
        try:
            nodes = _json.loads(self._graph_sig)["nodes"]
        except Exception:
            return bodies
        for opname in sorted({n.get("op") or "null" for n in nodes}):
            if opname == "null":
                continue
            opdef = get_op(_CAMEL_ALIASES.get(opname, opname))
            if opdef is not None:
                bodies.append(opdef.fn)
        return bodies

    def _artifact(self, bucket, amp_ver):
        """The :class:`CompiledArtifact` for a bucket executable. Salt
        composition is declarative: graph-opt rewrites, a plan-sharded
        snapshot (GSPMD collectives baked in), and int8 lowering all
        change the lowered program without changing the source graph
        signature, so their providers fold into the fingerprint. A
        graph that cannot symbol-trace is memory-only (key None)."""
        if self._graph_sig is None:
            return CompiledArtifact("serving", None)
        from ..gluon.block import SymbolBlock

        key = ("serving", hashlib.sha256(
            self._graph_sig.encode()).hexdigest(),
            tuple(self._param_names),
            tuple((tuple(v.shape), str(v.dtype))
                  for v in self._param_vals),
            tuple((s.name, (bucket,) + s.row_shape, str(s.dtype))
                  for s in self._input_specs),
            amp_ver, bucket)
        code_of = [type(self)._pure, type(self._block).forward]
        code_of.extend(self._graph_op_bodies())
        return CompiledArtifact(
            "serving", key, code_of=tuple(code_of),
            salts=("graph_opt", "sharding", "quantize", "autotune"),
            salt_ctx={
                "optimizable": isinstance(self._block, SymbolBlock),
                "shard": self._shard,
                "graph_signature": self._graph_sig,
            })

    def _fingerprint(self, bucket, amp_ver):
        """Hex fingerprint of the bucket executable's artifact; None
        for a memory-only session (no graph signature)."""
        return self._artifact(bucket, amp_ver).fingerprint

    def _avals(self, bucket):
        import jax

        sds = jax.ShapeDtypeStruct
        # shape/dtype of a PRNG key WITHOUT drawing one: warmup must not
        # advance the ambient eager stream (PRNG neutrality, cf. the
        # round-9 Trainer.warmup contract)
        key = jax.random.PRNGKey(0)
        if self._shard is not None:
            rep = self._shard["rep"]
            param_avals = [sds(v.shape, v.dtype, sharding=sh)
                           for v, sh in zip(self._param_vals,
                                            self._shard["shardings"])]
            key_aval = sds(key.shape, key.dtype, sharding=rep)
            input_avals = [sds((bucket,) + s.row_shape, s.dtype,
                               sharding=rep)
                           for s in self._input_specs]
        else:
            param_avals = [sds(v.shape, v.dtype)
                           for v in self._param_vals]
            key_aval = sds(key.shape, key.dtype)
            input_avals = [sds((bucket,) + s.row_shape, s.dtype)
                           for s in self._input_specs]
        return param_avals, key_aval, input_avals

    def _entry(self, bucket):
        """The resolved executable for ``bucket`` under the CURRENT AMP
        policy (an ``amp.init()``/``disable()`` between calls re-resolves
        — AMP casts are baked into the trace, like CachedOp)."""
        amp_ver = self._amp_version()
        # double-checked: lock-free hit, miss re-checks under _lock
        ent = self._entries.get((bucket, amp_ver))  # graft-lint: allow(L1102)
        if ent is not None:
            return ent
        with self._lock:
            ent = self._entries.get((bucket, amp_ver))
            if ent is not None:
                return ent
            art = self._artifact(bucket, amp_ver)
            # meta is a callable: num_outputs is only known after the
            # trace runs (a warm process reads it from the envelope of
            # an executable it never traced)
            fn, meta, source = art.resolve(
                self._jitted_for(amp_ver), self._avals(bucket),
                # the meta lambda runs inside art.resolve, i.e.
                # under the _lock block that encloses this call
                meta=lambda: {"num_outputs":
                              self._num_outputs})  # graft-lint: allow(L1102)
            from_disk = source != "compile"
            if art.fingerprint is not None:
                self._artifact_fps.add(art.fingerprint)
            if from_disk:
                METRICS.bump("warm_disk_hits")
                if self._num_outputs is None:
                    self._num_outputs = meta.get("num_outputs")
            else:
                METRICS.bump("warm_compiles")
            ent = _BucketEntry(bucket, amp_ver, fn,
                               self._num_outputs, from_disk)
            self._entries[(bucket, amp_ver)] = ent
            return ent

    def _step_artifact(self, occupancy, amp_ver):
        """The :meth:`_artifact` analog for step executables, kind
        ``serving_step`` with a **state-shape salt**: the same graph
        served stateless and stateful lowers different programs (state
        threading + donation), so their disk artifacts must never
        collide. No sharding provider — the step path is single-device
        by construction (``shard_params`` rejects stateful sessions)."""
        if self._graph_sig is None:
            return CompiledArtifact("serving_step", None)
        from ..gluon.block import SymbolBlock

        key = ("serving_step", hashlib.sha256(
            self._graph_sig.encode()).hexdigest(),
            tuple(self._param_names),
            tuple((tuple(v.shape), str(v.dtype))
                  for v in self._param_vals),
            tuple((s.name, (occupancy,) + s.row_shape, str(s.dtype))
                  for s in self._input_specs),
            ("state",) + tuple(
                (s.name, (occupancy,) + s.row_shape, str(s.dtype))
                for s in self._state_specs),
            amp_ver, occupancy)
        code_of = [type(self)._pure_step, type(self._block).forward]
        code_of.extend(self._graph_op_bodies())
        store = self.state_store
        return CompiledArtifact(
            "serving_step", key, code_of=tuple(code_of),
            salts=("graph_opt", "quantize", "paged_state", "autotune"),
            salt_ctx={
                "optimizable": isinstance(self._block, SymbolBlock),
                "graph_signature": self._graph_sig,
                # paged-KV serving knobs re-key step artifacts; a
                # row-slot store contributes the empty salt, keeping
                # every pre-r21 fingerprint stable
                "paged": bool(store is not None and store.paged),
                "page_tokens": getattr(store, "page_tokens", 0),
                "kv_int8": bool(getattr(store, "kv_int8", False)),
            })

    def _step_avals(self, occupancy):
        import jax

        sds = jax.ShapeDtypeStruct
        key = jax.random.PRNGKey(0)
        param_avals = [sds(v.shape, v.dtype) for v in self._param_vals]
        input_avals = [sds((occupancy,) + s.row_shape, s.dtype)
                       for s in self._input_specs]
        state_avals = [sds((occupancy,) + s.row_shape, s.dtype)
                       for s in self._state_specs]
        return (param_avals, sds(key.shape, key.dtype), input_avals,
                state_avals)

    def _step_entry(self, occupancy):
        """The resolved step executable for an occupancy bucket under
        the current AMP policy (the :meth:`_entry` pattern). The step
        path is deliberately breaker-free: a systemic step failure
        fails the whole decode batch loudly in the batcher rather than
        demoting a bucket, and mixing step keys into ``_breakers``
        would poison ``degraded``'s sort."""
        amp_ver = self._amp_version()
        ent = self._step_entries.get((occupancy, amp_ver))
        if ent is not None:
            return ent
        with self._lock:
            ent = self._step_entries.get((occupancy, amp_ver))
            if ent is not None:
                return ent
            art = self._step_artifact(occupancy, amp_ver)
            fn, meta, source = art.resolve(
                self._step_jitted_for(amp_ver),
                self._step_avals(occupancy),
                # the meta lambda runs inside art.resolve, i.e.
                # under the _lock block that encloses this call
                meta=lambda: {"num_outputs":
                              self._num_outputs})  # graft-lint: allow(L1102)
            from_disk = source != "compile"
            if art.fingerprint is not None:
                self._artifact_fps.add(art.fingerprint)
            if from_disk:
                METRICS.bump("warm_disk_hits")
                if self._num_outputs is None:
                    self._num_outputs = meta.get("num_outputs")
            else:
                METRICS.bump("warm_compiles")
            ent = _BucketEntry(occupancy, amp_ver, fn,
                               self._num_outputs, from_disk)
            self._step_entries[(occupancy, amp_ver)] = ent
            return ent

    def warmup(self, buckets=None):
        """Resolve every bucket executable now (AOT compile, or disk
        deserialize on a warm start); stateful sessions resolve their
        occupancy-bucketed STEP executables instead. Returns
        ``{"disk_hits": n, "compiles": m}`` for this call."""
        hits = compiles = 0
        resolve = self._step_entry if self._state_specs else self._entry
        for b in (buckets or self.buckets):
            ent = resolve(int(b))
            if ent.from_disk:
                hits += 1
            else:
                compiles += 1
        return {"disk_hits": hits, "compiles": compiles}

    def artifact_fingerprints(self):
        """The fingerprints of every disk-cacheable executable this
        session resolved (buckets and step occupancies, across AMP
        versions) — the set a deployment bundle packs."""
        with self._lock:
            return sorted(self._artifact_fps)

    @property
    def warm(self):
        """True when every configured bucket is resolved under the
        current AMP policy (consistent read under the session lock —
        see :meth:`health_snapshot`)."""
        return self.health_snapshot()["warm"]

    # -- the request path ---------------------------------------------

    @property
    def input_specs(self):
        return list(self._input_specs)

    @property
    def num_outputs(self):
        # write-once value (set at first trace/envelope read); a racy
        # read sees None or the final count, never garbage
        return self._num_outputs  # graft-lint: allow(L1102)

    @property
    def max_batch(self):
        return self.buckets[-1]

    @property
    def stateful(self):
        """True when this session threads server-side state
        (constructed with ``state_shapes=``)."""
        return bool(self._state_specs)

    @property
    def state_specs(self):
        return list(self._state_specs)

    def refresh_params(self):
        """Re-snapshot parameter values from the block (after a live
        weight update). Executables are shape-keyed, so no recompile;
        a sharded session re-places the fresh snapshot at the plan's
        layouts (identity when the trainer already keeps them there)."""
        with self._lock:
            self._param_vals = [p._ndarray._data
                                for p in self._param_list]
            if self._shard is not None:
                self._param_vals = self._place_param_vals(
                    self._param_vals)

    # -- tensor-parallel serving --------------------------------------

    def _place_param_vals(self, vals):
        import jax

        return [v if getattr(v, "sharding", None) == sh
                else jax.device_put(v, sh)
                for v, sh in zip(vals, self._shard["shardings"])]

    def shard_params(self, plan=None, mesh=None):
        """Place the parameter snapshot per a :class:`ShardingPlan` and
        serve tensor-parallel: every bucket executable is (re)compiled
        with the plan's in-shardings, so a model bigger than one device
        serves from ONE sharded AOT program (GSPMD inserts the
        collectives). Defaults to the scoped ``sharding.plan_scope``
        pair. The AOT disk fingerprint is salted with the plan + mesh,
        so sharded and unsharded artifacts never collide; request
        inputs are replicated onto the mesh at upload, so callers keep
        passing plain host arrays. Returns ``self``."""
        from .. import sharding as _sharding

        if self._state_specs:
            raise MXNetError(
                "shard_params is not supported on stateful sessions "
                "(the state pool is single-device; shard the stateless "
                "prefill model instead)")
        if plan is None or mesh is None:
            ctx = _sharding.current_plan()
            if ctx is None:
                raise MXNetError(
                    "shard_params needs a plan: pass plan=/mesh= or "
                    "call inside sharding.plan_scope")
            plan = plan if plan is not None else ctx[0]
            mesh = mesh if mesh is not None else ctx[1]
        shardings = [
            _sharding.named_sharding(
                mesh, plan.spec_for(name, tuple(v.shape), mesh))
            for name, v in zip(self._param_names, self._param_vals)]
        with self._lock:
            self._shard = {
                "mesh": mesh,
                "shardings": shardings,
                "rep": _sharding.replicated(mesh),
                "plan": plan,  # the "sharding" salt provider reads it
            }
            self._param_vals = self._place_param_vals(self._param_vals)
            # compiled-at-old-layout executables (and their demotions)
            # are stale: drop them; the salted fingerprint resolves
            # fresh sharded ones on the next warmup()/request
            self._entries.clear()
            self._demoted.clear()
        _sharding._count("serving_sharded_sessions")
        return self

    @property
    def sharded(self):
        """True when the session serves from a plan-sharded snapshot."""
        return self._shard is not None

    def validate(self, *inputs):
        """Check request inputs against the session's input specs;
        returns (arrays, batch). NDArrays pass through untouched (the
        device-native path); everything else is coerced to a HOST numpy
        array of the spec dtype — deliberately not uploaded here, so
        batchers can coalesce and pad in pure numpy (no per-pattern XLA
        prim compiles) and pay exactly one device transfer per executed
        batch. Raises ``ValueError`` — the per-request failure a
        batcher reports on one future without poisoning its batch."""
        if len(inputs) != len(self._input_specs):
            raise ValueError(
                f"expected {len(self._input_specs)} input(s), got "
                f"{len(inputs)}")
        arrs, batch = [], None
        for x, spec in zip(inputs, self._input_specs):
            if isinstance(x, NDArray):
                # the bucket executables are traced at the spec dtype;
                # a mismatched device array would raise inside the AOT
                # Compiled and permanently degrade that bucket to the
                # jit path — reject it here, per-request
                if onp.dtype(x.dtype) != spec.dtype:
                    raise ValueError(
                        f"input {spec.name!r} dtype {x.dtype} != "
                        f"expected {spec.dtype}")
                arr = x
            else:
                try:
                    arr = onp.asarray(x, dtype=spec.dtype)
                except (TypeError, ValueError) as e:
                    raise ValueError(
                        f"input {spec.name!r} is not convertible to "
                        f"dtype {spec.dtype}: {e}") from None
            if tuple(arr.shape[1:]) != spec.row_shape:
                raise ValueError(
                    f"input {spec.name!r} row shape "
                    f"{tuple(arr.shape[1:])} != expected "
                    f"{spec.row_shape}")
            if batch is None:
                batch = arr.shape[0]
            elif arr.shape[0] != batch:
                raise ValueError("inputs disagree on batch size "
                                 f"({batch} vs {arr.shape[0]})")
            if batch == 0:
                raise ValueError("empty batch")
            arrs.append(arr)
        return arrs, batch

    def _bucket_for(self, n):
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _breaker(self, bucket, amp_ver):
        """The per-bucket circuit breaker (created on first use). One
        breaker per (bucket, AMP version) — an AMP flip re-resolves
        the executable, so its failure history starts clean too."""
        from ..resilience.breaker import CircuitBreaker

        # double-checked: lock-free hit, miss goes through the locked
        # setdefault below
        br = self._breakers.get((bucket, amp_ver))  # graft-lint: allow(L1102)
        if br is None:
            who = f"serving {self.label} " if self.label else "serving "
            with self._lock:
                br = self._breakers.setdefault(
                    (bucket, amp_ver),
                    CircuitBreaker(name=f"{who}bucket {bucket}"))
        return br

    def _record_bucket_failure(self, bucket, amp_ver, err):
        """Serving-side degradation policy: the FIRST failures demote
        the bucket from its AOT/deserialized executable back to the
        plain jit path (a corrupt or stale disk artifact must not
        poison the bucket forever — the jit path retraces fresh);
        failures past the breaker threshold open the circuit and the
        bucket fails fast (CircuitOpen -> HTTP 503) until the cooldown
        admits a probe. ``/healthz`` reflects both states."""
        from ..resilience import _count

        br = self._breaker(bucket, amp_ver)
        br.record_failure()
        key = (bucket, amp_ver)
        # double-checked: the demotion branch re-tests membership under
        # _lock before mutating
        if key not in self._demoted and br.failures >= 2:  # graft-lint: allow(L1102)
            with self._lock:
                ent = self._entries.get(key)
                if ent is not None and key not in self._demoted:
                    self._demoted.add(key)
                    ent.fn = self._jitted_for(amp_ver)
                    ent.from_disk = False
                    _count("breaker_demotions")
                    logging.warning(
                        "serving: bucket %d (amp v%d) failed "
                        "repeatedly (%s: %s); demoted its executable "
                        "to the jit path", bucket, amp_ver,
                        type(err).__name__, err)

    @property
    def degraded(self):
        """Buckets no longer running their AOT executable under the
        CURRENT AMP policy (demoted to the jit path), sorted.
        Snapshot under the lock: /healthz handler threads iterate
        while serving workers insert."""
        amp_ver = self._amp_version()
        with self._lock:
            demoted = set(self._demoted)
        return sorted(b for b, v in demoted if v == amp_ver)

    def breaker_states(self):
        """{bucket: breaker state} under the current AMP policy, for
        buckets that recorded at least one outcome. Snapshot under the
        lock (see ``degraded``)."""
        amp_ver = self._amp_version()
        with self._lock:
            breakers = dict(self._breakers)
        return {b: br.state for (b, v), br in breakers.items()
                if v == amp_ver}

    def health_snapshot(self):
        """One CONSISTENT health view for /healthz probes: warmth,
        demoted buckets, and breaker states read under a single
        acquisition of the session lock. The pre-round-23 surface
        stitched three independent reads (``warm`` / ``degraded`` /
        ``breaker_states``) together, so a probe racing a resolve or a
        demotion could report a bucket simultaneously warm and
        demoted; the L1102 guards audit flagged the lock-free reads as
        allow-pragma'd. Returns ``{"warm", "buckets",
        "degraded_buckets", "breaker_states", "open_buckets"}``."""
        amp_ver = self._amp_version()
        with self._lock:
            entries = self._step_entries if self._state_specs \
                else self._entries
            warm = all((b, amp_ver) in entries for b in self.buckets)
            demoted = set(self._demoted)
            breakers = dict(self._breakers)
        states = {b: br.state for (b, v), br in breakers.items()
                  if v == amp_ver}
        return {
            "warm": warm,
            "buckets": list(self.buckets),
            "degraded_buckets": sorted(
                b for b, v in demoted if v == amp_ver),
            "breaker_states": states,
            "open_buckets": sorted(
                b for b, s in states.items() if s != "closed"),
        }

    def _run_bucket(self, arrs, n):
        """Execute one <=max_batch slice through its bucket executable;
        returns the list of output jax arrays sliced back to ``n``
        rows. Host (numpy) inputs are padded in numpy and uploaded
        ONCE — no shape-dependent eager prims on the request path;
        device (NDArray) inputs pad on device. Failures feed the
        bucket's circuit breaker (see ``_record_bucket_failure``); an
        open breaker fails the request fast with CircuitOpen."""
        from ..resilience import faults as _faults

        bucket = self._bucket_for(n)
        amp_ver = self._amp_version()
        # lock-free fast read on the request path; a miss just means
        # the breaker isn't born yet (first failure creates it under
        # _lock in _breaker)
        br = self._breakers.get((bucket, amp_ver))  # graft-lint: allow(L1102)
        if br is not None:
            br.check()  # open circuit: fail fast (HTTP 503)
        # EVERY failure past the check must reach the breaker — entry
        # resolution, padding/upload, key draw and execution alike. A
        # half-open probe admitted by check() that died without a
        # recorded outcome would leak the probe slot and wedge the
        # bucket in fail-fast forever.
        try:
            from ..kernels import serving_fused as _sf

            ent = self._entry(bucket)
            fuse_pad = _sf.serving_fusion_enabled()
            datas = [None] * len(arrs)
            dev_idx, dev_arrs = [], []
            for i, a in enumerate(arrs):
                if isinstance(a, NDArray):
                    # device inputs: fused path pads ALL of them in
                    # one dispatch; legacy path pays one per input
                    dev_idx.append(i)
                    dev_arrs.append(a.data)
                else:
                    if a.shape[0] != bucket:
                        padded = onp.zeros((bucket,) + a.shape[1:],
                                           a.dtype)
                        padded[:a.shape[0]] = a
                        a = padded
                    datas[i] = nd.array(a).data
            if dev_arrs:
                if fuse_pad:
                    padded = _sf.pad_all(dev_arrs, bucket)
                else:
                    padded = [cc.pad_batch(d, bucket)
                              for d in dev_arrs]
                for i, p in zip(dev_idx, padded):
                    datas[i] = p
            key = mxrandom.next_key()
            if self._shard is not None:
                # inputs ride the mesh replicated (eager arrays commit
                # to one device; the sharded executable wants the full
                # device set) — params are already placed
                import jax

                rep = self._shard["rep"]
                datas = [jax.device_put(d, rep) for d in datas]
                key = jax.device_put(key, rep)
            # registered fault point: one bucket execution on the
            # serving request path
            _faults.maybe_fail("serving_execute")
            out = ent.fn(self._param_vals, key, datas)
        except Exception as e:
            self._record_bucket_failure(bucket, amp_ver, e)
            raise
        self._breaker(bucket, amp_ver).record_success()
        METRICS.bump("bucket_execs")
        METRICS.bump("padded_rows", bucket - n)
        METRICS.bump("true_rows", n)
        if bucket == n:
            return list(out)  # nothing padded: no slice op to pay
        if fuse_pad:
            return _sf.slice_all(list(out), bucket, n)
        return [cc.slice_batch(o, bucket, n) for o in out]

    # -- the stateful decode path -------------------------------------

    def _validate_states(self, states, batch):
        """Check explicit state arrays against the state specs (the
        :meth:`validate` contract applied to states: host arrays stay
        host-side, ``ValueError`` for per-request rejection)."""
        if len(states) != len(self._state_specs):
            raise ValueError(
                f"expected {len(self._state_specs)} state(s), got "
                f"{len(states)}")
        out = []
        for s, spec in zip(states, self._state_specs):
            if isinstance(s, NDArray):
                if onp.dtype(s.dtype) != spec.dtype:
                    raise ValueError(
                        f"state {spec.name!r} dtype {s.dtype} != "
                        f"expected {spec.dtype}")
                arr = s
            else:
                try:
                    arr = onp.asarray(s, dtype=spec.dtype)
                except (TypeError, ValueError) as e:
                    raise ValueError(
                        f"state {spec.name!r} is not convertible to "
                        f"dtype {spec.dtype}: {e}") from None
            if tuple(arr.shape[1:]) != spec.row_shape:
                raise ValueError(
                    f"state {spec.name!r} row shape "
                    f"{tuple(arr.shape[1:])} != expected "
                    f"{spec.row_shape}")
            if arr.shape[0] != batch:
                raise ValueError(
                    f"state {spec.name!r} batch {arr.shape[0]} != "
                    f"input batch {batch}")
            out.append(arr)
        return out

    def _run_step(self, arrs, states, n, adopted=False):
        """Execute one decode step at occupancy ``n`` through its
        occupancy-bucket step executable; returns ``(outputs,
        new_states)`` as jax arrays sliced back to ``n`` rows.

        The state argument is donated into the executable, and
        donating a ``device_put``-uploaded buffer was seen to corrupt
        unrelated live arrays (the fused_step ``state_adopt`` hazard)
        — so host-origin states are laundered through
        ``jnp.array(..., copy=True)`` after upload, making every
        donated buffer an XLA computation output. ``adopted=True`` is
        the batcher's fast path: the states are ``SessionStateStore.
        gather`` outputs (already computation outputs), donated
        as-is."""
        import jax.numpy as jnp

        from ..resilience import faults as _faults

        bucket = self._bucket_for(n)
        ent = self._step_entry(bucket)
        datas = []
        for a in arrs:
            if isinstance(a, NDArray):
                datas.append(cc.pad_batch(a.data, bucket))
            else:
                if a.shape[0] != bucket:
                    padded = onp.zeros((bucket,) + a.shape[1:], a.dtype)
                    padded[:a.shape[0]] = a
                    a = padded
                datas.append(nd.array(a).data)
        sdatas = []
        for s, spec in zip(states, self._state_specs):
            if adopted:
                # gather/pad outputs are computation outputs:
                # donation-safe without laundering
                sdatas.append(s if s.shape[0] == bucket
                              else cc.pad_batch(s, bucket))
                continue
            if isinstance(s, NDArray):
                d = cc.pad_batch(s.data, bucket)
            else:
                if s.shape[0] != bucket:
                    padded = onp.zeros((bucket,) + s.shape[1:], s.dtype)
                    padded[:s.shape[0]] = s
                    s = padded
                d = nd.array(s).data
            sdatas.append(jnp.array(d, copy=True))
        key = mxrandom.next_key()
        # same registered fault point as the stateless request path:
        # one executable invocation on the serving hot path
        _faults.maybe_fail("serving_execute")
        out = ent.fn(self._param_vals, key, datas, sdatas)
        METRICS.bump("bucket_execs")
        METRICS.bump("padded_rows", bucket - n)
        METRICS.bump("true_rows", n)
        outs = list(out[:ent.num_outputs])
        news = list(out[ent.num_outputs:])
        if bucket != n:
            outs = [cc.slice_batch(o, bucket, n) for o in outs]
            news = [cc.slice_batch(s, bucket, n) for s in news]
        return outs, news

    def step(self, *inputs, states):
        """One incremental decode step with EXPLICIT states: ``(one
        row-batch of inputs, current states) -> (outputs, new
        states)``. This is the single-process stateful API (offline
        decode loops, tests, benchmarks); served traffic goes through
        a stateful ``DynamicBatcher``, which keeps states server-side
        in the session's :class:`~.state.SessionStateStore` and only
        ever passes slot gathers. Occupancy above ``max_batch`` is
        rejected (a decode step is never chunked — states would
        cross-talk)."""
        if not self._state_specs:
            raise MXNetError("step() requires a stateful session "
                             "(construct with state_shapes=)")
        arrs, batch = self.validate(*inputs)
        if batch > self.max_batch:
            raise ValueError(
                f"step occupancy {batch} exceeds max_batch "
                f"{self.max_batch}")
        svals = self._validate_states(states, batch)
        t0 = time.perf_counter()
        outs, news = self._run_step(arrs, svals, batch)
        import jax

        jax.block_until_ready(outs + news)
        METRICS.bump("decode_steps")
        METRICS.observe_batch(batch, time.perf_counter() - t0)
        result = tuple(NDArray(o) for o in outs)
        return (result[0] if len(result) == 1 else result,
                [NDArray(s) for s in news])

    def close(self):
        """Release resources a stateful session owns (its state
        store's metrics probe). Stateless sessions: no-op."""
        if self._owns_store and self.state_store is not None:
            self.state_store.close()

    def predict(self, *inputs):
        """Run eval-mode inference. Inputs may be NDArrays or anything
        ``numpy.asarray`` accepts (batch axis first). Batches larger
        than ``max_batch`` are chunked. Returns an NDArray (single
        output) or tuple of NDArrays."""
        if self._state_specs:
            raise MXNetError(
                "predict() is stateless; this session threads state — "
                "use step() or a stateful DynamicBatcher")
        arrs, batch = self.validate(*inputs)
        t0 = time.perf_counter()
        chunks = []
        start = 0
        while start < batch:
            n = min(self.max_batch, batch - start)
            if start == 0 and n == batch:
                chunk = arrs  # whole request fits one bucket: no slice
            else:
                chunk = [NDArray(a.data[start:start + n])
                         if isinstance(a, NDArray) else
                         a[start:start + n] for a in arrs]
            chunks.append(self._run_bucket(chunk, n))
            start += n
        if len(chunks) == 1:
            outs = chunks[0]
        else:
            import jax.numpy as jnp

            outs = [jnp.concatenate([c[i] for c in chunks], axis=0)
                    for i in range(len(chunks[0]))]
        # sync before stamping: jax dispatch is asynchronous, and an
        # unsynced stamp would report enqueue time as exec latency
        import jax

        jax.block_until_ready(outs)
        METRICS.observe_batch(batch, time.perf_counter() - t0)
        result = tuple(NDArray(o) for o in outs)
        return result[0] if len(result) == 1 else result

    def __call__(self, *inputs):
        return self.predict(*inputs)

    def __repr__(self):
        return (f"InferenceSession({type(self._block).__name__}, "
                f"inputs={self._input_specs}, buckets={self.buckets}, "
                f"warm={self.warm})")
