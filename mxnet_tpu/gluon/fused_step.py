"""Compiled fused train-step cache for the Gluon Trainer.

The eager ``Trainer.step`` hot loop is host-driven: one dispatch per
parameter for the optimizer update, a host-syncing AMP overflow check
(``LossScaler.has_overflow``), and — distributed — one collective per
parameter. This module compiles the whole weight-update phase into ONE
jit-compiled XLA executable per parameter-group signature (the
cross-replica weight-update fusion of "Automatic Cross-Replica Sharding
of Weight Update in Data-Parallel Training"; the cross-op fusion XLA is
built for). Per executable, entirely on device:

- device-side all-finite check over the raw gradients with
  ``lax.cond`` skip-step semantics — the check itself never rounds-trip
  to the host (``amp.scale_loss`` still pays ONE lazy scalar sync per
  applied step to learn the scale it must multiply the loss by —
  strictly less than the eager path's full all-finite readback);
- loss-scale grow/backoff folded into the same program (the scale,
  grow counter, skip counter and update count ride in a donated
  device-resident state tuple);
- rescale (1/batch_size · 1/loss_scale) and the multi-tensor optimizer
  update via the optimizer's ``_fused_kernel`` (optimizer/optimizer.py),
  with optimizer-state buffers donated (parameter donation is opt-in via
  ``MXNET_FUSED_STEP_DONATE`` — donation deletes the old buffer, which
  breaks tape nodes / detach() snapshots that still alias it).

Hyperparameters that change at runtime (learning rate, wd, rescale_grad,
loss scale) enter as dynamic scalar/vector arguments, so
``set_learning_rate`` and loss-scale updates never retrace. The cache is
a bounded LRU keyed like the PR-1 eager-dispatch cache: input avals +
optimizer class/static config + AMP version + distributed mode
(``MXNET_FUSED_STEP=0`` falls back to the eager per-param loop;
``MXNET_FUSED_STEP_CACHE_SIZE`` bounds the LRU). Counters surface via
``profiler.fused_step_counters()`` and the ``FUSED_STEP`` runtime
feature flag.
"""
from __future__ import annotations

import weakref

import jax
import jax.numpy as jnp

from ..telemetry import tracer as _telem
from ..utils import compile_cache as _cc
from ..utils.lru import CountedLRUCache

__all__ = ["fused_step_enabled", "fused_step_stats",
           "reset_fused_step_cache"]


def fused_step_enabled():
    """MXNET_FUSED_STEP knob (default on); 0 = eager per-param fallback.
    Read per-step so tests/benchmarks can toggle without reimport."""
    from .. import env as _env

    return _env.get_bool("MXNET_FUSED_STEP", True)


def donate_params_enabled():
    """MXNET_FUSED_STEP_DONATE — OPT-IN (default 0) parameter-buffer
    donation. CPU/TPU donation really deletes the old buffer, which
    breaks any alias still held elsewhere (autograd tape primals for
    double-backward, detach() snapshots, user copies of ``p.data()``
    buffers). Optimizer state and the loss-scale state tuple are
    trainer-internal and always donated."""
    from .. import env as _env

    return _env.get_bool("MXNET_FUSED_STEP_DONATE", False)


class _FusedStepCache(CountedLRUCache):
    """Bounded LRU of jit-compiled fused train-step executables
    (bypasses = unsupported optimizer / sparse grads / tracers;
    fallbacks = compiled step raised and the trainer went eager)."""

    def __init__(self, maxsize=None):
        from .. import env as _env

        super().__init__(maxsize if maxsize is not None else
                         _env.get_int("MXNET_FUSED_STEP_CACHE_SIZE", 16))


_CACHE = _FusedStepCache()

# trainers holding live device step-state, for the skip-step counter
# (the count rides the donated device state tuple — no per-step host
# read — and is summed here on demand)
_TRAINERS = weakref.WeakSet()


def register_trainer(trainer):
    _TRAINERS.add(trainer)


def fused_step_stats():
    """Hit/miss/evict/bypass/fallback counters + AMP skip-step total."""
    st = _CACHE.stats()
    skipped = 0
    for tr in list(_TRAINERS):
        try:
            skipped += tr._fused_skipped_steps()
        except Exception:  # graft-lint: allow(L501)
            pass
    st["skipped_steps"] = skipped
    return st


def reset_fused_step_cache(maxsize=None):
    """Drop all cached executables and counters (tests, benchmarks)."""
    _CACHE.clear()
    if maxsize is not None:
        _CACHE.maxsize = int(maxsize)


# ---------------------------------------------------------------------------
# signatures / state pytree helpers (states are None | NDArray | nested
# tuples thereof, as built by Optimizer.create_state_multi_precision)

def state_sig(s):
    if s is None:
        return None
    if isinstance(s, tuple):
        return tuple(state_sig(x) for x in s)
    return (tuple(s.shape), str(s.data.dtype))


def state_data(s):
    if s is None:
        return None
    if isinstance(s, tuple):
        return tuple(state_data(x) for x in s)
    return s.data


def state_copy(s):
    """Device COPIES of a state tree's buffers (shape of
    ``state_data``). Snapshots that must survive a fused step need
    copies, not refs: the step donates state buffers to XLA, which
    deletes the originals."""
    if s is None:
        return None
    if isinstance(s, tuple):
        return tuple(state_copy(x) for x in s)
    return jnp.array(s.data, copy=True)


def state_adopt(s):
    """Rebind a restored state tree's buffers to device-COMPUTED
    copies, in place; returns the tree.

    Restored optimizer states arrive as ``device_put`` uploads (host
    pickle -> ``nd.array``), and the fused step DONATES state buffers.
    Donating an externally-uploaded buffer was seen to recycle the
    upload's storage while earlier computation outputs still occupied
    it — flaky silent corruption of unrelated live buffers on the steps
    after a ``load_states``/checkpoint restore (caught by the
    resilience bench's bitwise kill-and-resume gate). One ``jnp``
    copy makes every donated buffer an XLA computation output, which
    donates safely on every backend — restores are rare, the copy is
    device-side and cheap."""
    if s is None:
        return None
    if isinstance(s, tuple):
        for x in s:
            state_adopt(x)
        return s
    s._data = jnp.array(s.data, copy=True)
    return s


def state_tree_restore(tree):
    """The ('nd' | 'tuple' | 'raw')-tagged host state tree — the wire
    format ``Trainer.save_states`` and the resilience CheckpointManager
    both emit — rebuilt as a live NDArray state tree with donation-safe
    buffers (``state_adopt`` applied to every array leaf). ONE shared
    walk on purpose: the round-12 donation fix had to land in two
    hand-copied restore closures, which is exactly the divergence this
    helper removes."""
    from .. import ndarray as nd

    tag, val = tree
    if tag == "nd":
        return state_adopt(nd.array(val))
    if tag == "tuple":
        return tuple(state_tree_restore(s) for s in val)
    return val


def rebind_state(old, new):
    """Write the executable's output arrays back into the existing
    NDArray state objects (identity of ``trainer._states`` entries is
    preserved across steps for save_states/user references)."""
    if old is None:
        return
    if isinstance(old, tuple):
        for o, n in zip(old, new):
            rebind_state(o, n)
    else:
        old._data = new


def has_tracer(arrays):
    return any(isinstance(a, jax.core.Tracer) for a in arrays)


# ---------------------------------------------------------------------------
# executable builder

class _FusedEntry:
    """LRU entry wrapping the fused-step executable with lazy disk-tier
    resolution. The first call (or an explicit ``prepare()``) resolves
    it: a serialized executable from a previous process is deserialized
    (no trace, no XLA compile — the warm-start win), else the jitted
    step is AOT-compiled once and written back for future processes.
    Resolution failures degrade to the plain jit path — a corrupt or
    stale cache entry must never break (or permanently eagerize) the
    trainer's step loop."""

    __slots__ = ("_jfn", "_call", "_artifact")

    def __init__(self, jfn, artifact=None):
        self._jfn = jfn
        self._call = None
        self._artifact = artifact

    def prepare(self, args):
        """Resolve without executing (``lower``/``compile`` only) —
        ``Trainer.warmup`` precompiles through this, so warmup has no
        side effects on parameters or optimizer state."""
        if self._call is None:
            self._resolve(args)

    def _resolve(self, args):
        with _telem.span("fused_step.resolve", cat="train") as sp:
            return self._resolve_inner(args, sp)

    def _resolve_inner(self, args, sp):
        art = self._artifact
        if art is not None and art.fingerprint is not None:
            loaded = art.load()
            if loaded is not None:
                sp.set(source=loaded[2])
                self._call = _cc.GuardedCompiled(loaded[0], self._jfn)
                return self._call
            try:
                with _telem.span("fused_step.trace_compile",
                                 cat="train"):
                    compiled = _cc.aot_compile(self._jfn, *args)
            except Exception:
                sp.set(source="jit_fallback")
                self._call = self._jfn
                return self._call
            sp.set(source="compile")
            art.store(compiled)
            self._call = _cc.GuardedCompiled(compiled, self._jfn)
            return self._call
        sp.set(source="jit")
        self._call = self._jfn
        return self._call

    def __call__(self, *args):
        call = self._call or self._resolve(args)
        with _telem.span("fused_step.execute", cat="train"):
            return call(*args)


def build_executable(kernel, mp_flags, scaler_cfg, donate_params,
                     cache_key=None, shard_cfg=None):
    """One donated XLA executable for the whole weight-update phase.

    kernel(w, g, s, lr, wd, rescale, t) -> (w2, s2) is the optimizer's
    fused per-parameter update (optimizer._fused_kernel), closing over
    static hyperparameters only. ``mp_flags[i]`` marks half-precision
    params updated through their fp32 master copy (state = (master,
    base)). ``scaler_cfg`` is None or (scale_factor, scale_window);
    with it the executable carries (t, scale, unskipped, skips) and
    wraps the update in ``lax.cond`` on the device-side all-finite
    check; without it the state is just (t,).

    Signature of the returned jitted function::

        step(params, grads, states, step_state, lrs, wds, rescale)
            -> (new_params, new_states, new_step_state)

    lrs/wds are f32 vectors (one per parameter, host-computed with the
    full lr_mult/wd_mult logic so multipliers never retrace); rescale is
    the f32 scalar self._scale/batch_size. States and step_state are
    donated; params donated only when ``donate_params``.

    ``shard_cfg`` (a ``sharding.FusedShardCfg``, built from the scoped
    ShardingPlan) compiles the SAME program under the mesh: params and
    grads laid out per plan, optimizer state per plan or ZeRO-1, the
    scalar step-state/hyperparameters replicated — GSPMD inserts the
    update-side collectives. Inputs not already resident at those
    layouts are resharded by jit on entry (first step after a restore);
    at steady state outputs feed back at the declared shardings and no
    data moves.
    """

    def apply_all(pvals, gvals, svals, lrs, wds, eff, t1):
        new_p, new_s = [], []
        for i, (w, g, s) in enumerate(zip(pvals, gvals, svals)):
            lr, wd = lrs[i], wds[i]
            if mp_flags[i]:
                # fp32 master update, half-precision weight written back
                # (reference: optimizer.py update_multi_precision)
                master, base = s
                m2, b2 = kernel(master, g.astype(jnp.float32), base,
                                lr, wd, eff, t1)
                new_p.append(m2.astype(w.dtype))
                new_s.append((m2, b2))
            else:
                w2, s2 = kernel(w, g, s, lr, wd, eff, t1)
                new_p.append(w2)
                new_s.append(s2)
        return tuple(new_p), tuple(new_s)

    if scaler_cfg is None:
        def step(pvals, gvals, svals, sstate, lrs, wds, rescale):
            (t,) = sstate
            t1 = t + jnp.int32(1)
            new_p, new_s = apply_all(pvals, gvals, svals, lrs, wds,
                                     rescale, t1)
            return new_p, new_s, (t1,)
    else:
        factor, window = float(scaler_cfg[0]), int(scaler_cfg[1])

        def step(pvals, gvals, svals, sstate, lrs, wds, rescale):
            t, scale, unskipped, skips = sstate
            # overflow check on the RAW (pre-rescale) gradients, exactly
            # like LossScaler.has_overflow over nd.all_finite
            finite = jnp.bool_(True)
            for g in gvals:
                if jnp.issubdtype(g.dtype, jnp.floating):
                    finite = jnp.logical_and(finite,
                                             jnp.all(jnp.isfinite(g)))

            def do_apply(_):
                t1 = t + jnp.int32(1)
                # divide by the CURRENT scale (the one the loss was
                # multiplied by); powers-of-two scales make this bitwise
                # equal to the eager host-side division
                eff = rescale / scale
                new_p, new_s = apply_all(pvals, gvals, svals, lrs, wds,
                                         eff, t1)
                # grow only after the step applied (LossScaler
                # update_scale(False))
                unsk = unskipped + jnp.int32(1)
                grow = unsk >= window
                scale2 = jnp.where(grow, scale * factor, scale)
                unsk2 = jnp.where(grow, jnp.int32(0), unsk)
                return new_p, new_s, (t1, scale2, unsk2, skips)

            def do_skip(_):
                # LossScaler update_scale(True): halve (floor 1.0), and
                # leave params/states/update-count untouched
                scale2 = jnp.maximum(jnp.float32(1.0), scale / factor)
                return (tuple(pvals), tuple(svals),
                        (t, scale2, jnp.int32(0), skips + jnp.int32(1)))

            return jax.lax.cond(finite, do_apply, do_skip, None)

    donate = (0, 2, 3) if donate_params else (2, 3)
    jit_kwargs = {}
    if shard_cfg is not None:
        pshard = tuple(shard_cfg.param_shardings)
        sshard = tuple(shard_cfg.state_shardings)
        srep = tuple(shard_cfg.rep for _ in
                     range(1 if scaler_cfg is None else 4))
        rep = shard_cfg.rep
        jit_kwargs = dict(
            in_shardings=(pshard, pshard, sshard, srep, rep, rep, rep),
            out_shardings=(pshard, sshard, srep))
    # an artifact only when the disk tier is armed (MXNET_COMPILE_CACHE=0
    # must mean the plain jit path, not a no-op GuardedCompiled layer),
    # salted with the bytecode of the optimizer kernel AND this builder
    # so editing either invalidates disk entries instead of serving the
    # old update math
    from ..artifact import CompiledArtifact

    art = CompiledArtifact("fused_step", cache_key,
                           code_of=(kernel, build_executable)) \
        if cache_key is not None and _cc.cache_enabled() else None
    return _FusedEntry(
        _cc.counting_jit(step, label="fused_step", donate_argnums=donate,
                         **jit_kwargs),
        art)
