"""Pipeline parallelism: GPipe-style microbatched stage pipeline over a
'pp' mesh axis.

NEW capability completing the parallelism set (dp/tp/sp/ep/pp): the
model's layer stack is split into P shape-preserving stages, one per
device along 'pp'; a microbatched loop runs M + P - 1 ticks where every
tick each device applies its stage and hands its activation to the next
stage over ICI via lax.ppermute (the canonical shard_map pipeline from
the TPU scaling playbook; reference MXNet's analog is the group2ctx
model-parallel placement, executor-level and bubble-free only for
pure layer splits).

The loop is a lax.scan, so the whole pipeline — bubbles and all — is
one differentiable XLA program: jax.grad through pipeline_apply gives
per-stage parameter gradients (GPipe's recompute-free backward).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply"]


def _pp_local(stage_params, x, fn, n_micro, axis_name):
    """Runs INSIDE shard_map. stage_params: this stage's params (leading
    stage dim of size 1 squeezed by the caller's spec); x: the full
    (replicated) batch (B, ...). Returns the pipelined output (B, ...).
    """
    p = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B = x.shape[0]
    assert B % n_micro == 0, "batch must divide microbatches"
    mbs = x.reshape(n_micro, B // n_micro, *x.shape[1:])
    # the carries become device-varying after the first ppermute tick;
    # mark the (zero) initial values varying so scan's type check passes
    def _vary(v):
        try:
            return lax.pcast(v, (axis_name,), to="varying")
        except (AttributeError, TypeError):
            return lax.pvary(v, (axis_name,))

    state0 = _vary(jnp.zeros_like(mbs[0]))
    out0 = _vary(jnp.zeros_like(mbs))
    mbs = _vary(mbs)
    shift = [(i, (i + 1) % p) for i in range(p)]

    def tick(carry, t):
        state, out = carry
        # stage 0 ingests microbatch t while it lasts; later stages use
        # the activation handed over by the previous tick
        mb_in = mbs[jnp.clip(t, 0, n_micro - 1)]
        cur = jnp.where(idx == 0, jnp.where(t < n_micro, mb_in,
                                            jnp.zeros_like(mb_in)),
                        state)
        y = fn(stage_params, cur)
        # the last stage emits microbatch t - (p - 1)
        emit = t - (p - 1)
        valid = (idx == p - 1) & (emit >= 0) & (emit < n_micro)
        slot = jnp.clip(emit, 0, n_micro - 1)
        out = jnp.where(valid, out.at[slot].set(y), out)
        # hand activations down the pipe (one ICI hop per tick)
        state = lax.ppermute(y, axis_name, shift)
        return (state, out), None

    (state, out), _ = lax.scan(tick, (state0, out0),
                               jnp.arange(n_micro + p - 1))
    # only the last stage holds real outputs; psum broadcasts them
    # (every other stage contributes zeros)
    out = lax.psum(jnp.where(idx == p - 1, out, jnp.zeros_like(out)),
                   axis_name)
    return out.reshape(B, *x.shape[1:])


def pipeline_apply(stage_fn, stage_params, x, mesh=None, axis_name="pp",
                   n_microbatches=None):
    """Apply P pipeline stages to x over the 'pp' mesh axis.

    stage_fn(params_i, act) -> act must be shape-preserving (uniform
    stages — e.g. transformer blocks). stage_params is a pytree whose
    leaves have a leading stage dimension of size P (sharded over
    ``axis_name``); x (B, ...) is replicated over the axis. Returns
    stage_{P-1}(... stage_0(x)) computed with M = ``n_microbatches``
    (default: the axis size) microbatches.

    With no mesh / axis of size 1, falls back to a sequential scan over
    the stage dimension (identical math, no collectives).
    """
    from .mesh import current_mesh

    mesh = mesh or current_mesh()
    if mesh is None or axis_name not in mesh.axis_names \
            or mesh.shape[axis_name] == 1:
        def body(act, params_i):
            return stage_fn(params_i, act), None

        out, _ = lax.scan(body, x, stage_params)
        return out

    p = mesh.shape[axis_name]
    n_micro = n_microbatches or p

    def squeeze_leading(t):
        return jax.tree_util.tree_map(lambda a: a.reshape(a.shape[1:]),
                                      t)

    def local(params, xl):
        return _pp_local(squeeze_leading(params), xl, stage_fn, n_micro,
                         axis_name)

    pspec = jax.tree_util.tree_map(lambda _: P(axis_name), stage_params)
    fn = _shard_map(local, mesh=mesh,
                       in_specs=(pspec, P()), out_specs=P())
    return fn(stage_params, x)
