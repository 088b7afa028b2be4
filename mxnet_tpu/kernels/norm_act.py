"""Fused norm+activation cluster op (LayerNorm → GELU/ReLU/…).

XLA compiles layer_norm and the following activation as separate
fusions around the reductions; the cluster op does normalize + affine
+ activation in one pass. Two implementations:

- ``lax`` (portable fallback, bit-identical): replay the registered
  ``layer_norm`` body then the activation body inside one dispatch.
- ``pallas`` (TPU): one row-blocked VMEM kernel — each grid step holds
  a (rows, C) tile, computes mean/var, normalizes, applies gamma/beta
  and the activation before the tile ever leaves VMEM. Off-TPU it runs
  only under ``impl="interpret"`` (parity tests); the cost model never
  selects it there.

BatchNorm→act is deliberately NOT backed here: ``batch_norm`` is
effectful (running-stat write-back through the aux-state machinery),
so the clustering pass matches it only to record a
``fallback_effectful`` counter and keeps the 1:1 lowering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..ndarray.registry import get_op, register

#: activation node forms a norm_act cluster may absorb:
#: {op name: set of fusable act_type values} (None = default)
FUSABLE_ACTS = {
    "activation": {"relu", "sigmoid", "tanh", "softrelu", "softsign"},
    "leaky_relu": {"leaky", "elu", "selu", "gelu", "rrelu"},
    "relu": {None}, "sigmoid": {None}, "tanh": {None},
    "softsign": {None},
}


def _apply_act(x, act_op, act_kw):
    """Dispatch the activation through its registered body (bitwise
    parity with the unfused node by construction)."""
    return get_op(act_op).fn(x, **dict(act_kw))


def _ln_act_kernel(x_ref, g_ref, b_ref, o_ref, *, eps, act_op, act_kw):
    """One (rows, C) tile: mean/var along the lane axis, normalize,
    affine, activation — all in VMEM."""
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    out = out * g_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    out = _apply_act(out, act_op, act_kw)
    o_ref[...] = out.astype(o_ref.dtype)


def _pallas_norm_act(data, gamma, beta, eps, act_op, act_kw, interpret):
    from jax.experimental import pallas as pl

    shape = data.shape
    c = shape[-1]
    rows = 1
    for d in shape[:-1]:
        rows *= d
    x2 = data.reshape(rows, c)
    br = min(128, rows)
    pr = (-rows) % br
    if pr:
        x2 = jnp.pad(x2, ((0, pr), (0, 0)))
    kern = functools.partial(_ln_act_kernel, eps=eps, act_op=act_op,
                             act_kw=act_kw)
    out = pl.pallas_call(
        kern,
        grid=((rows + pr) // br,),
        in_specs=[
            pl.BlockSpec((br, c), lambda i: (i, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pr, c), data.dtype),
        interpret=interpret,
        name="norm_act",
    )(x2, gamma.reshape(1, c), beta.reshape(1, c))
    if pr:
        out = out[:rows]
    return out.reshape(shape)


@register("_fused_norm_act", namespaces=())
def _fused_norm_act(data, gamma, beta, norm_kw=(), act_op="activation",
                    act_kw=(), impl="lax"):
    """Fused LayerNorm→activation cluster emitted by the
    analysis/fusion clustering pass. ``impl="lax"`` replays the
    registered ``layer_norm`` + activation bodies in one dispatch
    (bit-identical to the unfused pair); ``impl="pallas"`` runs the
    row-blocked TPU kernel (documented-ulp: fp32 VMEM accumulation);
    ``impl="interpret"`` runs that kernel interpreted for off-TPU
    parity tests. (Reference: src/operator/nn/layer_norm.cc +
    activation-inl.h, fused.)"""
    nkw = dict(norm_kw)
    if impl in ("pallas", "interpret") and \
            nkw.get("axis", -1) in (-1, data.ndim - 1):
        return _pallas_norm_act(data, gamma, beta,
                                float(nkw.get("eps", 1e-5)), act_op,
                                act_kw, impl == "interpret")
    out = get_op("layer_norm").fn(data, gamma, beta, **nkw)
    return _apply_act(out, act_op, act_kw)
