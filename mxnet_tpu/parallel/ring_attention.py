"""Ring attention: exact attention over a sequence sharded across devices.

NEW capability (SURVEY §5.7: the reference handles long sequences only by
bucketing; sequence/context parallelism is a first-class requirement of
the TPU rebuild). The sequence axis is sharded over a mesh axis; each of
the P devices holds S/P of q, k, v. P ring steps rotate the k/v shard one
neighbor over ICI via lax.ppermute while every device accumulates online-
softmax partial results of its local q against the visiting k/v chunk —
communication overlaps compute, memory stays O(S/P · D) per device, and
the result is bit-comparable to single-device attention.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

_NEG = -1e30


def _ring_attn_local(q, k, v, axis_name, sm_scale, causal):
    """Runs INSIDE shard_map: q/k/v are local shards (B, H, Sl, D)."""
    nds = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    B, H, Sl, D = q.shape
    qf = q.astype(jnp.float32)

    qid = my * Sl + jnp.arange(Sl)  # global positions of local queries

    def step(s, carry):
        m, l, acc, kc, vc = carry
        # the chunk we hold at step s originated on device (my - s) mod P
        src = (my - s) % nds
        kid = src * Sl + jnp.arange(Sl)
        sc = jnp.einsum("bhqd,bhkd->bhqk", qf, kc.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * sm_scale
        if causal:
            mask = kid[None, :] <= qid[:, None]
            sc = jnp.where(mask[None, None], sc, _NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vc.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        # rotate k/v to the next neighbor on the ring (ICI hop)
        perm = [(i, (i + 1) % nds) for i in range(nds)]
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return m_new, l, acc, kc, vc

    m0 = jnp.full((B, H, Sl, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, Sl, 1), jnp.float32)
    a0 = jnp.zeros((B, H, Sl, D), jnp.float32)
    carry = (m0, l0, a0, k, v)
    # python loop: nds is static under shard_map, ppermute pipelines
    for s in range(nds):
        carry = step(s, carry)
    m, l, acc, _, _ = carry
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ring_attention(q, k, v, mesh=None, axis_name="sp", batch_axis=None,
                   sm_scale=None, causal=False):
    """Exact attention with q/k/v sequence-sharded over `axis_name`.

    q, k, v: (B, H, S, D) NDArrays or jax arrays, S divisible by the axis
    size. `batch_axis` optionally names a mesh axis the batch dim is
    sharded over (dp×sp meshes) — without it the batch would be gathered
    across that axis on entry. Returns output with the q sharding. NDArray
    inputs run through the eager tape (one recorded node for the whole
    ring, like any registry op), so autograd.record() training works.
    """
    from .mesh import current_mesh
    from ..ndarray import NDArray
    from ..ndarray import registry as _registry

    unwrap = lambda x: x.data if isinstance(x, NDArray) else x  # noqa: E731
    wrap_out = isinstance(q, NDArray)
    qd, kd, vd = unwrap(q), unwrap(k), unwrap(v)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(qd.shape[-1])
    mesh = mesh or current_mesh()
    if mesh is None:
        raise ValueError("ring_attention needs a mesh (pass mesh= or use "
                         "parallel.mesh_scope)")
    spec = P(batch_axis, None, axis_name, None)
    from jax.sharding import NamedSharding
    sh = NamedSharding(mesh, spec)
    orig_sharding = getattr(qd, "sharding", None)
    relayout = orig_sharding is not None and \
        getattr(orig_sharding, "device_set", None) != sh.device_set
    fn = _shard_map(
        partial(_ring_attn_local, axis_name=axis_name,
                sm_scale=float(sm_scale), causal=bool(causal)),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)

    def pure(qx, kx, vx):
        # inputs produced by earlier single-device ops are committed to
        # one device; lay them out over the mesh, run the ring, and hand
        # the result back in the caller's layout (device_put is traceable
        # and differentiable, so this works eagerly, under vjp, and jit)
        from .mesh import put_back, put_sharded

        qx, kx, vx = (put_sharded(x, sh) for x in (qx, kx, vx))
        out = fn(qx, kx, vx)
        return put_back(out, orig_sharding, relayout)

    if wrap_out:
        return _registry.apply_pure(pure, [q, k, v])
    return pure(qd, kd, vd)
