"""Ulysses-style all-to-all sequence parallelism.

NEW capability alongside ring attention (SURVEY §5.7): the sequence axis
is sharded over a mesh axis like ring attention, but instead of rotating
k/v shards around a ring, ONE all-to-all redistributes the work from
sequence-sharded to head-sharded — every device then holds H/P complete
heads over the FULL sequence, runs an ordinary (fully local, fusible)
attention, and a second all-to-all restores sequence sharding. Two
collectives total, each moving S·H·D/P elements per device over ICI,
versus the ring's P ppermute hops — the better trade when H >= P and
sequence length dominates (the DeepSpeed-Ulysses scheme, arXiv
2309.14509, rebuilt here on lax.all_to_all).

Requires num_heads divisible by the axis size (head-granular scatter).
"""
from __future__ import annotations

import math
from functools import partial

import jax
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import NamedSharding, PartitionSpec as P


__all__ = ["ulysses_attention"]

def _ulysses_local(q, k, v, axis_name, sm_scale, causal):
    """Runs INSIDE shard_map: q/k/v are sequence shards (B, H, Sl, D)."""
    from ..ops.flash_attention import flash_attention

    # seq-sharded -> head-sharded: split heads across the axis, gather
    # the sequence (one ICI all-to-all per tensor)
    qh, kh, vh = (lax.all_to_all(x, axis_name, split_axis=1,
                                 concat_axis=2, tiled=True)
                  for x in (q, k, v))
    # local attention over the full sequence via the streaming flash
    # kernel — O(S) memory per head, not an S x S score matrix
    out = flash_attention(qh, kh, vh, sm_scale=sm_scale, causal=causal)
    # head-sharded -> seq-sharded
    return lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def ulysses_attention(q, k, v, mesh=None, axis_name="sp", batch_axis=None,
                      sm_scale=None, causal=False):
    """Exact attention with q/k/v sequence-sharded over ``axis_name``
    via head-scatter all-to-all (DeepSpeed-Ulysses scheme).

    Same calling convention as :func:`ring_attention` — (B, H, S, D)
    inputs, S divisible by the axis size — plus the constraint that H is
    divisible by the axis size. NDArray inputs run through the eager tape
    so autograd.record() training works.
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
        raise ValueError("ulysses_attention needs a mesh (pass mesh= or "
                         "use parallel.mesh_scope)")
    nsp = mesh.shape[axis_name]
    if qd.shape[1] % nsp:
        raise ValueError(
            f"num_heads {qd.shape[1]} not divisible by the '{axis_name}' "
            f"axis size {nsp}; use ring_attention for head-scarce models")
    spec = P(batch_axis, None, axis_name, None)
    sh = NamedSharding(mesh, spec)
    orig_sharding = getattr(qd, "sharding", None)
    relayout = orig_sharding is not None and \
        getattr(orig_sharding, "device_set", None) != sh.device_set
    fn = _shard_map(
        partial(_ulysses_local, axis_name=axis_name,
                sm_scale=float(sm_scale), causal=bool(causal)),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)

    def pure(qx, kx, vx):
        from .mesh import put_back, put_sharded

        qx, kx, vx = (put_sharded(x, sh) for x in (qx, kx, vx))
        out = fn(qx, kx, vx)
        return put_back(out, orig_sharding, relayout)

    if wrap_out:
        return _registry.apply_pure(pure, [q, k, v])
    return pure(qd, kd, vd)
