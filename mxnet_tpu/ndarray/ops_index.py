"""Indexing, gather/scatter and ordering ops.

TPU-native equivalents of ``src/operator/tensor/indexing_op.{h,cc}``
(take/gather_nd/scatter_nd/one_hot/Embedding), ``ordering_op-inl.h``
(topk/sort/argsort) and ``histogram`` (reference: SURVEY §2.2). gather and
scatter map to XLA gather/scatter HLO through jnp.take / ndarray.at; topk
uses lax.top_k which is native on TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import get_op, register


@register()
def take(data, indices, axis=0, mode="clip"):
    """Reference: indexing_op.h Take. mode clip/wrap (raise unsupported under
    jit; clip used)."""
    idx = indices.astype(jnp.int32)
    return jnp.take(data, idx, axis=axis,
                    mode="clip" if mode in ("clip", "raise") else "wrap")


@register()
def take_along_axis(data, indices, axis=0):
    """Gather values along ``axis`` at per-position ``indices`` (reference:
    np_take_along_axis)."""
    return jnp.take_along_axis(data, indices.astype(jnp.int32), axis=axis)


@register()
def pick(data, index, axis=-1, keepdims=False, mode="clip"):
    """Reference: broadcast_reduce_op_index.cc pick. ``mode`` as there:
    ``clip`` holds an index in ``[0, n - 1]``, ``wrap`` takes it modulo
    ``n``.

    The element is taken by a mask, not by a gather: the one place of
    ``axis`` whose number is the index is selected (never multiplied: an
    unpicked ``inf`` or NaN reaches nothing) and the rest summed as
    zeros, which is the gathered value (a picked ``-0.0`` reads ``0.0``:
    it was added to zeros). Under autodiff that is a select of the
    cotangent, dense, where a gather's would scatter-add into zeros the
    size of ``data``: at one sequence a batch XLA:TPU keeps that
    scatter, into a flat logits-sized array that a ``while`` then
    re-tiles (PERF.md section 6, PR 37). Counted once a trace:
    ``kernels.counters()["pick_masked"]``."""
    from ..kernels import _count

    _count("pick_masked")
    axis %= data.ndim
    n = data.shape[axis]
    idx = jnp.expand_dims(index.astype(jnp.int32), axis=axis)
    idx = idx % n if mode == "wrap" else jnp.clip(idx, 0, n - 1)
    place = lax.broadcasted_iota(
        jnp.int32, (1,) * axis + (n,) + (1,) * (data.ndim - axis - 1), axis)
    return jnp.sum(jnp.where(idx == place, data, 0), axis=axis,
                   keepdims=keepdims, dtype=data.dtype)


@register()
def gather_nd(data, indices):
    """Reference: indexing_op.h GatherND. indices: (M, ...) leading dim
    indexes the first M axes of data."""
    idx = tuple(indices[i].astype(jnp.int32) for i in range(indices.shape[0]))
    return data[idx]


@register()
def scatter_nd(data, indices, shape):
    """Reference: indexing_op.h ScatterND."""
    out = jnp.zeros(shape, data.dtype)
    idx = tuple(indices[i].astype(jnp.int32) for i in range(indices.shape[0]))
    return out.at[idx].add(data)


@register()
def one_hot(indices, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    """Reference: indexing_op.h OneHot."""
    from .ndarray import _canon_dtype

    oh = jax.nn.one_hot(indices.astype(jnp.int32), depth)
    out = oh * on_value + (1 - oh) * off_value
    return out.astype(_canon_dtype(dtype))


@register()
def index_copy(old, index_vector, new_tensor):
    """Reference: contrib/index_copy.cc."""
    return old.at[index_vector.astype(jnp.int32)].set(new_tensor)


@register()
def index_array(data, axes=None):
    """Reference: contrib/index_array.cc."""
    shape = data.shape
    axes = axes or tuple(range(len(shape)))
    grids = jnp.meshgrid(*[jnp.arange(s) for s in shape], indexing="ij")
    return jnp.stack([grids[a] for a in axes], axis=-1).astype(jnp.int64)


@register()
def boolean_mask(data, index, axis=0):
    """Reference: contrib/boolean_mask.cc — data-dependent output shape; the
    reference syncs to size the output (SURVEY §7 hard part 2). Same here:
    forces a host sync, not usable under jit (use `where` there)."""
    import numpy as onp

    mask = onp.asarray(index) != 0
    return jnp.compress(mask, data, axis=axis)


# ------------------------------------------------------------- ordering ---

@register()
def topk(data, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    """Reference: ordering_op-inl.h TopK → lax.top_k (TPU-native sort unit)."""
    from .ndarray import _canon_dtype

    x = jnp.moveaxis(data, axis, -1)
    if is_ascend:
        vals, idx = lax.top_k(-x, k)
        vals = -vals
    else:
        vals, idx = lax.top_k(x, k)
    vals = jnp.moveaxis(vals, -1, axis)
    idx = jnp.moveaxis(idx, -1, axis).astype(_canon_dtype(dtype))
    if ret_typ == "value":
        return vals
    if ret_typ == "indices":
        return idx
    if ret_typ == "both":
        return vals, idx
    if ret_typ == "mask":
        x = jnp.moveaxis(jnp.zeros_like(data), axis, -1)
        oh = jax.nn.one_hot(jnp.moveaxis(idx, axis, -1).astype(jnp.int32),
                            data.shape[axis]).sum(axis=-2)
        return jnp.moveaxis(oh, -1, axis).astype(data.dtype)
    raise ValueError(f"unknown ret_typ {ret_typ}")


@register()
def sort(data, axis=-1, is_ascend=True):
    """Sort values along ``axis``; is_ascend=False reverses (reference:
    ordering_op.cc sort)."""
    out = jnp.sort(data, axis=axis)
    return out if is_ascend else jnp.flip(out, axis=axis)


@register()
def argsort(data, axis=-1, is_ascend=True, dtype="float32"):
    """Sorting indices along ``axis`` in the requested dtype (reference:
    ordering_op.cc argsort)."""
    from .ndarray import _canon_dtype

    idx = jnp.argsort(data, axis=axis, stable=True)
    if not is_ascend:
        idx = jnp.flip(idx, axis=axis)
    return idx.astype(_canon_dtype(dtype))


@register()
def shuffle(data):
    """Random permutation of the first axis (reference: shuffle_op.cc)."""
    from .. import random as mxrandom

    key = mxrandom.next_key()
    return jax.random.permutation(key, data, axis=0)


@register()
def histogram(data, bins=10, range=None, bin_cnt=None):
    """Reference: src/operator/tensor/histogram.cc."""
    if bin_cnt is not None:
        bins = bin_cnt
    cnt, edges = jnp.histogram(data.reshape(-1), bins=bins, range=range)
    return cnt.astype(jnp.int64), edges


@register()
def unravel(data, shape=None):
    """Flat indices -> coordinate rows for ``shape`` (reference: ravel.cc
    unravel_index)."""
    idx = jnp.unravel_index(data.astype(jnp.int32), shape)
    return jnp.stack(idx).astype(data.dtype)


@register()
def ravel_multi_index(data, shape=None):
    """Coordinate rows -> flat indices for ``shape`` (reference: ravel.cc
    ravel_multi_index)."""
    idx = tuple(data[i].astype(jnp.int32) for i in range(data.shape[0]))
    return jnp.ravel_multi_index(idx, shape, mode="clip").astype(data.dtype)


# -------------------------------------------------------- internal helpers

@register(name="_static_slice")
def _static_slice(data, key=None):
    """Basic-indexing kernel behind NDArray.__getitem__ for static keys
    (reference: ndarray.py _get_nd_basic_indexing)."""
    return data[key]


@register(name="_slice_take")
def _slice_take(data, key=None):
    """Advanced-indexing kernel: take rows by index array after a static
    prefix (reference: ndarray.py advanced indexing)."""
    return data[key]


@register(differentiable=False)
def unravel_index(data, shape=None):
    """Alias of `unravel` under the reference's public name
    (src/operator/tensor/ravel.cc _unravel_index)."""
    return get_op("unravel").fn(data, shape=shape)


@register()
def slice_assign(lhs, rhs, begin=None, end=None, step=None):
    """Functional slice write: lhs with lhs[begin:end:step] = rhs
    (reference: src/operator/tensor/matrix_op.cc _slice_assign — the op
    form of sliced __setitem__; XLA lowers to dynamic_update_slice)."""
    idx = tuple(slice(b if b is not None else None,
                      e if e is not None else None,
                      s if s not in (None, 0) else None)
                for b, e, s in zip(begin or (), end or (),
                                   step or (None,) * len(begin or ())))
    return lhs.at[idx].set(rhs.astype(lhs.dtype))


@register()
def slice_assign_scalar(data, begin=None, end=None, step=None,
                        scalar=0.0):
    """Reference: _slice_assign_scalar."""
    idx = tuple(slice(b if b is not None else None,
                      e if e is not None else None,
                      s if s not in (None, 0) else None)
                for b, e, s in zip(begin or (), end or (),
                                   step or (None,) * len(begin or ())))
    return data.at[idx].set(jnp.asarray(scalar, data.dtype))


@register()
def scatter_set_nd(lhs, rhs, indices, shape=None):
    """Reference: src/operator/tensor/indexing_op.cc _scatter_set_nd —
    lhs with lhs[indices] = rhs (gather_nd's inverse on an existing
    tensor; indices (M, N) index the first M axes)."""
    idx = tuple(indices[i].astype(jnp.int32) for i in
                range(indices.shape[0]))
    return lhs.at[idx].set(rhs.astype(lhs.dtype))


@register(differentiable=False)
def arange_like(data, start=0.0, step=1.0, repeat=1, axis=None):
    """Reference: src/operator/tensor/init_op.cc _contrib_arange_like —
    arange shaped like `data` (or its `axis` length)."""
    def seq(n):
        base = start + step * jnp.arange(
            -(-n // repeat) if repeat != 1 else n, dtype=jnp.float32)
        return jnp.repeat(base, repeat)[:n] if repeat != 1 else base

    if axis is None:
        return seq(data.size).reshape(data.shape)
    return seq(data.shape[axis])
