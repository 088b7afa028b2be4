"""Grouped matrix product: ``(M, K) x (G, K, N)`` by group sizes.

The expert layer's product (``parallel/moe.py``): the rows of ``lhs`` are
sorted by group, group g owns the ``group_sizes[g]`` rows after those of
the groups before it, and row r of the result is ``lhs[r] @ rhs[g(r)]``.
Rows past the last group's (the unused end of a bounded buffer) come out
zero and cost no product. Three Pallas kernels, named so that a device
trace shows them by instruction name:

- ``moe_gmm_fwd``: the product. The grid walks *visits*: every
  (row tile, group) pair that overlap, in row order, so a tile that
  straddles g groups is visited g times and each visit keeps only its
  own group's rows. A group's ``rhs`` block stays in VMEM across its
  consecutive visits (its block index does not change). Tiles past the
  last routed row belong to no group: one visit each, which stores zeros
  and runs no product.
- ``moe_gmm_dlhs``: the same walk with ``rhs`` transposed,
  ``dlhs = dout @ rhs[g]^T``.
- ``moe_gmm_drhs``: ``drhs[g] = lhs[rows of g]^T @ dout[rows of g]``,
  accumulated in float32 scratch over a group's consecutive visits with
  the rows of other groups zeroed, written when the group ends. A group
  with no row is never visited; its block is zeroed after the kernel.

Operands go to the MXU in their own dtype (bf16 on the training path)
with float32 accumulation. The contraction is held whole in VMEM, so the
eligibility gate (``kernels.cost_model``) refuses contractions its budget
cannot hold; ``jax.lax.ragged_dot`` is the plain twin that then runs, and
that every test compares with. ``kernels.counters()`` counts which one a
trace lowered: ``moe_gmm_pallas`` or ``moe_gmm_plain``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import _count
from .cost_model import _TILE_COLS, _VMEM_BUDGET_BYTES
from .flash_attention import _tile_under

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b

#: rows of a tile: a visit's product is (ROWS x K x tn); a tile that
#: straddles groups is computed once per group, so taller tiles waste
#: more of the G - 1 straddles and shorter ones pay more grid steps
ROWS = 256
#: widest result block of the products, widest contraction block of drhs
_TN_CAP = 512
_TK_CAP = 1024


def _widest(n, cap, nbytes):
    """The widest tile of ``n`` under ``cap`` whose grid step, by
    ``nbytes(tile)``, stays inside the VMEM budget; None when not even
    128 columns do."""
    while cap >= _TILE_COLS:
        tile = _tile_under(n, cap)
        if nbytes(tile) <= _VMEM_BUDGET_BYTES:
            return tile
        cap = tile - _TILE_COLS
    return None


def choose_tiles(k, n, itemsize):
    """``(tn, tkn, tk)`` for a (M, k) x (G, k, n) product and its two
    backward products, or None when a kernel cannot hold its blocks:
    the result's width a step of the product takes, the width a step of
    ``dlhs`` takes of k, and the contraction block of ``drhs`` (whose
    result block is (tk, tn)). A step holds its blocks double-buffered
    by the pipeline, the float32 product with the copies the row mask
    makes of it, ``dlhs`` its transposed ``rhs`` block too, ``drhs`` its
    float32 scratch and masked ``lhs``."""
    work = 3 * 4 * ROWS     # the product, the tile it joins, their select
    # a float32 product at ``highest`` goes to the MXU as three bfloat16
    # parts of each operand, which Mosaic keeps beside the blocks
    parts = 3 * 2 if itemsize == 4 else 0

    tn = _widest(n, _TN_CAP, lambda t: 2 * itemsize * (
        ROWS * k + k * t + ROWS * t) + work * t + parts * (ROWS * k + k * t))
    tkn = _widest(k, _TN_CAP, lambda t: 2 * itemsize * (
        ROWS * n + t * n + ROWS * t) + itemsize * t * n + work * t
        + parts * (ROWS * n + t * n))
    if tn is None or tkn is None:
        return None
    tk = _widest(k, _TK_CAP, lambda t: 2 * itemsize * (
        ROWS * t + ROWS * tn + t * tn) + 2 * 4 * t * tn + itemsize * ROWS * t
        + parts * (ROWS * t + ROWS * tn))
    return None if tk is None else (tn, tkn, tk)


def eligible(m, k, n, itemsize):
    """Can the kernels take this product? Rows in whole tiles, both
    widths on the 128 lanes, every block inside the VMEM budget."""
    return (m % ROWS == 0 and k % _TILE_COLS == 0 and n % _TILE_COLS == 0
            and choose_tiles(k, n, itemsize) is not None)


def visits(group_sizes, m):
    """The walk over (row tile, group) pairs for ``m`` rows in tiles of
    ROWS: int32 arrays ``(group, tile)`` of the static length
    ``m / ROWS + G``, each group's ``starts`` and ``ends``, and the
    number of live visits. The rows no group owns are walked as group G,
    so that every tile is visited; visits past the live ones repeat the
    last live one."""
    sizes = group_sizes.astype(jnp.int32)
    g = sizes.shape[0]
    sizes = jnp.concatenate([sizes, (m - jnp.sum(sizes))[None]])
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // ROWS
    tiles = jnp.where(sizes > 0, (ends - 1) // ROWS - first + 1, 0)
    upto = jnp.cumsum(tiles)
    live = upto[-1]
    v = jnp.minimum(jnp.arange(m // ROWS + g, dtype=jnp.int32), live - 1)
    group = jnp.searchsorted(upto, v, side="right").astype(jnp.int32)
    tile = first[group] + v - (upto - tiles)[group]
    return group, tile.astype(jnp.int32), starts, ends, live[None]


def _inside(group_ref, tile_ref, starts_ref, ends_ref, v, g_count):
    """(ROWS, 1) mask of the visit's tile rows that its group owns."""
    g = group_ref[v]
    rows = tile_ref[v] * ROWS + lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)
    return (rows >= starts_ref[g]) & (rows < ends_ref[g]) & (g < g_count)


def _gmm_kernel(group_ref, tile_ref, starts_ref, ends_ref, live_ref,
                lhs_ref, rhs_ref, out_ref, *, g_count, transpose_rhs):
    """Grid (n tiles, visits), visits innermost: the tile's first visit
    zeroes it, every visit of a real group stores its own rows."""
    v = pl.program_id(1)

    @pl.when(v < live_ref[0])
    def _visit():
        @pl.when((v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != tile_ref[v]))
        def _first():
            out_ref[:] = jnp.zeros_like(out_ref)

        @pl.when(group_ref[v] < g_count)
        def _product():
            res = lax.dot_general(
                lhs_ref[:], rhs_ref[:],
                _NT if transpose_rhs else (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            keep = _inside(group_ref, tile_ref, starts_ref, ends_ref, v,
                           g_count)
            out_ref[:] = jnp.where(keep, res.astype(out_ref.dtype),
                                   out_ref[:])


def _gmm_call(lhs, rhs, meta, transpose_rhs, interpret, name):
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    g_count = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    # dlhs is the product of (M, n') x (G, k', n')^T: its width is k'
    tn = (choose_tiles(n, k, lhs.dtype.itemsize)[1] if transpose_rhs
          else choose_tiles(k, n, lhs.dtype.itemsize)[0])
    n_visits = meta[0].shape[0]

    def rhs_map(j, v, group, *_):
        g = jnp.minimum(group[v], g_count - 1)
        return (g, j, 0) if transpose_rhs else (g, 0, j)

    return pl.pallas_call(
        functools.partial(_gmm_kernel, g_count=g_count,
                          transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, n_visits),
            in_specs=[
                pl.BlockSpec((ROWS, k), lambda j, v, g, t, *_: (t[v], 0)),
                pl.BlockSpec((None, tn, k) if transpose_rhs
                             else (None, k, tn), rhs_map),
            ],
            out_specs=pl.BlockSpec((ROWS, tn),
                                   lambda j, v, g, t, *_: (t[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*meta, lhs, rhs)


def _tgmm_kernel(group_ref, tile_ref, starts_ref, ends_ref, live_ref,
                 lhs_ref, dout_ref, out_ref, acc, *, g_count):
    """Grid (k tiles, n tiles, visits), visits innermost: a group's
    consecutive visits accumulate in ``acc``; its last one writes."""
    v = pl.program_id(2)
    g = group_ref[v]
    live = live_ref[0]

    @pl.when((v < live) & (g < g_count))
    def _visit():
        @pl.when((v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != g))
        def _first():
            acc[:] = jnp.zeros_like(acc)

        keep = _inside(group_ref, tile_ref, starts_ref, ends_ref, v, g_count)
        lhs = jnp.where(keep, lhs_ref[:], jnp.zeros_like(lhs_ref))
        acc[:] += lax.dot_general(lhs, dout_ref[:], _TN,
                                  preferred_element_type=jnp.float32)

        @pl.when((v + 1 >= live)
                 | (group_ref[jnp.minimum(v + 1, group_ref.shape[0] - 1)]
                    != g))
        def _last():
            out_ref[:] = acc[:].astype(out_ref.dtype)


def _tgmm_call(lhs, dout, meta, g_count, interpret, name):
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = dout.shape[1]
    tn, _, tk = choose_tiles(k, n, lhs.dtype.itemsize)
    n_visits = meta[0].shape[0]
    out = pl.pallas_call(
        functools.partial(_tgmm_kernel, g_count=g_count),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(k // tk, n // tn, n_visits),
            in_specs=[
                pl.BlockSpec((ROWS, tk),
                             lambda i, j, v, g, t, *_: (t[v], i)),
                pl.BlockSpec((ROWS, tn),
                             lambda i, j, v, g, t, *_: (t[v], j)),
            ],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda i, j, v, g, *_: (
                    jnp.minimum(g[v], g_count - 1), i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g_count, k, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*meta, lhs, dout)
    # a group with no row was never visited: its block holds nothing
    sizes = meta[3][:g_count] - meta[2][:g_count]
    return jnp.where((sizes > 0)[:, None, None], out, jnp.zeros_like(out))


# ---------------------------------------------------------------------------
# the differentiable entry

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(lhs, rhs, group_sizes, impl):
    return _gmm_fwd(lhs, rhs, group_sizes, impl)[0]


def _owned(rows, group_sizes):
    """``rows`` with those no group owns zeroed: ``jax.lax.ragged_dot``
    and its transpose leave them whatever the TPU computed there (my
    chip run, PR 30)."""
    owned = lax.broadcasted_iota(jnp.int32, (rows.shape[0], 1), 0) \
        < jnp.sum(group_sizes.astype(jnp.int32))
    return jnp.where(owned, rows, jnp.zeros_like(rows))


def _plain(lhs, rhs, group_sizes):
    return _owned(lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32)),
                  group_sizes)


def _gmm_fwd(lhs, rhs, group_sizes, impl):
    if impl == "plain":
        return _plain(lhs, rhs, group_sizes), (lhs, rhs, group_sizes, None)
    meta = visits(group_sizes, lhs.shape[0])
    out = _gmm_call(lhs, rhs, meta, False, impl == "interpret",
                    "moe_gmm_fwd")
    return out, (lhs, rhs, group_sizes, meta)


def _gmm_bwd(impl, res, dout):
    lhs, rhs, group_sizes, meta = res
    with jax.named_scope("moe_gmm_bwd"):
        return _gmm_bwd_scoped(impl, lhs, rhs, group_sizes, meta, dout)


def _gmm_bwd_scoped(impl, lhs, rhs, group_sizes, meta, dout):
    if meta is None:
        _, vjp = jax.vjp(lambda a, b: _plain(a, b, group_sizes), lhs, rhs)
        dlhs, drhs = vjp(dout)
        return _owned(dlhs, group_sizes), drhs, None
    interpret = impl == "interpret"
    dout = dout.astype(lhs.dtype)
    dlhs = _gmm_call(dout, rhs, meta, True, interpret, "moe_gmm_dlhs")
    drhs = _tgmm_call(lhs, dout, meta, rhs.shape[0], interpret,
                      "moe_gmm_drhs")
    return dlhs, drhs.astype(rhs.dtype), None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes, use_pallas=None):
    """``out[r] = lhs[r] @ rhs[g(r)]`` for ``lhs`` (M, K) whose rows are
    sorted by group, ``rhs`` (G, K, N) and ``group_sizes`` (G,) int; rows
    past ``sum(group_sizes)`` give zeros. Differentiable in ``lhs`` and
    ``rhs``.

    use_pallas: None = the kernels on TPU where the gate admits the
    shape, ``jax.lax.ragged_dot`` elsewhere; True forces the kernels
    (interpreted off-TPU, for testing); False forces the plain twin.
    """
    m, k = lhs.shape
    fits = eligible(m, k, rhs.shape[2], lhs.dtype.itemsize)
    on_tpu = jax.default_backend() == "tpu"
    if use_pallas is None:
        impl = "pallas" if on_tpu and fits else "plain"
    elif use_pallas:
        if not fits:
            raise ValueError(
                f"the grouped-matmul kernels cannot take ({m}, {k}) x "
                f"{rhs.shape}: rows in tiles of {ROWS}, widths in "
                f"multiples of {_TILE_COLS}, blocks inside VMEM")
        impl = "pallas" if on_tpu else "interpret"
    else:
        impl = "plain"
    _count("moe_gmm_plain" if impl == "plain" else "moe_gmm_pallas")
    with jax.named_scope("moe_gmm"):
        return _gmm(lhs, rhs, group_sizes, impl)
