"""The prologue of a Gated DeltaNet layer as one Pallas kernel pair: from
the fused q|k|v|z projection to the delta rule's inputs.

``GatedDeltaNet`` (``models/moe_decoder.py``) projects its input to one
array ``qkvz`` (B, S, 2 Hk dk + 2 Hv dv): the key heads' queries, the
key heads, the value heads, then the gate z. Before the rule q | k | v
pass a causal depthwise convolution over ``taps`` positions (``y_t =
sum_j w_j x_(t - taps + 1 + j)``, the last tap the position's own) and
SiLU, q and k an l2 norm over each head (q then scaled by ``dk ** -0.5``),
and all three go to (B, H, S, d). As XLA passes that is a pad, ``taps``
shifted multiply-adds, SiLU and two norms in float32 over every channel,
and three transposes, once forward, once more recomputed under
``jax.checkpoint``, and their VJP. Here it is two kernels:

- ``delta_prologue_fwd`` reads ``qkvz`` in place, a grid step a tile of
  positions of a group of heads that the column block's index map picks
  (so the transposes cost nothing and z is never read), with the
  ``taps - 1`` positions before the tile from a halo block of the same
  array (one sublane tile of rows, the block before the tile's; zero for
  the first tile), and writes q, k or v in (B, H, S, d): per row, in
  float32, the convolution, SiLU and, for q and k, the norm, rounded once.
- ``delta_prologue_bwd`` takes dq, dk, dv as the rule's VJP returns them
  and writes d(q|k|v) in the projection's layout, one output: y and SiLU
  recomputed from ``qkvz`` (the one residual, which the projection's own
  backward keeps anyway), the norm's VJP, SiLU's derivative, then ``dx_t
  = sum_j w_j dy_(t + taps - 1 - j)``, for which it reads halos on both
  sides (``qkvz`` before the tile, ``qkvz`` and the head's cotangent after
  it). The convolution's weight gradient ``dw_j = sum_t x_(t - taps + 1 +
  j) dy_t`` leaves as float32 partial sums a tile, which XLA adds up.
  z's cotangent, from the gated norm after the rule, is not the pair's:
  autodiff adds the two, each padded to the projection's width, and XLA
  folds pads and sum into the projection's two backward products.

Both run on the grid (B, position tiles, column groups), the groups
innermost and in order: an input or output that a step does not use
keeps its block index, so nothing is fetched or written back between.
Shifts along the positions are sublane rotations (``pltpu.roll``) of the
tile with its halos stacked around it; no shifted row wraps into the
rows that are kept.

The plain twin (``use_pallas=False``, off-TPU, and whatever ``eligible``
refuses) is the same work in ``jax.numpy`` under autodiff and
``jax.checkpoint``, rounded once to the input's dtype: what every test
compares the kernels with. ``kernels.counters()`` counts which one a
trace lowered, ``delta_prologue_pallas`` or ``delta_prologue_plain``, a
call each.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import _count
from . import causal_conv as cc
from .cost_model import _TILE_COLS, _VMEM_BUDGET_BYTES

#: position tiles a grid step may take, largest first
_ROWS = (1024, 512, 256, 128)
#: the l2 norms' epsilon, as the model family has it
_EPS = 1e-6


class Layout(NamedTuple):
    """One layer's heads: ``k_heads`` of ``k_dim`` (q and k),
    ``v_heads`` of ``v_dim`` (v and z), and the convolution's taps."""
    k_heads: int
    v_heads: int
    k_dim: int
    v_dim: int
    taps: int

    @property
    def mixed(self):
        """Columns of ``qkvz`` that pass the convolution: q | k | v."""
        return 2 * self.k_heads * self.k_dim + self.v_heads * self.v_dim


def _plain(qkvz, conv_w, lay):
    """q, k (B, Hk, S, dk) and v (B, Hv, S, dv) in ``jax.numpy``."""
    hk, hv, dk, dv, taps = lay
    b, s, _ = qkvz.shape
    kd, f32 = hk * dk, jnp.float32

    def unit(a):    # (B, S, H, d) float32, l2-normalised a head
        return a * lax.rsqrt(jnp.sum(jnp.square(a), -1, keepdims=True)
                             + _EPS)

    # y_t = sum_j w_j x_(t - (taps - 1) + j): the last tap is the
    # position's own
    with jax.named_scope("conv"):
        mixed = qkvz[..., :lay.mixed]
        padded = jnp.pad(mixed, ((0, 0), (taps - 1, 0), (0, 0)))
        conv = jax.nn.silu(sum(
            padded[:, j:j + s].astype(f32) * conv_w[j].astype(f32)
            for j in range(taps)))
    with jax.named_scope("l2norm"):
        q = unit(conv[..., :kd].reshape(b, s, hk, dk)) * dk ** -0.5
        k = unit(conv[..., kd:2 * kd].reshape(b, s, hk, dk))
    v = conv[..., 2 * kd:].reshape(b, s, hv, dv)
    return tuple(a.astype(qkvz.dtype).transpose(0, 2, 1, 3)
                 for a in (q, k, v))


# ---------------------------------------------------------------------------
# the kernels

_halo = cc.halo


def _vmem_bytes(rows, hb, lay, itemsize):
    """VMEM of the backward's grid step, the larger of the two: dq, dk,
    dv, ``qkvz`` and d(q|k|v), each double-buffered with their halos; the
    weight's and its partial gradient's float32 blocks; a dozen float32
    working copies of a head with its halos."""
    return cc.vmem_bytes(rows, hb * lay.k_dim, itemsize, 5, 5, lay.k_dim,
                         12)


def tiles(s, lay, itemsize):
    """(rows, heads) of a grid step's block: the most positions, then the
    most heads of a kind (a divisor of both head counts) that stay inside
    the VMEM budget; None when nothing fits."""
    for rows in _ROWS:
        if s % rows:
            continue
        for hb in range(lay.k_heads, 0, -1):
            if lay.k_heads % hb == 0 and lay.v_heads % hb == 0 \
                    and _vmem_bytes(rows, hb, lay, itemsize) \
                    <= _VMEM_BUDGET_BYTES:
                return rows, hb
    return None


def eligible(s, lay, itemsize):
    """Can the kernels take this layer? Key and value heads of one size
    on whole 128-lane columns, a convolution whose reach fits a halo,
    positions in whole tiles of ``_ROWS``, blocks inside VMEM."""
    return (lay.k_dim == lay.v_dim and lay.k_dim % _TILE_COLS == 0
            and 1 <= lay.taps <= _halo(itemsize) + 1
            and tiles(s, lay, itemsize) is not None)


def _clip(a, n):
    return jnp.minimum(jnp.maximum(a, 0), n - 1)


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _call(kernel, name, grid, in_specs, out_specs, out_shape, interpret):
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        # the groups in order: a kind's blocks are revisited
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name)      # the HLO instruction's name on a device trace


def _fwd_kernel(x_ref, xb_ref, w_ref, q_ref, k_ref, v_ref, *, lay, hb, nq):
    t, c = pl.program_id(1), pl.program_id(2)
    d, halo, f32 = lay.k_dim, xb_ref.shape[0], jnp.float32

    def heads(out_ref, norm, scale=None):
        for j in range(hb):
            cols = slice(j * d, (j + 1) * d)
            before = jnp.where(t > 0, xb_ref[:, cols].astype(f32), 0.0)
            ext = jnp.concatenate([before, x_ref[:, cols].astype(f32)], 0)
            y = cc.conv(ext, w_ref[:, cols], lay.taps)[halo:]
            y = y * _sigmoid(y)
            if norm:
                y = y * lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + _EPS)
                if scale is not None:
                    y = y * scale
            out_ref[j] = y.astype(out_ref.dtype)

    pl.when(c < nq)(lambda: heads(q_ref, True, lay.k_dim ** -0.5))
    pl.when((c >= nq) & (c < 2 * nq))(lambda: heads(k_ref, True))
    pl.when(c >= 2 * nq)(lambda: heads(v_ref, False))


def _forward(qkvz, w, lay, interpret):
    """The kernel ``delta_prologue_fwd``: q, k (B, Hk, S, d), v (B, Hv, S,
    d). Grid step (i, t, c): column block c of ``qkvz``'s q | k | v part,
    its kind given by its place."""
    b, s, _ = qkvz.shape
    hk, hv, d, dt = lay.k_heads, lay.v_heads, lay.k_dim, qkvz.dtype
    rows, hb = tiles(s, lay, dt.itemsize)
    halo = _halo(dt.itemsize)
    nq, nv, per = hk // hb, hv // hb, rows // halo

    def out(first, n):
        return pl.BlockSpec((None, hb, rows, d),
                            lambda i, t, c: (i, _clip(c - first, n), t, 0))

    return _call(
        functools.partial(_fwd_kernel, lay=lay, hb=hb, nq=nq),
        "delta_prologue_fwd", (b, s // rows, 2 * nq + nv),
        [pl.BlockSpec((None, rows, hb * d), lambda i, t, c: (i, t, c)),
         pl.BlockSpec((None, halo, hb * d),
                      lambda i, t, c: (i, jnp.maximum(t * per - 1, 0), c)),
         pl.BlockSpec((lay.taps, hb * d), lambda i, t, c: (0, c))],
        [out(0, nq), out(nq, nq), out(2 * nq, nv)],
        [jax.ShapeDtypeStruct((b, hk, s, d), dt)] * 2
        + [jax.ShapeDtypeStruct((b, hv, s, d), dt)],
        interpret,
    )(qkvz, qkvz, w)


def _bwd_kernel(dq_ref, dk_ref, dv_ref, aq_ref, ak_ref, av_ref, x_ref,
                xb_ref, xa_ref, w_ref, dx_ref, dw_ref, *, lay, hb, nq):
    t, c, last = pl.program_id(1), pl.program_id(2), pl.num_programs(1) - 1
    d, taps, f32 = lay.k_dim, lay.taps, jnp.float32
    rows, halo = x_ref.shape[0], xb_ref.shape[0]
    keep = slice(halo, halo + rows)

    def heads(cot_ref, after_ref, norm, scale=None):
        for j in range(hb):
            cols = slice(j * d, (j + 1) * d)
            w = w_ref[:, cols]
            ext = jnp.concatenate([
                jnp.where(t > 0, xb_ref[:, cols].astype(f32), 0.0),
                x_ref[:, cols].astype(f32),
                jnp.where(t < last, xa_ref[:, cols].astype(f32), 0.0)], 0)
            y = cc.conv(ext, w, taps)
            sig = _sigmoid(y)
            # the cotangent at the tile's rows and the halo's after them
            g = jnp.concatenate([
                jnp.zeros((halo, d), f32), cot_ref[j].astype(f32),
                jnp.where(t < last, after_ref[j].astype(f32), 0.0)], 0)
            if norm:
                u = y * sig
                r = lax.rsqrt(jnp.sum(u * u, -1, keepdims=True) + _EPS)
                u = u * r
                if scale is not None:
                    g = g * scale
                g = r * (g - u * jnp.sum(u * g, -1, keepdims=True))
            dy = g * (sig * (1.0 + y * (1.0 - sig)))
            dx = cc.conv_back(dy, w, taps)
            dx_ref[:, cols] = dx[keep].astype(dx_ref.dtype)
            for k, row in enumerate(cc.taps_grad(ext, dy[keep], taps, keep)):
                dw_ref[k:k + 1, cols] = row

    pl.when(c < nq)(lambda: heads(dq_ref, aq_ref, True, d ** -0.5))
    pl.when((c >= nq) & (c < 2 * nq))(lambda: heads(dk_ref, ak_ref, True))
    pl.when(c >= 2 * nq)(lambda: heads(dv_ref, av_ref, False))


def _backward(dq, dk, dv, qkvz, w, lay, interpret):
    """The kernel ``delta_prologue_bwd``: (d(q|k|v) (B, S, q|k|v
    columns), the weight's partial gradients (B, tiles, taps, q|k|v
    columns) float32). Grid step (i, t, c): column block c of ``qkvz``'s
    q | k | v part in order, its kind given by its place."""
    b, s, _ = qkvz.shape
    hk, hv, d, dt = lay.k_heads, lay.v_heads, lay.k_dim, qkvz.dtype
    rows, hb = tiles(s, lay, dt.itemsize)
    halo = _halo(dt.itemsize)
    nq, nv, per, nh = hk // hb, hv // hb, rows // halo, s // halo

    def after(t):       # the halo block after tile t, in halo blocks
        return jnp.minimum((t + 1) * per, nh - 1)

    def cot(first, n, size=rows, at=lambda t: t):
        return pl.BlockSpec((None, hb, size, d),
                            lambda i, t, c: (i, _clip(c - first, n), at(t),
                                             0))

    in_specs = [cot(0, nq), cot(nq, nq), cot(2 * nq, nv),
                cot(0, nq, halo, after), cot(nq, nq, halo, after),
                cot(2 * nq, nv, halo, after),
                pl.BlockSpec((None, rows, hb * d), lambda i, t, c: (i, t, c)),
                pl.BlockSpec((None, halo, hb * d),
                             lambda i, t, c: (i, jnp.maximum(t * per - 1, 0),
                                              c)),
                pl.BlockSpec((None, halo, hb * d),
                             lambda i, t, c: (i, after(t), c)),
                pl.BlockSpec((lay.taps, hb * d), lambda i, t, c: (0, c))]
    out_specs = [pl.BlockSpec((None, rows, hb * d), lambda i, t, c: (i, t, c)),
                 pl.BlockSpec((None, None, lay.taps, hb * d),
                              lambda i, t, c: (i, t, 0, c))]
    out_shape = [jax.ShapeDtypeStruct((b, s, lay.mixed), dt),
                 jax.ShapeDtypeStruct((b, s // rows, lay.taps, lay.mixed),
                                      jnp.float32)]
    return _call(
        functools.partial(_bwd_kernel, lay=lay, hb=hb, nq=nq),
        "delta_prologue_bwd", (b, s // rows, 2 * nq + nv), in_specs,
        out_specs, out_shape, interpret,
    )(dq, dk, dv, dq, dk, dv, qkvz, qkvz, qkvz, w)


# ---------------------------------------------------------------------------
# the differentiable entry

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _prologue(qkvz, conv_w, lay, interpret):
    return _prologue_fwd(qkvz, conv_w, lay, interpret)[0]


def _prologue_fwd(qkvz, conv_w, lay, interpret):
    q, k, v = _forward(qkvz, conv_w.astype(jnp.float32), lay, interpret)
    return (q, k, v), (qkvz, conv_w)


def _prologue_bwd(lay, interpret, res, cts):
    qkvz, conv_w = res
    with jax.named_scope("delta_prologue_bwd"):
        dmixed, dw = _backward(*cts, qkvz, conv_w.astype(jnp.float32), lay,
                               interpret)
        # z's columns get nothing here: the gated norm's cotangent joins
        # at the projection, whose two products read both in place
        pad = qkvz.shape[-1] - lay.mixed
        return (jnp.pad(dmixed, ((0, 0), (0, 0), (0, pad))),
                dw.sum((0, 1)).astype(conv_w.dtype))


_prologue.defvjp(_prologue_fwd, _prologue_bwd)


def delta_prologue(qkvz, conv_w, k_heads, v_heads, head_k_dim, head_v_dim,
                   *, use_pallas=None):
    """(q (B, Hk, S, dk), k (B, Hk, S, dk), v (B, Hv, S, dv)) from the
    projection's ``qkvz`` (B, S, 2 Hk dk + 2 Hv dv): q | k | v through
    the causal depthwise convolution ``conv_w`` (taps, 2 Hk dk + Hv dv)
    and SiLU, q and k l2-normalised a head, q scaled by ``dk ** -0.5``;
    z, the last Hv dv columns, is not read. Differentiable in ``qkvz``
    and ``conv_w``.

    use_pallas: None = the kernels on TPU where ``eligible`` admits the
    layer, the plain twin elsewhere; True forces the kernels
    (interpreted off-TPU, for testing); False forces the twin.
    """
    _, s, cols = qkvz.shape
    lay = Layout(int(k_heads), int(v_heads), int(head_k_dim),
                 int(head_v_dim), int(conv_w.shape[0]))
    if cols != lay.mixed + lay.v_heads * lay.v_dim \
            or conv_w.shape[1:] != (lay.mixed,):
        raise ValueError(f"qkvz of {cols} columns and a convolution of "
                         f"{conv_w.shape} for {k_heads} key heads of "
                         f"{head_k_dim} and {v_heads} value heads of "
                         f"{head_v_dim}")
    fits = eligible(s, lay, qkvz.dtype.itemsize)
    on_tpu = jax.default_backend() == "tpu"
    if use_pallas is None:
        impl = "pallas" if on_tpu and fits else "plain"
    elif use_pallas:
        if not fits:
            raise ValueError(
                f"the prologue kernels cannot take heads of {head_k_dim} "
                f"and {head_v_dim} at {s} positions: heads of one size in "
                f"multiples of {_TILE_COLS} lanes, positions in multiples "
                f"of {_ROWS[-1]}")
        impl = "pallas" if on_tpu else "interpret"
    else:
        impl = "plain"
    _count("delta_prologue_plain" if impl == "plain"
           else "delta_prologue_pallas")
    if impl == "plain":
        return jax.checkpoint(_plain, static_argnums=(2,))(qkvz, conv_w,
                                                            lay)
    with jax.named_scope("delta_prologue"):
        return _prologue(qkvz, conv_w, lay, impl == "interpret")
