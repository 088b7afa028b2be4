"""LFM2's gated short convolution as one Pallas kernel pair: from the
fused B|C|x projection to the output projection's input.

``GatedShortConv`` (``models/moe_decoder.py``) projects its input to one
array ``bcx`` (B, S, 3E): the input gate B, the output gate C and the
values x, E channels each. The mixer is ``u = B * x``, a causal
depthwise convolution over ``taps`` positions, ``z_t = sum_j w_j
u_(t - taps + 1 + j)`` (the last tap the position's own), and ``y = C *
z``. As XLA passes that is a product, a pad, ``taps`` shifted
multiply-adds and a product over every channel, once forward, once more
recomputed, and their VJP. Here it is two kernels:

- ``short_conv_fwd`` reads ``bcx`` in place, a grid step a tile of
  positions of a block of channels, its three column blocks picked by
  the index maps, with the ``taps - 1`` positions before the tile from a
  halo block of B and of x (``kernels/causal_conv.py``; zero for the
  first tile), and writes y (B, S, E) once: per row, in float32, the
  gate, the convolution and the other gate, rounded once.
- ``short_conv_bwd`` takes dy and writes d(B|C|x) in the projection's
  layout, one output: ``dz = dy * C``, ``du_t = sum_j w_j dz_(t + taps
  - 1 - j)``, ``dB = du * x``, ``dx = du * B``, ``dC = dy * z`` with u
  and z recomputed from ``bcx`` (the one residual), for which it reads
  halos on both sides (B and x before the tile, C and dy after it). The
  weight's gradient ``dw_j = sum_t u_(t - taps + 1 + j) dz_t`` leaves as
  float32 partial sums a tile, which XLA adds up.

The forward runs on the grid (B, position tiles, channel blocks). The
backward's grid has a fourth axis, innermost, for the three column
blocks it writes of a channel block: dB first (du is computed and kept
in VMEM), then dx (from the kept du), then dC with the weight's partial
sums, the step at which the next tile's inputs are fetched. Its inputs
keep their block index over the three, so they are fetched once.

The plain twin (``use_pallas=False``, off-TPU, and whatever ``eligible``
refuses) is the same work in ``jax.numpy`` under autodiff and
``jax.checkpoint``, float32 within, rounded once to the input's dtype:
what every test compares the kernels with. ``kernels.counters()`` counts
which one a trace lowered, ``short_conv_pallas`` or
``short_conv_plain``, a call each. Both run under the scope
``short_conv``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _count
from . import causal_conv as cc
from .cost_model import _TILE_COLS, _VMEM_BUDGET_BYTES

#: position tiles a grid step may take, largest first
_ROWS = (1024, 512, 256, 128)
#: float32 working copies of 128 channels of a tile the pricing allows:
#: forward, backward
_WORK = (8, 12)


def _plain(bcx, conv_w):
    """y (B, S, E) in ``jax.numpy``."""
    taps, e = conv_w.shape
    s, f32 = bcx.shape[1], jnp.float32
    u = bcx[..., :e].astype(f32) * bcx[..., 2 * e:].astype(f32)
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    z = sum(padded[:, j:j + s] * conv_w[j].astype(f32) for j in range(taps))
    return (bcx[..., e:2 * e].astype(f32) * z).astype(bcx.dtype)


# ---------------------------------------------------------------------------
# the kernels

def _vmem(rows, cols, itemsize, backward):
    """VMEM of a grid step. Forward: B, C, x in and y out, B's and x's
    halos before the tile. Backward: dy, B, C, x in and one block of
    d(B|C|x) out, four halos (dy and C after the tile, B and x before
    it), and du kept in float32 between the first two kinds."""
    if backward:
        return cc.vmem_bytes(rows, cols, itemsize, 5, 4, _TILE_COLS,
                             _WORK[1]) + rows * cols * 4
    return cc.vmem_bytes(rows, cols, itemsize, 4, 2, _TILE_COLS, _WORK[0])


def tiles(s, e, itemsize, backward):
    """(rows, channels) of a grid step's block: the most positions, then
    the most channels (a divisor of ``e`` in whole 128-lane columns)
    that stay inside the VMEM budget; None when nothing fits."""
    for rows in _ROWS:
        if s % rows:
            continue
        for cols in range(e, 0, -_TILE_COLS):
            if e % cols == 0 and _vmem(rows, cols, itemsize, backward) \
                    <= _VMEM_BUDGET_BYTES:
                return rows, cols
    return None


def eligible(s, e, taps, itemsize):
    """Can the kernels take this layer? Channels in whole 128-lane
    columns, a convolution whose reach fits a halo, positions in whole
    tiles of ``_ROWS``, both passes' blocks inside VMEM."""
    return (e % _TILE_COLS == 0 and 1 <= taps <= cc.halo(itemsize) + 1
            and tiles(s, e, itemsize, False) is not None
            and tiles(s, e, itemsize, True) is not None)


def _lanes(cols):
    """The 128-lane column slices of a block, one at a time in a kernel."""
    return [slice(j, j + _TILE_COLS) for j in range(0, cols, _TILE_COLS)]


def _call(kernel, name, grid, in_specs, out_specs, out_shape, interpret,
          scratch=()):
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=list(scratch),
        # the kinds in order: du is carried from one to the next
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            ("parallel",) * (len(grid) - 1) + ("arbitrary",))),
        interpret=interpret,
        name=name)      # the HLO instruction's name on a device trace


def _gated(b_ref, x_ref, bh_ref, xh_ref, ln, t):
    """u = B * x over the halo before the tile and the tile, float32."""
    f32 = jnp.float32
    before = jnp.where(t > 0, bh_ref[:, ln].astype(f32)
                       * xh_ref[:, ln].astype(f32), 0.0)
    return jnp.concatenate(
        [before, b_ref[:, ln].astype(f32) * x_ref[:, ln].astype(f32)], 0)


def _fwd_kernel(b_ref, c_ref, x_ref, bh_ref, xh_ref, w_ref, y_ref, *, taps):
    t, halo = pl.program_id(1), bh_ref.shape[0]
    for ln in _lanes(y_ref.shape[1]):
        z = cc.conv(_gated(b_ref, x_ref, bh_ref, xh_ref, ln, t),
                    w_ref[:, ln], taps)[halo:]
        y_ref[:, ln] = (c_ref[:, ln].astype(jnp.float32) * z).astype(
            y_ref.dtype)


def _forward(bcx, w, interpret):
    """The kernel ``short_conv_fwd``: y (B, S, E). Grid step (i, t, c):
    channel block c of each of ``bcx``'s three parts."""
    b, s, _ = bcx.shape
    taps, e = w.shape
    rows, cols = tiles(s, e, bcx.dtype.itemsize, False)
    halo = cc.halo(bcx.dtype.itemsize)
    nc, per = e // cols, rows // halo

    def part(k):
        return pl.BlockSpec((None, rows, cols),
                            lambda i, t, c: (i, t, c + k * nc))

    def before(k):
        return pl.BlockSpec(
            (None, halo, cols),
            lambda i, t, c: (i, jnp.maximum(t * per - 1, 0), c + k * nc))

    return _call(
        functools.partial(_fwd_kernel, taps=taps), "short_conv_fwd",
        (b, s // rows, nc),
        [part(0), part(1), part(2), before(0), before(2),
         pl.BlockSpec((taps, cols), lambda i, t, c: (0, c))],
        pl.BlockSpec((None, rows, cols), lambda i, t, c: (i, t, c)),
        jax.ShapeDtypeStruct((b, s, e), bcx.dtype), interpret,
    )(bcx, bcx, bcx, bcx, bcx, w)


def _bwd_kernel(dy_ref, dya_ref, b_ref, c_ref, x_ref, bh_ref, xh_ref,
                ca_ref, w_ref, d_ref, dw_ref, du_ref, *, taps):
    t, kind = pl.program_id(1), pl.program_id(3)
    last = pl.num_programs(1) - 1
    rows, halo, f32 = dy_ref.shape[0], bh_ref.shape[0], jnp.float32
    lanes = _lanes(d_ref.shape[1])

    def d_b():          # du from dz over the tile and the halo after it
        for ln in lanes:
            dz = jnp.concatenate([
                dy_ref[:, ln].astype(f32) * c_ref[:, ln].astype(f32),
                jnp.where(t < last, dya_ref[:, ln].astype(f32)
                          * ca_ref[:, ln].astype(f32), 0.0)], 0)
            du = cc.conv_back(dz, w_ref[:, ln], taps)[:rows]
            du_ref[:, ln] = du
            d_ref[:, ln] = (du * x_ref[:, ln].astype(f32)).astype(
                d_ref.dtype)

    def d_x():
        for ln in lanes:
            d_ref[:, ln] = (du_ref[:, ln] * b_ref[:, ln].astype(f32)).astype(
                d_ref.dtype)

    def d_c():          # and the weight's partial sums
        for ln in lanes:
            u = _gated(b_ref, x_ref, bh_ref, xh_ref, ln, t)
            z = cc.conv(u, w_ref[:, ln], taps)[halo:]
            dy = dy_ref[:, ln].astype(f32)
            d_ref[:, ln] = (dy * z).astype(d_ref.dtype)
            dz = dy * c_ref[:, ln].astype(f32)
            for j, row in enumerate(cc.taps_grad(
                    u, dz, taps, slice(halo, halo + rows))):
                dw_ref[j:j + 1, ln] = row

    pl.when(kind == 0)(d_b)
    pl.when(kind == 1)(d_x)
    pl.when(kind == 2)(d_c)


def _backward(dy, bcx, w, interpret):
    """The kernel ``short_conv_bwd``: (d(B|C|x) (B, S, 3E), the weight's
    partial gradients (B, tiles, taps, E) float32). Grid step (i, t, c,
    kind): channel block c, writing dB (kind 0), dx (1) or dC (2)."""
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = bcx.shape
    taps, e = w.shape
    rows, cols = tiles(s, e, bcx.dtype.itemsize, True)
    halo = cc.halo(bcx.dtype.itemsize)
    nc, per, nh = e // cols, rows // halo, s // halo

    def after(t):       # the halo block after tile t, in halo blocks
        return jnp.minimum((t + 1) * per, nh - 1)

    def block(k, size=rows, at=lambda t: t):
        return pl.BlockSpec((None, size, cols),
                            lambda i, t, c, kind: (i, at(t), c + k * nc))

    def before(t):
        return jnp.maximum(t * per - 1, 0)

    in_specs = [block(0), block(0, halo, after),           # dy
                block(0), block(1), block(2),               # B, C, x
                block(0, halo, before), block(2, halo, before),
                block(1, halo, after),
                pl.BlockSpec((taps, cols), lambda i, t, c, kind: (0, c))]
    # kinds 0, 1, 2 write the column blocks of B, x and C: 0, 2, 1
    out_specs = [pl.BlockSpec((None, rows, cols), lambda i, t, c, kind: (
                     i, t, c + nc * ((2 * kind) % 3))),
                 pl.BlockSpec((None, None, taps, cols),
                              lambda i, t, c, kind: (i, t, 0, c))]
    out_shape = [jax.ShapeDtypeStruct((b, s, 3 * e), bcx.dtype),
                 jax.ShapeDtypeStruct((b, s // rows, taps, e), jnp.float32)]
    return _call(
        functools.partial(_bwd_kernel, taps=taps), "short_conv_bwd",
        (b, s // rows, nc, 3), in_specs, out_specs, out_shape, interpret,
        [pltpu.VMEM((rows, cols), jnp.float32)],
    )(dy, dy, bcx, bcx, bcx, bcx, bcx, bcx, w)


# ---------------------------------------------------------------------------
# the differentiable entry

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pair(bcx, conv_w, interpret):
    return _pair_fwd(bcx, conv_w, interpret)[0]


def _pair_fwd(bcx, conv_w, interpret):
    return _forward(bcx, conv_w.astype(jnp.float32), interpret), \
        (bcx, conv_w)


def _pair_bwd(interpret, res, dy):
    bcx, conv_w = res
    with jax.named_scope("short_conv"):
        d, dw = _backward(dy, bcx, conv_w.astype(jnp.float32), interpret)
        return d, dw.sum((0, 1)).astype(conv_w.dtype)


_pair.defvjp(_pair_fwd, _pair_bwd)


def short_conv(bcx, conv_w, *, use_pallas=None):
    """y (B, S, E) from the projection's ``bcx`` (B, S, 3E), columns
    B | C | x: ``y = C * conv(B * x)``, the causal depthwise convolution
    ``conv_w`` (taps, E), no bias, its last tap the position's own.
    Differentiable in ``bcx`` and ``conv_w``.

    use_pallas: None = the kernels on TPU where ``eligible`` admits the
    layer, the plain twin elsewhere; True forces the kernels
    (interpreted off-TPU, for testing); False forces the twin.
    """
    _, s, cols = bcx.shape
    taps, e = conv_w.shape
    if cols != 3 * e:
        raise ValueError(f"bcx of {cols} columns for a convolution of "
                         f"{conv_w.shape}: B | C | x of {e} channels each")
    fits = eligible(s, e, taps, bcx.dtype.itemsize)
    on_tpu = jax.default_backend() == "tpu"
    if use_pallas is None:
        impl = "pallas" if on_tpu and fits else "plain"
    elif use_pallas:
        if not fits:
            raise ValueError(
                f"the short-convolution kernels cannot take {e} channels "
                f"and {taps} taps at {s} positions: channels in multiples "
                f"of {_TILE_COLS}, positions in multiples of {_ROWS[-1]}, "
                f"at most {cc.halo(bcx.dtype.itemsize) + 1} taps")
        impl = "pallas" if on_tpu else "interpret"
    else:
        impl = "plain"
    _count("short_conv_plain" if impl == "plain" else "short_conv_pallas")
    with jax.named_scope("short_conv"):
        if impl == "plain":
            return jax.checkpoint(_plain)(bcx, conv_w)
        return _pair(bcx, conv_w, impl == "interpret")
