"""What the Pallas kernels of a causal depthwise convolution share: the
halo of a position tile, the shifted multiply-adds over a tile stacked
with its halos, and the VMEM a grid step of such a kernel takes.

A kernel of this kind walks a sequence in tiles of positions. The
convolution ``y_t = sum_j w_j x_(t - taps + 1 + j)`` (the last tap the
position's own) reaches ``taps - 1`` positions before the tile, and its
adjoint as many after it: each is read as a *halo*, one sublane tile of
rows of the same array, the block just before or just after the tile's
(zero past either end of the sequence). Stacked with the tile, the taps
are sublane rotations (``pltpu.roll``) of the stack; the rows a rotation
wraps around are never kept. ``kernels/delta_prologue.py`` (Gated
DeltaNet) and ``kernels/short_conv.py`` (LFM2's gated short
convolution) are built on these.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


def halo(itemsize):
    """Rows of a halo block: one sublane tile of the input's dtype."""
    return 8 * max(1, 4 // itemsize)


def vmem_bytes(rows, cols, itemsize, blocks, halos, work_cols, work):
    """VMEM of a grid step over ``rows`` positions and ``cols`` channels:
    ``blocks`` arrays of the tile's size and ``halos`` halo blocks in the
    input's dtype, each double-buffered; the weight's and its partial
    gradient's float32 blocks (the taps padded to a sublane tile); and
    ``work`` float32 working copies of ``work_cols`` channels of the tile
    with a halo on either side."""
    h = halo(itemsize)
    tiles = 2 * itemsize * cols * (blocks * rows + halos * h)
    weights = 2 * 2 * 8 * cols * 4
    return tiles + weights + work * (rows + 2 * h) * work_cols * 4


def conv(ext, w, taps):
    """``y_p = sum_j w_j ext_(p - taps + 1 + j)`` over the rows of ``ext``
    (n, d) float32, ``w`` (taps, d), the taps summed in order; the first
    ``taps - 1`` rows wrap around and are never kept."""
    y = None
    for j in range(taps):
        back = taps - 1 - j
        term = (pltpu.roll(ext, back, 0) if back else ext) * w[j:j + 1, :]
        y = term if y is None else y + term
    return y


def conv_back(ext, w, taps):
    """The adjoint of ``conv``: ``g_p = sum_j w_j ext_(p + taps - 1 - j)``
    over the rows of ``ext`` (n, d) float32; the last ``taps - 1`` rows
    wrap around and are never kept."""
    n, g = ext.shape[0], None
    for j in range(taps):
        ahead = taps - 1 - j
        term = (pltpu.roll(ext, n - ahead, 0) if ahead else ext) \
            * w[j:j + 1, :]
        g = term if g is None else g + term
    return g


def taps_grad(ext, dy, taps, keep):
    """``dw_j = sum_p ext_(p - taps + 1 + j) dy_p`` over the rows ``keep``
    of the stack: one (1, d) float32 row a tap."""
    rows = []
    for j in range(taps):
        back = taps - 1 - j
        xs = pltpu.roll(ext, back, 0) if back else ext
        rows.append(jnp.sum(xs[keep] * dy, 0, keepdims=True))
    return rows
