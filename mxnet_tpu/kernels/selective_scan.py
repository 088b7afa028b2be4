"""Selective scan: the recurrence of a Mamba layer (Gu and Dao,
arXiv:2312.00752) as a Pallas kernel pair.

Per channel c and state n, with ``h_0 = 0`` and for t = 1..S

    delta_t[c] = softplus(dt_t[c] + dt_bias[c])
    h_t[c, n] = exp(delta_t[c] A[c, n]) h_(t-1)[c, n]
                + delta_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n C_t[n] h_t[c, n] + D[c] u_t[c]
    g_t[c]    = y_t[c] silu(z_t[c])

``A <= 0`` a channel's decay rates (float32), delta its step sizes,
taken from the raw dt in float32 inside the kernels as Mamba's own
kernel takes them, B and C shared by every channel; z may be read in
place from a wider array (the input projection's ``[u | z]``). The state
is a (N, channels) float32 array a sequence, updated once a position
with a decay that differs by position, channel and state: S dependent
steps of element-wise work, which no product form shortens without
exponentials of positive sums.

Two kernels, named so that a device trace shows them by instruction, on
the grid (B, channel tiles, chunks of positions), chunks innermost and in
order (reversed backward):

- ``ssm_scan_fwd``: the state in float32 VMEM scratch from a tile's
  first chunk to its last; each chunk's incoming state written out for
  the backward; ``exp(delta A)`` on the EUP; the read-out ``C_t . h_t``,
  ``D u`` and the gate ``silu(z)`` inside, so that g leaves in the
  input's dtype and y never reaches HBM.
- ``ssm_scan_bwd``: the chunk's states again from its stored incoming
  state (into a (chunk + 1, N, tile) float32 scratch, one a position) and
  the read-out's cotangent ``dy = dg silu(z)`` for the whole chunk; then
  the walk backwards, which carries only the recurrence:
  ``dh <- dh + C_t dy_t``, stored for each position into a (chunk, N,
  tile) float32 scratch, then ``dh <- exp(delta_t A) dh``, ``dh`` kept
  in scratch across chunks. Everything else leaves the serial loops for
  a pass over the chunk after the walk, eight positions a step, that
  reads the states, the stored ``dh`` and the chunk's inputs: dC and dB
  a (position, state) pair, summed over the tile's channels (partial
  sums, added over the tiles after the kernel); the read-out y, du and
  d(dt) (through softplus) a position and channel, summed over the
  states; dz a position and channel; dA, dD and d(dt_bias) summed over
  the tile's positions in blocks that stay resident across its chunks.

Inside a chunk a loop takes ``_GROUP`` positions at a time (unrolled,
the backward's two groups an iteration): its rows of u, dt, z are
loaded once and B and C come as (N, _GROUP) blocks with the positions
along the lanes (``_by_group``). The forward selects a position's
read-out into the group's block, which is stored whole. The backward's
sums over channels fold a tile's lanes to 128 and transpose eight
positions' (N, 128) blocks, so that vector adds down the sublanes take
them (``_lane_sums``); its sums over states fold eight positions' (8,
tile) blocks into one in three halving steps (``_sublane_sums``): whole
blocks, no per-position select and no cross-lane reduction. The
backward's exponentials are ``2^(delta A log2 e)``, A scaled once.

The plain twin (``use_pallas=False``, off-TPU, and whatever ``eligible``
refuses) is the recurrence under ``lax.scan`` over positions in float32,
differentiated by JAX: what every test compares the kernels with.
``kernels.counters()`` counts which one a trace lowered,
``ssm_scan_pallas`` or ``ssm_scan_plain``, and ``ssm_scan_chunks``: the
chunks a channel tile the kernels' passes of a trace walk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import _count
from .cost_model import _TILE_COLS, _VMEM_BUDGET_BYTES

#: positions a loop iteration takes, unrolled: bfloat16's sublane tile
_GROUP = 16
#: positions a grid step takes, and the widest channel tile
_CHUNK = 256
_LANES = 256
_LOG2E = 1.4426950408889634


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _silu_parts(z):
    """(sigmoid(z), silu(z)) in float32."""
    s = _sigmoid(z)
    return s, z * s


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


# ---------------------------------------------------------------------------
# the plain twin

def _plain(u, dt, a, b, c, d, z, bias):
    """The recurrence under ``lax.scan`` over positions, float32."""
    f32 = jnp.float32
    uf = u.astype(f32)
    df = _softplus(dt.astype(f32) + bias.astype(f32))
    af, bf, cf = a.astype(f32), b.astype(f32), c.astype(f32)

    def step(h, xs):
        ut, dt, bt, ct = xs         # (B, Cn), (B, Cn), (B, N), (B, N)
        h = jnp.exp(dt[..., None] * af) * h \
            + (dt * ut)[..., None] * bt[:, None, :]
        return h, jnp.einsum("bcn,bn->bc", h, ct,
                             precision=lax.Precision.HIGHEST)

    h0 = jnp.zeros(u.shape[:1] + a.shape, f32)
    _, y = lax.scan(step, h0, tuple(jnp.moveaxis(x, 1, 0)
                                    for x in (uf, df, bf, cf)))
    y = jnp.moveaxis(y, 0, 1) + d.astype(f32) * uf
    return (y * _silu_parts(z.astype(f32))[1]).astype(u.dtype)


# ---------------------------------------------------------------------------
# the kernels

def _select(acc, piece, j, axis):
    """``acc`` with its row (axis 0) or column (axis 1) ``j`` replaced by
    ``piece``."""
    at = lax.broadcasted_iota(jnp.int32, acc.shape, axis)
    return jnp.where(at == j, piece, acc)


def _rows(i):
    return pl.ds(pl.multiple_of(i * _GROUP, _GROUP), _GROUP)


def _group_inputs(i, u_ref, d_ref, b_ref, c_ref, bias_ref):
    """A group's delta and delta * u (G, tile), its B, C (N, G), and its
    raw step sizes with the bias."""
    rows = _rows(i)
    raw = d_ref[rows, :].astype(jnp.float32) + bias_ref[...]
    dl = _softplus(raw)
    du = dl * u_ref[rows, :].astype(jnp.float32)
    return dl, du, b_ref[i], c_ref[i], raw


def _fwd_kernel(u_ref, d_ref, z_ref, b_ref, c_ref, a_ref, dd_ref, bias_ref,
                g_ref, s_ref, h_scr, y_scr, *, groups):
    """Grid (B, channel tiles, chunks), chunks innermost: the state in
    ``h_scr`` across a tile's chunks, its value entering the chunk
    written to ``s_ref``."""
    @pl.when(pl.program_id(2) == 0)
    def _first():
        h_scr[...] = jnp.zeros_like(h_scr)

    s_ref[...] = h_scr[...]
    a = a_ref[...]

    def group(i, h):
        dl, du, bg, cg, _ = _group_inputs(i, u_ref, d_ref, b_ref, c_ref,
                                          bias_ref)
        y = jnp.zeros(dl.shape, jnp.float32)
        for j in range(_GROUP):
            h = jnp.exp(dl[j:j + 1] * a) * h + bg[:, j:j + 1] * du[j:j + 1]
            y = _select(y, jnp.sum(cg[:, j:j + 1] * h, axis=0,
                                   keepdims=True), j, 0)
        y_scr[_rows(i), :] = y
        return h

    h_scr[...] = lax.fori_loop(0, groups, group, h_scr[...])
    u = u_ref[...].astype(jnp.float32)
    gate = _silu_parts(z_ref[...].astype(jnp.float32))[1]
    g_ref[...] = ((y_scr[...] + dd_ref[...] * u) * gate).astype(g_ref.dtype)


def _fold_lanes(x):
    """(N, tile) -> (N, 128): the tile's 128-lane pieces added."""
    out = x[:, :_TILE_COLS]
    for k in range(_TILE_COLS, x.shape[1], _TILE_COLS):
        out = out + x[:, k:k + _TILE_COLS]
    return out


def _fold_states(x):
    """(N, tile) -> (8, tile): the state's sublane tiles added."""
    out = x[:8]
    for k in range(8, x.shape[0], 8):
        out = out + x[k:k + 8]
    return out


def _lane_sums(blocks):
    """Eight positions' (N, 128) blocks -> (1, 8 N): each (position,
    state) row summed over its lanes, position-major along the result's
    lanes. One transpose puts the rows' sums down the sublanes, where
    plain vector adds take them, instead of a cross-lane reduction a row."""
    return jnp.sum(jnp.concatenate(blocks, axis=0).T, axis=0, keepdims=True)


def _sublane_sums(blocks):
    """Eight positions' (8, tile) blocks -> (8, tile), row j the sum of
    block j's rows: three halving steps, each adding rows ``k`` apart
    with those of the second half's blocks rolled in."""
    row = lax.broadcasted_iota(jnp.int32, blocks[0].shape, 0)
    for k in (4, 2, 1):
        low = (row & k) == 0
        half = len(blocks) // 2
        nxt = []
        for p in range(half):
            keep = jnp.where(low, blocks[p], blocks[p + half])
            other = jnp.where(low, blocks[p + half], blocks[p])
            moved = _roll(other, 8 - k)         # other[row + k]
            if k < 4:                           # other[row - k] above
                moved = jnp.where(low, moved, _roll(other, k))
            nxt.append(keep + moved)
        blocks = nxt
    return blocks[0]


def _roll(x, shift):
    """x's rows rolled down by ``shift`` (``jnp.roll`` on axis 0)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.roll(x, shift, 0)


def _pairs(groups, body, carry, reverse=False):
    """``carry = body(i, carry)`` for the groups i in order (reversed),
    two groups a loop iteration, so that the scheduler overlaps one
    group's set-up with the other's work; an odd group last."""
    def two(k, carry):
        i = groups - 1 - 2 * k if reverse else 2 * k
        return body(i - 1 if reverse else i + 1, body(i, carry))

    carry = lax.fori_loop(0, groups // 2, two, carry)
    if groups % 2:
        carry = body(0 if reverse else groups - 1, carry)
    return carry


def _bwd_kernel(u_ref, d_ref, z_ref, b_ref, c_ref, a_ref, dd_ref, bias_ref,
                s_ref, dg_ref, du_ref, ddl_ref, dz_ref, db_ref, dc_ref,
                da_ref, dD_ref, dbias_ref, dh_scr, hs_scr, y_scr, dhs_scr, *,
                groups):
    """The same grid, the chunks reversed: a tile's last chunk first,
    ``dh`` in ``dh_scr``; ``hs_scr[t]`` the state entering position t of
    the chunk (``hs_scr[t + 1]`` leaving it), ``dhs_scr[t]`` the state's
    gradient at t. dA, dD and the bias's gradient accumulate in their
    resident output blocks; dB and dC leave a row of eight positions'
    (position, state) sums at a time."""
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _first():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        da_ref[...] = jnp.zeros_like(da_ref)
        dD_ref[...] = jnp.zeros_like(dD_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    a = a_ref[...]
    a2 = a * _LOG2E        # exp(delta A) = 2^(delta a2)
    hs_scr[0] = s_ref[...]

    def again(i, h):        # the forward of the chunk, every state kept
        dl, du, bg, _, _ = _group_inputs(i, u_ref, d_ref, b_ref, c_ref,
                                         bias_ref)
        for j in range(_GROUP):
            h = jnp.exp2(dl[j:j + 1] * a2) * h + bg[:, j:j + 1] * du[j:j + 1]
            hs_scr[i * _GROUP + j + 1] = h
        return h

    _pairs(groups, again, s_ref[...])
    # g = (y + D u) silu(z): the read-out's cotangent dy for the chunk at
    # once, into y_scr
    dy = dg_ref[...].astype(f32) * _silu_parts(z_ref[...].astype(f32))[1]
    dD_ref[...] += jnp.sum(dy * u_ref[...].astype(f32), axis=0,
                           keepdims=True)
    y_scr[...] = dy

    def walk_group(i, dh):  # the recurrence alone, backwards
        dl, _, _, cg, _ = _group_inputs(i, u_ref, d_ref, b_ref, c_ref,
                                        bias_ref)
        dyg = y_scr[_rows(i), :]
        for j in reversed(range(_GROUP)):
            dh = dh + cg[:, j:j + 1] * dyg[j:j + 1]
            dhs_scr[i * _GROUP + j] = dh
            dh = jnp.exp2(dl[j:j + 1] * a2) * dh
        return dh

    dh_scr[...] = _pairs(groups, walk_group, dh_scr[...], reverse=True)

    def after(i, carry):    # a group's gradients from the stored states
        da, dbias = carry
        rows = _rows(i)
        dl, du, bg, cg, raw = _group_inputs(i, u_ref, d_ref, b_ref, c_ref,
                                            bias_ref)
        dyg = y_scr[rows, :]
        ys, dus, dxa = [], [], []
        for half in (0, 8):
            cs, bs, us, xs, hy = [], [], [], [], []
            for j in range(half, half + 8):
                t = i * _GROUP + j
                dh, hp, h = dhs_scr[t], hs_scr[t], hs_scr[t + 1]
                hy.append(_fold_states(h * cg[:, j:j + 1]))
                cs.append(_fold_lanes(h * dyg[j:j + 1]))
                bs.append(_fold_lanes(dh * du[j:j + 1]))
                us.append(_fold_states(dh * bg[:, j:j + 1]))
                dexp = dh * hp * jnp.exp2(dl[j:j + 1] * a2)   # of delta A
                da = da + dexp * dl[j:j + 1]
                xs.append(_fold_states(dexp * a))
            at = pl.ds(2 * i + half // 8, 1)
            dc_ref[at, :] = _lane_sums(cs)
            db_ref[at, :] = _lane_sums(bs)
            ys.append(_sublane_sums(hy))        # the read-out y
            dus.append(_sublane_sums(us))       # of delta u
            dxa.append(_sublane_sums(xs))
        y, dus, dxa = (jnp.concatenate(x) for x in (ys, dus, dxa))
        uu = u_ref[rows, :].astype(f32)
        dds = (dxa + dus * uu) * _sigmoid(raw)  # of the raw step size
        du_ref[rows, :] = (dus * dl + dd_ref[...] * dyg).astype(du_ref.dtype)
        ddl_ref[rows, :] = dds.astype(ddl_ref.dtype)
        zz = z_ref[rows, :].astype(f32)
        sz = _sigmoid(zz)
        dz_ref[rows, :] = (dg_ref[rows, :].astype(f32) * (y + dd_ref[...] * uu)
                           * sz * (1.0 + zz * (1.0 - sz))).astype(dz_ref.dtype)
        return da, dbias + jnp.sum(dds, axis=0, keepdims=True)

    da, dbias = _pairs(groups, after, (jnp.zeros_like(da_ref),
                                       jnp.zeros_like(dbias_ref)))
    da_ref[...] += da
    dbias_ref[...] += dbias


# ---------------------------------------------------------------------------
# calls

def chunk_rows(s, chunk=_CHUNK):
    """Positions a grid step takes: ``chunk``, or the sequence rounded up
    to whole groups where that is shorter."""
    return min(chunk, -(-s // _GROUP) * _GROUP)


def lanes_of(channels):
    """The channel tile: 256 lanes where the channels divide into them,
    else 128; None where they do not divide into 128."""
    for lanes in (_LANES, _TILE_COLS):
        if channels % lanes == 0:
            return lanes
    return None


def vmem_bytes(rows, lanes, n, itemsize):
    """What the backward, the larger pass, holds: u, z, dg, du, dz in the
    input's dtype and dt, d(dt) float32 at most, a chunk by a tile each, B,
    C as (N, 128)-padded group blocks, dB, dC a chunk's (position, state)
    sums, A, dA, the states and dh (N, tile) float32, all blocks
    double-buffered; then the chunk's states, the state's gradient at each
    position and dy, float32 scratch."""
    seq = rows * lanes * (5 * itemsize + 2 * 4)
    small = 2 * (rows // _GROUP) * n * _TILE_COLS * 4 + 2 * rows * n * 4
    tile = 4 * n * lanes * 4
    scratch = (2 * rows + 1) * n * lanes * 4 + rows * lanes * 4 \
        + n * lanes * 4
    return 2 * (seq + small + tile) + scratch


def eligible(channels, n, itemsize, chunk=_CHUNK):
    """Can the kernels take these blocks? The channels in whole tiles of
    the 128 lanes, the state in whole sublane tiles, and the backward's
    blocks and scratch inside the VMEM budget."""
    lanes = lanes_of(channels)
    return lanes is not None and n % 8 == 0 and vmem_bytes(
        chunk_rows(chunk, chunk), lanes, n, itemsize) <= _VMEM_BUDGET_BYTES


def _by_group(x):
    """(B, S, N) -> (B, S / G, N, G) float32: a group's positions along
    the lanes."""
    b, s, n = x.shape
    return x.astype(jnp.float32).reshape(b, s // _GROUP, _GROUP, n) \
        .transpose(0, 1, 3, 2)


def _specs(rows, lanes, n, at=lambda t: t, zc=0):
    """Block specs on the grid (B, tiles, chunks) at the chunk ``at(t)``:
    a chunk by a tile of a (B, S, channels) array (z's from the tile
    ``zc`` on); a chunk's groups of a (B, S / G, N, G) one; a (N, tile)
    row block of A^T and a (1, tile) one of D or the bias; a chunk's (N,
    tile) state; a tile's chunk of (position, state) sums over its
    channels, eight positions a row of (chunk / 8, 8 N); a tile's (N,
    tile) and (1, tile) sums over positions, resident across the
    chunks."""
    return dict(
        seq=pl.BlockSpec((None, rows, lanes),
                         lambda b, c, t: (b, at(t), c)),
        z=pl.BlockSpec((None, rows, lanes),
                       lambda b, c, t: (b, at(t), c + zc)),
        grp=pl.BlockSpec((None, rows // _GROUP, n, _GROUP),
                         lambda b, c, t: (b, at(t), 0, 0)),
        a=pl.BlockSpec((n, lanes), lambda b, c, t: (0, c)),
        d=pl.BlockSpec((1, lanes), lambda b, c, t: (0, c)),
        state=pl.BlockSpec((None, None, n, lanes),
                           lambda b, c, t: (b, at(t), 0, c)),
        sum_c=pl.BlockSpec((None, None, None, rows // 8, 8 * n),
                           lambda b, c, t: (b, c, at(t), 0, 0)),
        sum_a=pl.BlockSpec((None, n, lanes), lambda b, c, t: (b, 0, c)),
        sum_d=pl.BlockSpec((None, 1, lanes), lambda b, c, t: (b, 0, c)),
    )


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          interpret):
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name)      # the HLO instruction's name on a device trace


def _row(v):
    return v.reshape(1, -1).astype(jnp.float32)


def _forward(u, dt, at, bg, cg, d, z, bias, how):
    """(g, states): the kernel ``ssm_scan_fwd``; ``at`` is A^T (N,
    channels), ``bg``, ``cg`` by group; states (B, chunks, N, channels)
    float32, each chunk's incoming state."""
    rows, z_col, interpret = how
    b, s, ch = u.shape
    n, lanes = at.shape[0], lanes_of(ch)
    _count("ssm_scan_chunks", s // rows)
    sp = _specs(rows, lanes, n, zc=z_col // lanes)
    return _call(
        functools.partial(_fwd_kernel, groups=rows // _GROUP),
        "ssm_scan_fwd", (b, ch // lanes, s // rows),
        [sp["seq"], sp["seq"], sp["z"], sp["grp"], sp["grp"], sp["a"],
         sp["d"], sp["d"]],
        [sp["seq"], sp["state"]],
        [jax.ShapeDtypeStruct(u.shape, u.dtype),
         jax.ShapeDtypeStruct((b, s // rows, n, ch), jnp.float32)],
        [(n, lanes), (rows, lanes)], interpret,
    )(u, dt, z, bg, cg, at, _row(d), _row(bias))


def _backward(u, dt, at, bg, cg, d, z, bias, states, dg, how):
    """Gradients of u, dt, z (the channels read), A^T, B, C, D and the
    bias by the kernel ``ssm_scan_bwd``."""
    rows, z_col, interpret = how
    b, s, ch = u.shape
    n, lanes = at.shape[0], lanes_of(ch)
    tiles, chunks = ch // lanes, s // rows
    _count("ssm_scan_chunks", chunks)
    sp = _specs(rows, lanes, n, at=lambda t: chunks - 1 - t,
                zc=z_col // lanes)
    sums = (b, tiles, chunks, rows // 8, 8 * n)   # (S, N) a tile
    du, ddl, dz, db, dc, da, dd, dbias = _call(
        functools.partial(_bwd_kernel, groups=rows // _GROUP),
        "ssm_scan_bwd", (b, tiles, chunks),
        [sp["seq"], sp["seq"], sp["z"], sp["grp"], sp["grp"], sp["a"],
         sp["d"], sp["d"], sp["state"], sp["seq"]],
        [sp["seq"], sp["seq"], sp["seq"], sp["sum_c"], sp["sum_c"],
         sp["sum_a"], sp["sum_d"], sp["sum_d"]],
        [jax.ShapeDtypeStruct(u.shape, u.dtype),
         jax.ShapeDtypeStruct(dt.shape, dt.dtype),
         jax.ShapeDtypeStruct(u.shape, z.dtype),
         jax.ShapeDtypeStruct(sums, jnp.float32),
         jax.ShapeDtypeStruct(sums, jnp.float32),
         jax.ShapeDtypeStruct((b, n, ch), jnp.float32),
         jax.ShapeDtypeStruct((b, 1, ch), jnp.float32),
         jax.ShapeDtypeStruct((b, 1, ch), jnp.float32)],
        [(n, lanes), (rows + 1, n, lanes), (rows, lanes), (rows, n, lanes)],
        interpret,
    )(u, dt, z, bg, cg, at, _row(d), _row(bias), states,
      dg.astype(u.dtype))
    return (du, ddl, dz, da.sum(0), db.sum(1).reshape(b, s, n),
            dc.sum(1).reshape(b, s, n), dd.sum((0, 1)), dbias.sum((0, 1)))


# ---------------------------------------------------------------------------
# the differentiable entry

@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _scan(u, dt, a, b, c, d, z, bias, how):
    return _scan_fwd(u, dt, a, b, c, d, z, bias, how)[0]


def _scan_fwd(u, dt, a, b, c, d, z, bias, how):
    at, bg, cg = a.astype(jnp.float32).T, _by_group(b), _by_group(c)
    g, states = _forward(u, dt, at, bg, cg, d, z, bias, how)
    return g, (u, dt, a, b, c, d, z, bias, states)


def _scan_bwd(how, res, dg):
    u, dt, a, b, c, d, z, bias, states = res
    with jax.named_scope("ssm_scan_bwd"):
        at, bg, cg = a.astype(jnp.float32).T, _by_group(b), _by_group(c)
        du, ddl, dz, dat, db, dc, dd, dbias = _backward(
            u, dt, at, bg, cg, d, z, bias, states, dg, how)
        z_col = how[1]
        if dz.shape != z.shape:     # z read in place from a wider array
            dz = jnp.pad(dz, ((0, 0), (0, 0),
                              (z_col, z.shape[2] - z_col - dz.shape[2])))
        return (du, ddl, dat.T.astype(a.dtype), db.astype(b.dtype),
                dc.astype(c.dtype), dd.astype(d.dtype), dz,
                dbias.astype(bias.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(u, dt, A, B, C, D, z, dt_bias, z_col=0, chunk=_CHUNK,
                   use_pallas=None):
    """g (batch, S, channels) in u's dtype of the selective scan for u
    (batch, S, channels), dt (batch, S, channels) the raw step sizes
    (``delta = softplus(dt + dt_bias)`` in float32, dt_bias (channels,)),
    A (channels, N) the decay rates (<= 0, float32), B, C (batch, S, N),
    D (channels,) and z: channels ``z_col`` onwards of (batch, S, >=
    channels), read in place. Differentiable in all eight.

    use_pallas: None = the kernels on TPU where ``eligible`` admits the
    blocks, the ``lax.scan`` twin elsewhere; True forces the kernels
    (interpreted off-TPU, for testing); False forces the twin.
    """
    b, s, ch = u.shape
    n = A.shape[1]
    if dt.shape != u.shape or z.shape[:2] != (b, s) \
            or z.shape[2] < z_col + ch or A.shape != (ch, n) \
            or B.shape != (b, s, n) or C.shape != B.shape \
            or D.shape != (ch,) or dt_bias.shape != (ch,):
        raise ValueError(f"u {u.shape}, dt {dt.shape}, A {A.shape}, "
                         f"B {B.shape}, C {C.shape}, D {D.shape}, "
                         f"z {z.shape} from column {z_col}, dt_bias "
                         f"{dt_bias.shape}")
    lanes = lanes_of(ch)
    fits = eligible(ch, n, u.dtype.itemsize, chunk) and z_col % lanes == 0
    on_tpu = jax.default_backend() == "tpu"
    if use_pallas is None:
        impl = "pallas" if on_tpu and fits else "plain"
    elif use_pallas:
        if not fits:
            raise ValueError(
                f"the selective-scan kernels cannot take {ch} channels of "
                f"{n} states in chunks of {chunk}, z from column {z_col}: "
                f"channels and z's first in multiples of {_TILE_COLS}, "
                "states of 8, blocks inside VMEM")
        impl = "pallas" if on_tpu else "interpret"
    else:
        impl = "plain"
    _count("ssm_scan_plain" if impl == "plain" else "ssm_scan_pallas")
    with jax.named_scope("ssm_scan"):
        if impl == "plain":
            return _plain(u, dt, A, B, C, D, z[..., z_col:z_col + ch],
                          dt_bias)
        # whole chunks: a padded position comes after every real one and
        # reads u, z 0: it changes no real result and its g is 0
        rows = chunk_rows(s, chunk)
        pad = (-s) % rows
        if pad:
            u, dt, z, B, C = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                              for x in (u, dt, z, B, C))
        g = _scan(u, dt, A, B, C, D, z, dt_bias,
                  (rows, z_col, impl == "interpret"))
        return g[:, :s]
