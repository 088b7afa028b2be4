"""Flash attention: blockwise online-softmax attention as Pallas kernels.

NEW capability beyond the reference (MXNet 1.5 has no attention op —
SURVEY §5.7: long-context handling is a first-class requirement of the TPU
rebuild, not a port). The S_q x S_k scores never leave VMEM, in either
pass, so attention memory is O(S·D) and HBM carries q, k, v, o and their
gradients once. Design:

- tiles: ``choose_tiles`` sizes (bq, bk) from (S_q, S_k, D, itemsize)
  and the VMEM budget ``cost_model`` states — multiples of 128 that
  divide the sequence padded to 128, as large as the pass's cap and the
  budget allow, so a grid step carries enough work to hide its overhead.
  ``cost_model.pallas_vmem_bytes("attention", ...)`` prices the same
  tiles, so the gate and the kernel cannot disagree.
- both kernels take (B, H, S, D) as it is, one head a grid row: no
  (B*H, S, D) array exists in the program.
- forward ``flash_fwd``: grid (B, H, S_q/bq, S_k/bk), k innermost. A q
  tile stays in VMEM while k/v tiles stream past it; running (max, sum,
  acc) are float32 scratch; both products take MXU operands in the
  input's dtype with float32 accumulation. Causal tiles wholly above the
  diagonal are skipped and their k/v index maps clamp to the last live
  tile, so they cost no copy (each is still a grid step). Under a mask
  spec the grid is (B, H, visits): the live tiles alone, a q tile's k
  tiles one after another, the first of which clears the scratch and the
  last of which writes o. Under differentiation the kernel also writes
  the row log-sum-exp (float32, lane-dense (B, H, 1, S_q)).
- backward ``flash_bwd``: ONE fused kernel, grid (B, H, S_k/bk, S_q/bq),
  q innermost, on the transposed tile s^T = k q^T (bk, bq) so the row
  statistics (lse, delta = rowsum(do * o)) broadcast along sublanes.
  p^T = exp(s^T - lse) is recomputed, ds^T = p^T (dp^T - delta) stays
  float32 in VMEM; dk and dv tiles are float32 scratch resident over the
  q sweep; the (S_q, D) float32 dq of one head is the resident output
  block of the whole sweep. A head too long for that (16,384 x 128:
  8 MiB, twice that double-buffered) is cut into equal *segments* of
  query rows whose dq block fits (``choose_backward``): the grid gains
  the segment as an outer dimension, (B, H, segments, k tiles, q tiles
  of a segment), dk and dv leave once per segment and are summed over
  the segments where the group is summed, after the kernel. Under a
  mask spec the grid is (B, H, visits) however many segments there are:
  segment after segment, in each the k tiles of its band (as wide as the
  widest segment's live k tiles), for each the segment's live q tiles;
  a k tile's first visit in a segment clears dk_s and dv_s, its last
  writes them, and a k tile of the band that no query of the segment
  sees has one visit that does both and no product, so that the sum over
  segments reads no block that was never written. The kernel
  counts ``flash_bwd_pallas`` and its segments ``flash_bwd_q_segments``
  in ``kernels.counters()``; a shape beside whose smallest segment no
  tile fits takes the ``lax.scan`` backward and counts
  ``flash_bwd_scan``. Both passes count a head's grid steps and the
  live tiles among them, ``flash_grid_steps`` and
  ``flash_grid_steps_live``: apart by the dead tiles of the causal
  rectangle, and under a spec by the visits that only write zeros.
- which scores live: ``causal`` (the diagonal, decided from the indices
  of a rectangular grid as it always was), or a static ``mask`` spec
  (``BlockDiffusionMask``, ``SlidingWindowMask``: dead tiles may lie on
  either side of a query tile's live ones): at trace time the spec
  gives a table of tile kinds (dead, whole, partly masked) per (q tile,
  k tile), and each pass walks the table's live tiles as a list of
  *visits* in the order the rectangle swept them (``_visits``; as
  ``grouped_matmul`` walks its row tiles): per visit its q tile, its k
  tile, its kind and whether it opens or closes its sweep, prefetched
  scalar arrays that the index maps and the kernel read at the grid's one
  index. A block whose index is that of the visit before is not copied
  again; a dead tile is no grid step at all. A partly masked tile
  is computed by strips: the table also gives it the number of its
  *pattern*, which says for each strip of queries (512, or the largest
  halving of it that divides both tiles) the hull of the strip-sized
  sub-tiles of its keys that hold a live score. A regular spec has few
  patterns (the block-diffusion band and its block-causal diagonal; a
  window's diagonal and trailing edge), static data each kernel gets one
  unrolled branch for: a strip does its products, its statistics and its
  rows of the gradients on its hull alone, the spec's element rule on a
  column of its query ids and a row of its key ids; a pattern's strips
  go through each stage together, and a strip with no live key does
  nothing. No (S, S) array exists in HBM either way.
- grouped heads: q of H heads reads k, v of H / group heads in place
  through the index map. The backward writes dk, dv per query head and
  the group is summed after the kernel (a grid order that kept one k
  tile's dk, dv resident across the group would revisit each head's dq
  block once per k tile, which an output block cannot survive).
- off-TPU (tests, CPU) the same kernels run under interpret=True, or the
  pure-XLA path via flash_attention(..., use_pallas=False): plain
  attention forward, q-chunk recompute scan backward — the oracle the
  kernels are tested against.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax
from jax.experimental import pallas as pl

from . import _count
from .cost_model import _TILE_COLS, _VMEM_BUDGET_BYTES

_NEG = -1e30
_NT = (((1,), (1,)), ((), ()))  # a @ b.T

#: largest (bq, bk) each pass starts from, the fastest of those measured
#: on the chip at (2, 32, 2048, 64) bf16 (PERF.md section 6, PR 29); the
#: budget and the sequence only ever shrink them
_FWD_CAPS = (1024, 1024)
_BWD_CAPS = (512, 512)

# ---------------------------------------------------------------------------
# mask specs

#: kinds of a (q tile, k tile) pair in a mask's table; FIRST is added to
#: the first live k tile of a q tile (the backward's dq assigns there),
#: and a PARTIAL tile's pattern number counts in units of PATTERN
DEAD, WHOLE, PARTIAL, FIRST, PATTERN = 0, 1, 2, 4, 8

#: where a visit stands in its sweep (a q tile's k tiles forward, a k
#: tile's q tiles of one segment backward): the sweep's first visit
#: clears the scratch, its last writes the sweep's result
OPENS, CLOSES = 1, 2

#: rows of a strip of a partly masked tile, halved until it divides both
#: tiles. On the chip at (2, 32 over 4, 8192, 128) bf16 under the
#: block-diffusion mask the backward's (256, 512) tiles are fastest in
#: strips of 256 and the forward's (1024, 1024) in strips of 128, then
#: 256, then 512 (0.7 ms of a call's 6.1 apart), but every strip is an
#: unrolled body each layer's trace and lowering pays for in the step's
#: set-up (PERF.md section 6, PR 33). And how many patterns of strips a
#: kernel gets a branch for
_STRIP = 512
_MAX_PATTERNS = 4


@dataclasses.dataclass(frozen=True)
class BlockDiffusionMask:
    """Which scores live in block-diffusion training (BD3-LM, Arriola et
    al. 2025): the input is ``[xt ; x0]``, ``seq_len`` noised positions
    and then their ``seq_len`` clean copies, cut into blocks of
    ``block_length``. A noised query sees its own noised block (both
    directions) and the clean copy of every earlier block; a clean query
    sees the clean copies causally by blocks. Static and hashable: the
    kernels are specialised on it."""

    seq_len: int
    block_length: int

    def __post_init__(self):
        if self.seq_len % self.block_length:
            raise ValueError(f"seq_len {self.seq_len} is no multiple of "
                             f"block_length {self.block_length}")

    @property
    def size(self):
        return 2 * self.seq_len

    def _block(self, pos):
        b = self.block_length
        if b & (b - 1) == 0:    # a shift where the vector units have no divide
            return jnp.right_shift(pos, b.bit_length() - 1)
        return lax.div(pos, jnp.int32(b))

    def element(self, qid, kid):
        """The element rule on int32 index arrays that broadcast."""
        L = self.seq_len
        q_noisy, k_noisy = qid < L, kid < L
        qb = self._block(jnp.where(q_noisy, qid, qid - L))
        kb = self._block(jnp.where(k_noisy, kid, kid - L))
        # everything a query or a key decides alone first, as integers
        # (cheap where the ids are a column and a row), so that two
        # comparisons are all that broadcasts: a noised key lives for the
        # noised queries of its block, a clean one for the queries whose
        # block comes later, or is its own if they are clean
        k_noised = jnp.where(k_noisy, kb, -2)
        k_clean = jnp.where(k_noisy, self.size, kb)
        q_own = jnp.where(q_noisy, qb, -1)
        q_below = jnp.where(q_noisy, qb, qb + 1)
        return (k_noised == q_own) | (k_clean < q_below)

    def row_intervals(self):
        """(S, 2, 2) int: every query row's live keys as two [start, end)
        intervals, from which the tile table is counted."""
        L, b = self.seq_len, self.block_length
        blk = onp.arange(L) // b
        empty = onp.zeros(L, onp.int64)
        noisy = onp.stack([onp.stack([blk * b, (blk + 1) * b], -1),
                           onp.stack([L + empty, L + blk * b], -1)], 1)
        clean = onp.stack([onp.stack([L + empty, L + (blk + 1) * b], -1),
                           onp.stack([empty, empty], -1)], 1)
        return onp.concatenate([noisy, clean], 0)


@dataclasses.dataclass(frozen=True)
class SlidingWindowMask:
    """Which scores live under causal sliding-window attention over
    ``seq_len`` positions: a query sees its own position and the
    ``window - 1`` before it (``0 <= i - j < window``). Dead tiles lie on
    both sides of a query tile's band; the diagonal's triangle and the
    band's trailing edge, its complement, are the two partly masked
    patterns at square tiles. Static and hashable, as the other spec."""

    seq_len: int
    window: int

    def __post_init__(self):
        if self.window < 1:
            raise ValueError(f"a window of {self.window} positions")

    @property
    def size(self):
        return self.seq_len

    def element(self, qid, kid):
        """The element rule on int32 index arrays that broadcast: the
        window's edge is the query's own to work out, two comparisons
        broadcast."""
        return (kid <= qid) & (kid > qid - self.window)

    def row_intervals(self):
        """(S, 1, 2) int: every query row's live keys, one interval."""
        i = onp.arange(self.seq_len)
        return onp.stack([onp.maximum(i - self.window + 1, 0), i + 1],
                         -1)[:, None]


def _live_counts(mask, unit):
    """(S / unit, S / unit) live scores in each unit x unit square, from
    the spec's row intervals."""
    S = mask.size
    iv = mask.row_intervals()                       # (S, n, 2)
    edges = onp.arange(S // unit + 1) * unit
    lo = onp.maximum(iv[:, :, :1], edges[None, None, :-1])
    hi = onp.minimum(iv[:, :, 1:], edges[None, None, 1:])
    live = onp.maximum(hi - lo, 0).sum(1)           # (S, S / unit) per row
    return live.reshape(S // unit, unit, S // unit).sum(1)


def _strip_size(bq, bk):
    """The strip size of partly masked tiles (bq, bk): ``_STRIP``, halved
    until it divides both (128 divides every tile)."""
    sub = _STRIP
    while bq % sub or bk % sub:
        sub //= 2
    return sub


@functools.lru_cache(maxsize=None)
def mask_tile_table(mask, bq, bk, sub):
    """``(table, patterns)`` of ``mask`` at tiles (bq, bk) in strips of
    ``sub``, which divides both.

    ``table`` is (nq, nk) int32: DEAD, WHOLE or PARTIAL by the count of
    live scores in the tile, plus FIRST on each q tile's first live k
    tile, plus, from ``PATTERN`` up, a PARTIAL tile's pattern number.
    ``patterns[n - 1]`` is pattern n: for each strip of ``sub`` queries
    of the tile the hull ``(lo, hi)`` of the sub x sub sub-tiles of its
    keys in which any score lives, ``(0, 0)`` where none does. Number 0
    is the tile computed whole: one whose every hull is all of it, and
    what is left once ``_MAX_PATTERNS`` patterns, those that spare the
    most sub-tiles, have their numbers."""
    S = mask.size
    if S % bq or S % bk:
        raise ValueError(f"tiles ({bq}, {bk}) do not divide the mask's "
                         f"{S} positions")
    nq, nk = S // bq, S // bk
    rq, rk = bq // sub, bk // sub
    live = _live_counts(mask, sub).reshape(nq, rq, nk, rk)
    count = live.sum((1, 3))
    kinds = onp.where(count == 0, DEAD,
                      onp.where(count == bq * bk, WHOLE, PARTIAL))
    if (kinds == DEAD).all(1).any():
        raise ValueError("a q tile with no live score: its output and dq "
                         "would never be written")
    first = (kinds != DEAD).argmax(1)
    kinds[onp.arange(nq), first] += FIRST

    spared = {}         # pattern -> [sub-tiles it spares in all, its tiles]
    for i, j in zip(*onp.nonzero(count % (bq * bk))):
        any_live = live[i, :, j] > 0                # (rq, rk)
        lo = any_live.argmax(1)
        hi = onp.where(any_live.any(1), rk - any_live[:, ::-1].argmax(1), 0)
        pattern = tuple(zip(lo.tolist(), hi.tolist()))
        entry = spared.setdefault(pattern, [0, []])
        entry[0] += rq * rk - int((hi - lo).sum())
        entry[1].append((i, j))
    kept = sorted((p for p in spared if spared[p][0]),
                  key=lambda p: (-spared[p][0], p))[:_MAX_PATTERNS]
    for n, pattern in enumerate(kept, 1):
        rows, cols = zip(*spared[pattern][1])
        kinds[rows, cols] += n * PATTERN
    return kinds.astype(onp.int32), tuple(kept)


def _walk(kinds):
    """The visits of a pass that sweeps the rows of ``kinds`` one after
    another: int32 arrays ``(row, col, kind, edge)``, a visit for every
    live tile in row-major order, with its kind as the table has it and
    ``edge`` saying whether it OPENS or CLOSES its row's sweep. A row
    with no live tile still has a result to write: it gets one visit of
    kind DEAD that does both and no product, at the column of the visit
    before it (the first column where there is none), so that it copies
    nothing new."""
    live = (kinds & (WHOLE | PARTIAL)) != 0
    visited = live.copy()
    visited[~live.any(1), 0] = True
    row, col = onp.nonzero(visited)
    kind = onp.where(live[row, col], kinds[row, col], DEAD)
    for at in onp.nonzero(kind[1:] == DEAD)[0] + 1:
        col[at] = col[at - 1]
    turns = row[1:] != row[:-1]
    edge = OPENS * onp.r_[True, turns] + CLOSES * onp.r_[turns, True]
    return tuple(a.astype(onp.int32) for a in (row, col, kind, edge))


def _ref_attention(q, k, v, sm_scale, causal, s_k_real, spec=None):
    """Plain XLA attention, the correctness oracle.

    Causal masking is bottom-right aligned: query row i sits at global
    position i + (S_k - S_q), so decode-style calls (S_q=1 against a long
    KV cache) attend to the whole prefix. A ``mask`` spec gives a dense
    boolean mask from its element rule; grouped heads repeat k and v."""
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    S_q, S_k = q.shape[2], k.shape[2]
    kid = jnp.arange(S_k)[None, :]
    mask = kid < s_k_real
    if causal:
        qid = jnp.arange(S_q)[:, None] + (s_k_real - S_q)
        mask = mask & (kid <= qid)
    if spec is not None:
        mask = mask & spec.element(jnp.arange(S_q)[:, None], kid)
    s = jnp.where(mask[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# tiles

def _pad128(n):
    return -(-int(n) // _TILE_COLS) * _TILE_COLS


def _tile_under(padded, cap):
    """Largest multiple of 128 that divides ``padded`` and is <= cap."""
    n = padded // _TILE_COLS
    return _TILE_COLS * max(d for d in range(1, n + 1)
                            if n % d == 0 and d * _TILE_COLS <= cap)


def tile_vmem_bytes(bq, bk, s_q, d, itemsize, backward=False):
    """Upper estimate of the VMEM one grid step holds at tiles (bq, bk):
    every operand and result block double-buffered by the pipeline (the
    head dimension padded to the 128 lanes), the float32 scratch, and the
    float32 (bq, bk) working tiles with their operand-dtype casts.
    ``s_q`` is the query rows whose float32 dq the backward keeps
    resident: a whole head's, or a segment's of it. The v5e compiler
    takes about twice the tile this admits and refuses four times
    (tests/test_chip_compile.py holds both sides)."""
    lanes = _pad128(d)
    row = lanes * itemsize
    if backward:
        # q, do, k, v in; dk, dv out; lse, delta rows; dq of s_q rows
        blocks = 2 * bq * row + 4 * bk * row + 2 * 8 * bq * 4 \
            + _pad128(s_q) * lanes * 4
        scratch = 2 * bk * lanes * 4
        work = bq * bk * (3 * 4 + 2 * itemsize)  # s/p, dp/ds, ds.T + casts
    else:
        blocks = 2 * bq * row + 2 * bk * row + 8 * bq * 4  # q o, k v, lse
        scratch = 2 * bq * _TILE_COLS * 4 + bq * lanes * 4  # m l, acc
        work = bq * bk * (2 * 4 + itemsize)  # s, p + cast
    return 2 * blocks + scratch + work


def _tiles_within(rows_q, s_k, d, itemsize, backward, budget):
    """(bq, bk) as large as the pass's cap and ``budget`` allow, bq
    dividing ``rows_q`` (the padded query rows of the pass, or of one
    segment of the backward, whose dq is priced) and bk the padded keys;
    None when not even 128 x 128 fits."""
    sk_p = _pad128(s_k)
    cq, ck = _BWD_CAPS if backward else _FWD_CAPS
    while True:
        bq, bk = _tile_under(rows_q, cq), _tile_under(sk_p, ck)
        if tile_vmem_bytes(bq, bk, rows_q, d, itemsize,
                           backward) <= budget:
            return bq, bk
        if bq == bk == _TILE_COLS:
            return None
        if bq >= bk:  # shrink the larger side first
            cq = bq - _TILE_COLS
        else:
            ck = bk - _TILE_COLS


def choose_backward(s_q, s_k, d, itemsize, budget=_VMEM_BUDGET_BYTES):
    """``(bq, bk, rows)`` of the backward kernel, or None when nothing
    fits ``budget``: its tiles and the query rows of one *segment*, the
    part of a head whose float32 dq is the resident output block. A head
    whose whole dq fits beside some tile is one segment, at the tiles it
    always had. A longer head is cut into the fewest equal segments
    (multiples of 128 rows) beside which the tiles reach the pass's caps
    as far as the shape lets them; where none is, into the fewest beside
    which anything fits."""
    sq_p, sk_p = _pad128(s_q), _pad128(s_k)
    whole = _tiles_within(sq_p, s_k, d, itemsize, True, budget)
    if whole:
        return (*whole, sq_p)
    fallback = None
    for n in range(2, sq_p // _TILE_COLS + 1):
        rows = sq_p // n
        if sq_p % n or rows % _TILE_COLS:
            continue
        tiles = _tiles_within(rows, s_k, d, itemsize, True, budget)
        if tiles == (_tile_under(rows, _BWD_CAPS[0]),
                     _tile_under(sk_p, _BWD_CAPS[1])):
            return (*tiles, rows)
        if tiles and fallback is None:
            fallback = (*tiles, rows)
    return fallback


def choose_tiles(s_q, s_k, d, itemsize, backward=False,
                 budget=_VMEM_BUDGET_BYTES):
    """(bq, bk) for one pass of the kernel, or None when not even a
    128 x 128 tile fits ``budget``: a pure function of the shape. Tiles
    are multiples of 128 dividing the sequence padded to 128, as large as
    the pass's cap and the budget allow (the backward's beside the dq of
    the segment ``choose_backward`` gives it)."""
    if backward:
        chosen = choose_backward(s_q, s_k, d, itemsize, budget)
        return chosen and chosen[:2]
    return _tiles_within(_pad128(s_q), s_k, d, itemsize, False, budget)


def vmem_bytes(s_q, s_k, d, itemsize):
    """What the gate prices: the larger of the two passes' footprints at
    the tiles (and the backward's segment) the chooser gives them — at
    the 128 floor where nothing fits, so that the answer is over the
    budget then."""
    floor = (_TILE_COLS, _TILE_COLS)
    bq, bk, rows = choose_backward(s_q, s_k, d, itemsize) or (*floor, s_q)
    return max(
        tile_vmem_bytes(*(choose_tiles(s_q, s_k, d, itemsize) or floor),
                        s_q, d, itemsize),
        tile_vmem_bytes(bq, bk, rows, d, itemsize, True))


def _pad_rows(x, n):
    return jnp.pad(x, ((0, 0), (0, 0), (0, n), (0, 0))) if n else x


def _tile_mask(shape, q0, k0, q_axis, causal, s_k_real):
    """Which scores of the tile whose first query sits at global causal
    position ``q0`` and first key at ``k0`` count: real keys, on or under
    the (bottom-right aligned) diagonal. Queries run along ``q_axis``."""
    kid = k0 + lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    mask = kid < s_k_real
    if causal:
        mask &= kid <= q0 + lax.broadcasted_iota(jnp.int32, shape, q_axis)
    return mask


def _spec_where(mask, s, q0, k0, q_axis):
    """The scores ``s`` of a partly masked tile, or of a strip of one,
    under a mask spec: -1e30 where its element rule says dead, on the
    scores' own indices, queries along ``q_axis`` from ``q0`` and keys
    from ``k0``. The ids are a column and a row (or a row and a column):
    what the rule does to either alone costs a vector, not a tile."""
    along = lambda axis: tuple(n if d == axis else 1
                               for d, n in enumerate(s.shape))
    qid = q0 + lax.broadcasted_iota(jnp.int32, along(q_axis), q_axis)
    kid = k0 + lax.broadcasted_iota(jnp.int32, along(1 - q_axis), 1 - q_axis)
    return jnp.where(mask.element(qid, kid), s, _NEG)


#: the parts of a tile computed whole: all its rows by all its columns
_WHOLE_TILE = ((slice(None), slice(None)),)


def _strips(pattern, sub):
    """A pattern's strips as slices of the tile: ``(rows, cols)`` of each
    strip with a live key, and the rows of each with none."""
    rows = [slice(r * sub, (r + 1) * sub) for r in range(len(pattern))]
    return (tuple((at, slice(lo * sub, hi * sub))
                  for at, (lo, hi) in zip(rows, pattern) if hi > lo),
            tuple(at for at, (lo, hi) in zip(rows, pattern) if hi == lo))


def _from(origin, part):
    """Where the slice ``part`` of a tile starts, the tile at ``origin``."""
    return origin + part.start if part.start else origin


def _tile_kinds(i, kb, bq, bk, causal, s_k_real, causal_off):
    """(live, masked) of tile (i, kb): live unless wholly above the
    diagonal; masked when the diagonal or the keys' padding crosses it —
    a tile under both pays no iota, compare and select."""
    masked = (kb + 1) * bk > s_k_real
    if not causal:
        return True, masked
    live = kb * bk <= (i + 1) * bq - 1 + causal_off
    return live, masked | ((kb + 1) * bk - 1 > i * bq + causal_off)


def _causal_live(nq, nk, bq, bk, causal, causal_off):
    """How many of the (nq, nk) tiles ``_tile_kinds`` calls live."""
    i, kb = onp.ogrid[:nq, :nk]
    return (kb * bk <= (i + 1) * bq - 1 + causal_off).sum() if causal \
        else nq * nk


# ---------------------------------------------------------------------------
# forward

def _fa_kernel(*refs, bq, bk, nk, sm_scale, causal, s_k_real, causal_off,
               mask=None, strips=()):
    """Grid (B, H, nq, nk), kb innermost: one (bq, bk) tile per step. Only
    a q tile, one k/v tile and the (m, l, acc) scratch live in VMEM — true
    streaming, O(bq·D + bk·D) on-chip whatever the sequence length. The
    scratch carries the online softmax across the kb sweep (TPU grid steps
    run sequentially, scratch persists). ``rest`` is (m, l, acc), led by
    the lse output block when the backward will want it. Under a ``mask``
    spec the grid is (B, H, visits): the prefetched arrays of ``_walk``
    lead the refs, a visit's q tile, k tile, kind and place in the q
    tile's sweep, and ``strips`` are those of each pattern number the
    partly masked tiles bear."""
    if mask is not None:
        qt_ref, kt_ref, kind_ref, edge_ref, *refs = refs
    q_ref, k_ref, v_ref, o_ref, *rest = refs
    *lse_out, m_s, l_s, acc_s = rest
    if mask is None:
        i = pl.program_id(2)
        kb = pl.program_id(3)
    else:
        at = pl.program_id(2)
        i, kb, kind = qt_ref[at], kt_ref[at], kind_ref[at] & ~FIRST
        edge = edge_ref[at]

    @pl.when(kb == 0 if mask is None else (edge & OPENS) != 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def _tile(masked, parts=_WHOLE_TILE):
        """The online-softmax update of the tile: of its ``parts``, each a
        strip's query rows by the key columns of its hull, or the one
        part that is all of it. Every part's scores, then every part's
        statistics, then its products with v: the parts share no row, so
        one's latencies hide behind another's work."""
        vs, ss = [], []
        for rows, cols in parts:
            vs.append(v_ref[cols])
            s = lax.dot_general(q_ref[rows], k_ref[cols], _NT,
                                preferred_element_type=jnp.float32) * sm_scale
            if masked and mask is not None:
                s = _spec_where(mask, s, _from(i * bq, rows),
                                _from(kb * bk, cols), 0)
            elif masked:
                s = jnp.where(_tile_mask((bq, bk), i * bq + causal_off,
                                         kb * bk, 0, causal, s_k_real),
                              s, _NEG)
            ss.append(s)
        steps = []
        for (rows, _), s in zip(parts, ss):
            m = m_s[rows]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            steps.append((m_new, jnp.exp(s - m_new), jnp.exp(m - m_new)))
        for (rows, _), v, (m_new, p, alpha) in zip(parts, vs, steps):
            m_s[rows] = m_new
            l_s[rows] = l_s[rows] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_s[rows] = acc_s[rows] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    if mask is not None:
        pl.when(kind == WHOLE)(functools.partial(_tile, False))

        @pl.when(kind > WHOLE)
        def _partly():
            for n, parts, _ in strips:  # a strip with no live key adds nothing
                pl.when(kind == PARTIAL + n * PATTERN)(
                    functools.partial(_tile, True, parts))
    else:
        live, masked = _tile_kinds(i, kb, bq, bk, causal, s_k_real,
                                   causal_off)
        pl.when(live & masked)(functools.partial(_tile, True))
        pl.when(live & ~masked)(functools.partial(_tile, False))

    @pl.when(kb == nk - 1 if mask is None else (edge & CLOSES) != 0)
    def _finalize():
        l = jnp.maximum(l_s[:], 1e-30)
        o_ref[:] = (acc_s[:] / l).astype(o_ref.dtype)
        if lse_out:
            # the TPU's log is good to 2e-5 of its value (1e-4 at l = 600,
            # my chip run, PR 29), which a float32 backward shows: one
            # Newton step on exp(y) = l makes exp(s - lse) sum to 1 under
            # the exp the backward recomputes p with (5e-6 there)
            log_l = jnp.log(l)
            log_l += l * jnp.exp(-log_l) - 1.0
            # (bq, 1) column to the lane-dense (1, bq) row the backward
            # broadcasts along sublanes
            lse = jnp.broadcast_to(m_s[:] + log_l, (bq, _TILE_COLS))
            lse_out[0][:] = lse.T[:1]


def _segment_bands(kinds, nseg):
    """``(first, width)`` in k tiles of the band each of ``nseg`` equal
    segments of q tiles walks in the backward: ``width`` holds the live
    k tiles of the segment that has most, ``first[s]`` is where segment
    s's band starts (pulled back where it would pass the last k tile).
    One segment walks every k tile, as it always did."""
    nk = kinds.shape[1]
    if nseg == 1:
        return onp.zeros(1, onp.int32), nk
    live = ((kinds & (WHOLE | PARTIAL)) != 0).reshape(nseg, -1, nk).any(1)
    lo = live.argmax(1)
    hi = nk - live[:, ::-1].argmax(1)
    width = int((hi - lo).max())
    return onp.minimum(lo, nk - width).astype(onp.int32), width


def _visits(kinds, bands=None):
    """The walk of a pass over the live tiles of the table ``kinds``, as
    int32 arrays with an entry a visit. Forward ``(q tile, k tile, kind,
    edge)``: a q tile's live k tiles one after another. Backward, given
    the ``bands`` of its segments as ``_segment_bands`` has them, ``(q
    tile, k tile, kind, edge, slot)``: segment after segment, in each
    the k tiles of its band, for each the segment's live q tiles;
    ``slot`` is where the sweep's dk and dv go, the band's k tiles of
    segment after segment. A k tile of a band with no live q tile in the
    segment has the one DEAD visit ``_walk`` gives it, which writes its
    zeros."""
    if bands is None:
        return _walk(kinds)
    first, width = bands
    per = kinds.shape[0] // len(first)
    walks = []
    for s, k0 in enumerate(first.tolist()):
        at, i, kind, edge = _walk(kinds[s * per:(s + 1) * per,
                                        k0:k0 + width].T)
        walks.append((i + s * per, at + k0, kind, edge, at + s * width))
    return tuple(onp.concatenate(a) for a in zip(*walks))


def _count_steps(steps, live):
    """A head's grid steps in the pass being traced, and the live tiles
    among them."""
    _count("flash_grid_steps", int(steps))
    _count("flash_grid_steps_live", int(live))


def _mask_tiles(mask, q, k, bq, bk, nseg=0):
    """Strips, bands and visits of a mask spec's pass at tiles (bq, bk),
    the forward's or the backward's in ``nseg`` segments: ``(number,
    parts, empty)`` for each pattern number a partly masked tile bears,
    cut as ``_strips`` cuts them (number 0: the tile whole); each
    segment's first k tile and the k tiles a band holds (None forward);
    and the prefetched arrays of ``_visits``. Counts the pass's grid
    steps, and the strip-sized sub-tiles its partly masked tiles hold
    and those of them it computes."""
    S = q.shape[2]
    if mask.size != S or k.shape[2] != S:
        raise ValueError(f"mask over {mask.size} positions, q {q.shape} "
                         f"and k {k.shape}")
    if S % _TILE_COLS:
        raise ValueError(f"a mask spec needs a multiple of {_TILE_COLS} "
                         f"positions, got {S}")
    sub = _strip_size(bq, bk)
    kinds, patterns = mask_tile_table(mask, bq, bk, sub)
    bands = _segment_bands(kinds, nseg) if nseg else None
    visits = _visits(kinds, bands)
    _count_steps(visits[0].size, (visits[2] != DEAD).sum())
    held = (bq // sub) * (bk // sub)
    number = kinds[(kinds & PARTIAL) != 0] // PATTERN
    computed = onp.array([held] + [sum(hi - lo for lo, hi in pattern)
                                   for pattern in patterns])
    _count("flash_mask_subtiles_live", int(computed[number].sum()))
    _count("flash_mask_subtiles_tile", held * number.size)
    strips = tuple(
        (n,) + (_strips(patterns[n - 1], sub) if n else (_WHOLE_TILE, ()))
        for n in onp.unique(number).tolist())
    return strips, bands, [jnp.asarray(a) for a in visits]


def _pallas_forward(q, k, v, sm_scale, causal, interpret, with_lse=False,
                    bq=None, bk=None, mask=None):
    """The forward kernel at the chooser's tiles (``bq``/``bk`` override
    them for tests). Returns o, or (o, lse) with lse float32 of shape
    (B, H, 1, S_q padded to bq) when ``with_lse``. k and v may hold
    H / group heads."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, S_q, D = q.shape
    S_k, Dv = k.shape[2], v.shape[3]
    group = H // k.shape[1]
    visits = strips = ()
    if bq is None or bk is None:
        bq, bk = choose_tiles(S_q, S_k, max(D, Dv), q.dtype.itemsize)
    pq = (-S_q) % bq
    pk = (-S_k) % bk
    Sq_p, Sk_p = S_q + pq, S_k + pk
    nk = Sk_p // bk
    off = S_k - S_q
    if mask is not None:
        strips, _, visits = _mask_tiles(mask, q, k, bq, bk)
        _count("flash_mask_pallas")
    else:
        _count_steps(Sq_p // bq * nk, _causal_live(Sq_p // bq, nk, bq, bk,
                                                   causal, off))
    kern = functools.partial(_fa_kernel, bq=bq, bk=bk, nk=nk,
                             sm_scale=sm_scale, causal=causal,
                             s_k_real=S_k, causal_off=off, mask=mask,
                             strips=strips)

    def tile(*at):
        """(q tile, k tile) of a grid step: its own two indices, or the
        visit's under a spec."""
        if mask is None:
            return at
        v, qt, kt, *_ = at
        return qt[v], kt[v]

    def kv_map(b, h, *at):
        i, kb = tile(*at)
        if causal:
            # a tile above the diagonal is skipped: name the last live
            # one again and the pipeline copies nothing
            kb = jnp.minimum(kb, ((i + 1) * bq - 1 + off) // bk)
        if group > 1:
            h = h // group
        return b, h, kb, 0

    # one head a grid row, its block's two leading dimensions squeezed:
    # the kernel sees (bq, D), (bk, D), v's (bk, Dv), o's (bq, Dv) and the
    # (1, bq) row of lse
    def rows_spec(width):
        return pl.BlockSpec((None, None, bq, width),
                            lambda b, h, *at: (b, h, tile(*at)[0], 0))

    q_spec = rows_spec(D)
    kv_spec = pl.BlockSpec((None, None, bk, D), kv_map)
    v_spec = pl.BlockSpec((None, None, bk, Dv), kv_map)
    out_specs = [rows_spec(Dv)]
    out_shape = [jax.ShapeDtypeStruct((B, H, Sq_p, Dv), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec(
            (None, None, 1, bq), lambda b, h, *at: (b, h, 0, tile(*at)[0])))
        out_shape.append(jax.ShapeDtypeStruct((B, H, 1, Sq_p), jnp.float32))
    grid = (B, H, visits[0].size) if visits else (B, H, Sq_p // bq, nk)
    in_specs = [q_spec, kv_spec, v_spec]
    out_specs = out_specs if with_lse else out_specs[0]
    scratch = [
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, Dv), jnp.float32),
    ]
    if visits:
        how = {"grid_spec": pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(visits), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch)}
    else:
        how = {"grid": grid, "in_specs": in_specs, "out_specs": out_specs,
               "scratch_shapes": scratch}
    out = pl.pallas_call(
        kern,
        out_shape=out_shape if with_lse else out_shape[0],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            ("parallel",) * (len(grid) - 1) + ("arbitrary",))),
        interpret=interpret,
        name="flash_fwd",  # the HLO instruction's name on a device trace
        **how,
    )(*visits, _pad_rows(q, pq), _pad_rows(k, pk), _pad_rows(v, pk))
    if with_lse:
        return out[0][:, :, :S_q], out[1]
    return out[:, :, :S_q]


# ---------------------------------------------------------------------------
# backward

def _fa_bwd_kernel(*refs, bq, bk, nq, sm_scale, causal, s_k_real,
                   causal_off, mask=None, strips=(), nseg=1):
    """Grid (B, H, nk, nq), i innermost: one transposed (bk, bq) tile per
    step. dk_s/dv_s accumulate one k tile's gradients over the q sweep;
    dq_ref is the (rows, D) float32 block of ``nq`` q tiles, resident
    until the head changes, and takes each tile's rows as they come: the
    first live k tile of a q tile assigns (kb == 0 under ``causal``, the
    table's FIRST under a ``mask`` spec, whose tables lead the refs and
    whose ``strips`` are those of each pattern number its tiles bear).
    A head too long for its dq to be one block has ``nseg`` > 1 such
    *segments*: the grid is (B, H, nseg, k tiles, nq) and dk and dv leave
    once per segment. Under a mask spec the grid is (B, H, visits) for
    any ``nseg``: the prefetched arrays of ``_visits`` lead the refs, a
    visit's q tile, k tile, kind and place in the sweep of its segment's
    q tiles past the k tile (and the slot of that sweep's dk and dv,
    which only the index maps read)."""
    if mask is not None:
        qt_ref, kt_ref, kind_ref, edge_ref, _, *refs = refs
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref,
     dv_ref, dk_s, dv_s) = refs
    if mask is None:
        lead = 3 if nseg > 1 else 2     # grid dimensions before the k tile's
        kb = pl.program_id(lead)
        il = i = pl.program_id(lead + 1)    # in the segment, in the head
        if nseg > 1:
            i = pl.program_id(2) * nq + il
    else:
        at = pl.program_id(2)
        i, kb, kind, edge = qt_ref[at], kt_ref[at], kind_ref[at], edge_ref[at]
        il = lax.rem(i, nq)

    @pl.when(il == 0 if mask is None else (edge & OPENS) != 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    def _to_dq(rows, dq=None):
        """``dq`` (None: zeros) into the segment's dq at the tile's query
        ``rows``: assigned by a q tile's first live k tile, added by the
        rest."""
        n_rows = len(range(*rows.indices(bq)))
        at = pl.ds(pl.multiple_of(_from(il * bq, rows), n_rows), n_rows)

        @pl.when(kb == 0 if mask is None else (kind & FIRST) != 0)
        def _first():
            dq_ref[at, :] = jnp.zeros((n_rows, dq_ref.shape[1]),
                                      dq_ref.dtype) if dq is None else dq

        if dq is not None:
            @pl.when(kb > 0 if mask is None else (kind & FIRST) == 0)
            def _rest():
                dq_ref[at, :] += dq

    def _tile(masked, parts=_WHOLE_TILE, empty=()):
        """The five products of the tile: of its ``parts``, each a
        strip's query rows by the key columns of its hull, or the one
        part that is all of it; the strips with no live key are the
        ``empty`` rows. Every part's scores, then its p and ds, then its
        gradients, as in the forward."""
        loaded, scores = [], []
        for rows, cols in parts:
            q, k, v, do = q_ref[rows], k_ref[cols], v_ref[cols], do_ref[rows]
            sT = lax.dot_general(k, q, _NT,
                                 preferred_element_type=jnp.float32) * sm_scale
            if masked and mask is not None:
                sT = _spec_where(mask, sT, _from(i * bq, rows),
                                 _from(kb * bk, cols), 1)
            elif masked:
                sT = jnp.where(_tile_mask((bk, bq), i * bq + causal_off,
                                          kb * bk, 1, causal, s_k_real),
                               sT, _NEG)
            loaded.append((q, k, v, do))
            scores.append(sT)
        grads = []
        for (rows, _), (q, k, v, do), sT in zip(parts, loaded, scores):
            pT = jnp.exp(sT - lse_ref[:, rows])  # (1, bq) rows along sublanes
            dpT = lax.dot_general(v, do, _NT,
                                  preferred_element_type=jnp.float32)
            grads.append((pT, pT * (dpT - delta_ref[:, rows])))
        for (rows, cols), (q, k, v, do), (pT, dsT) in zip(parts, loaded,
                                                          grads):
            dv_s[cols] += jnp.dot(pT.astype(do.dtype), do,
                                  preferred_element_type=jnp.float32)
            dk_s[cols] += jnp.dot(dsT.astype(q.dtype), q,
                                  preferred_element_type=jnp.float32)
            _to_dq(rows, jnp.dot(dsT.T.astype(k.dtype), k,
                                 preferred_element_type=jnp.float32)
                   * sm_scale)
        for rows in empty:      # only a first tile's dq rows
            _to_dq(rows)

    if mask is not None:
        live_kind = kind & ~FIRST
        pl.when(live_kind == WHOLE)(functools.partial(_tile, False))

        @pl.when(live_kind > WHOLE)
        def _partly():
            for n, parts, empty in strips:
                pl.when(live_kind == PARTIAL + n * PATTERN)(
                    functools.partial(_tile, True, parts, empty))
    else:
        live, masked = _tile_kinds(i, kb, bq, bk, causal, s_k_real,
                                   causal_off)
        pl.when(live & masked)(functools.partial(_tile, True))
        pl.when(live & ~masked)(functools.partial(_tile, False))

    @pl.when(il == nq - 1 if mask is None else (edge & CLOSES) != 0)
    def _finalize():
        dk_ref[:] = (dk_s[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[:] = dv_s[:].astype(dv_ref.dtype)


def _sum_segments(a, group, first, bk, s_k):
    """dk or dv as the kernel left it, (B, H, segments, a band's rows, D)
    with segment s's band starting at k tile ``first[s]``, summed over
    the segments and the group into (B, H / group, s_k, D), in
    float32."""
    B, H, nseg, band, D = a.shape
    a = a.reshape(B, H // group, group, nseg, band, D)
    out = jnp.zeros((B, H // group, max(s_k, int(first.max()) * bk + band),
                     D), jnp.float32)
    for s, at in enumerate(first.tolist()):
        out = out.at[:, :, at * bk:at * bk + band].add(
            a[:, :, :, s].astype(jnp.float32).sum(2))
    return out[:, :, :s_k].astype(a.dtype)


def _pallas_backward(q, k, v, o, lse, do, sm_scale, causal, interpret,
                     bq=None, bk=None, mask=None, rows=None):
    """(dq, dk, dv) by the fused backward kernel at the chooser's tiles
    and segment (``bq``/``bk``/``rows`` override them for tests), from
    the forward's o and lse. The kernel writes dk, dv per query head and
    per segment; the group and the segments are summed here, in
    float32."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, S_q, D = q.shape
    S_k, Dv = k.shape[2], v.shape[3]
    group = H // k.shape[1]
    if bq is None or bk is None:
        bq, bk, chosen = choose_backward(S_q, S_k, max(D, Dv),
                                         q.dtype.itemsize)
        rows = rows or chosen
    pq = (-S_q) % bq
    pk = (-S_k) % bk
    Sq_p, Sk_p = S_q + pq, S_k + pk
    rows = rows or Sq_p
    if Sq_p % rows or rows % bq:
        raise ValueError(f"segments of {rows} rows, {Sq_p} query rows in "
                         f"tiles of {bq}")
    nseg = Sq_p // rows
    _count("flash_bwd_q_segments", nseg)
    nq = rows // bq                     # q tiles a segment
    nk = Sk_p // bk
    first, band = onp.zeros(nseg, onp.int32), nk
    visits = strips = ()
    if mask is not None:
        strips, (first, band), visits = _mask_tiles(mask, q, k, bq, bk, nseg)
    else:
        _count_steps(nseg * nq * nk, _causal_live(nseg * nq, nk, bq, bk,
                                                  causal, S_k - S_q))
    if lse.shape != (B, H, 1, Sq_p):
        raise ValueError(f"lse {lse.shape} is not the forward's for q "
                         f"{q.shape} padded to tiles of {bq}")
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.pad(delta, ((0, 0), (0, 0), (0, pq)))[:, :, None]
    off = S_k - S_q
    kern = functools.partial(_fa_bwd_kernel, bq=bq, bk=bk, nq=nq,
                             sm_scale=sm_scale, causal=causal,
                             s_k_real=S_k, causal_off=off, mask=mask,
                             strips=strips, nseg=nseg)

    def ids(args):
        """(b, h, segment, k tile, q tile of the head, dk and dv's block)
        of a grid step; the segment is the number 0 where there is one.
        Under a spec they are the visit's."""
        b, h, *rest = args
        if mask is not None:
            v, qt, kt, _, _, slot = rest
            return b, h, qt[v] // nq, kt[v], qt[v], (slot[v],)
        if nseg == 1:
            kb, i = rest
            return b, h, 0, kb, i, (kb,)
        seg, kb, i = rest
        return b, h, seg, kb, seg * nq + i, (seg, kb)

    def _live_i(args):
        b, h, seg, kb, i, _ = ids(args)
        if causal:
            # q tiles above the diagonal of k tile kb are skipped: name a
            # live one of the segment and the pipeline copies nothing
            i = jnp.maximum(i, jnp.maximum(kb * bk - off, 0) // bq)
            if nseg > 1:
                i = jnp.minimum(i, (seg + 1) * nq - 1)
        return b, h, i

    def kv_map(*args):
        b, h, _, kb, _, _ = ids(args)
        return b, (h // group if group > 1 else h), kb, 0

    def row_map(*args):
        b, h, i = _live_i(args)
        return b, h, 0, i

    def dkv_map(*args):
        b, h, *_, block = ids(args)
        return (b, h, *block, 0)

    def q_spec(width):
        return pl.BlockSpec((None, None, bq, width),
                            lambda *args: (*_live_i(args), 0))

    row_spec = pl.BlockSpec((None, None, 1, bq), row_map)
    kv_spec = pl.BlockSpec((None, None, bk, D), kv_map)
    v_spec = pl.BlockSpec((None, None, bk, Dv), kv_map)
    # dk, dv: a block for every sweep of q tiles. Without a spec a grid
    # step's indices but the sweep's say which; a spec's visits give the
    # blocks of all segments' bands one after another
    if mask is not None:
        grid = (B, H, visits[0].size)
        dkv_shape = (B, H, nseg * band * bk, D)
    else:
        grid = (B, H) + ((nseg,) if nseg > 1 else ()) + (nk, nq)
        dkv_shape = (B, H) + ((nseg,) if nseg > 1 else ()) + (nk * bk, D)
    dv_shape = dkv_shape[:-1] + (Dv,)
    dkv_spec = pl.BlockSpec((None,) * (len(dkv_shape) - 2) + (bk, D), dkv_map)
    dv_spec = pl.BlockSpec((None,) * (len(dv_shape) - 2) + (bk, Dv),
                           dkv_map)
    in_specs = [q_spec(D), kv_spec, v_spec, q_spec(Dv), row_spec, row_spec]
    out_specs = [
        pl.BlockSpec((None, None, rows, D),
                     lambda *args: (args[0], args[1], ids(args)[2], 0)),
        dkv_spec, dv_spec,
    ]
    scratch = [
        pltpu.VMEM((bk, D), jnp.float32),
        pltpu.VMEM((bk, Dv), jnp.float32),
    ]
    if visits:
        how = {"grid_spec": pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(visits), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch)}
    else:
        how = {"grid": grid, "in_specs": in_specs, "out_specs": out_specs,
               "scratch_shapes": scratch}
    dq, dk, dv = pl.pallas_call(
        kern,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq_p, D), jnp.float32),
            jax.ShapeDtypeStruct(dkv_shape, k.dtype),
            jax.ShapeDtypeStruct(dv_shape, v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            ("parallel", "parallel", "arbitrary") if visits else
            ("parallel",) * (len(grid) - 2) + ("arbitrary", "arbitrary"))),
        interpret=interpret,
        # attn_bwd_ms.tokens finds the backward by this name alone
        name="flash_bwd",
        **how,
    )(*visits, _pad_rows(q, pq), _pad_rows(k, pk), _pad_rows(v, pk),
      _pad_rows(do, pq), lse, delta)  # zero do: padded rows add nothing
    if nseg > 1:
        dk, dv = (_sum_segments(a.reshape(B, H, nseg, band * bk,
                                          a.shape[-1]), group, first, bk,
                                S_k) for a in (dk, dv))
    else:
        dk, dv = dk[:, :, :S_k], dv[:, :, :S_k]
        if group > 1:
            dk, dv = (a.reshape(B, H // group, group, S_k, a.shape[-1])
                      .astype(jnp.float32).sum(2).astype(a.dtype)
                      for a in (dk, dv))
    return dq[:, :, :S_q].astype(q.dtype), dk, dv


# ---------------------------------------------------------------------------
# decode

def _dec_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *, sm_scale):
    """Decode-mode kernel, grid (B*H,): one query row against its whole
    KV cache row in VMEM. Decode is a GEMV — the S² tiling of the
    training kernel buys nothing at S_q=1, so the cache row (S, D)
    streams in as one block (VMEM-bound: fine for serving prefix
    lengths; S·D·4 bytes must fit VMEM) and the masked softmax runs
    fused in fp32. Per-session visible lengths arrive as a prefetched
    scalar vector — one compiled kernel serves every mixed-length
    batch."""
    b = pl.program_id(0)
    n = len_ref[b]
    q = q_ref[0].astype(jnp.float32)  # (1, D)
    k = k_ref[0].astype(jnp.float32)  # (S, D)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
    kid = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kid < n, s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)  # masked scores underflow to exact +0.0
    o_ref[0] = (jnp.dot(p, v, preferred_element_type=jnp.float32)
                / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True),
                              1e-30)).astype(o_ref.dtype)


def _decode_flash(q, k, v, lengths, sm_scale, interpret):
    """One incremental decode step: q (B, H, D) attends against the
    cache k/v (B, H, S, D) masked to per-row prefix ``lengths`` (B,)
    int32. Returns (B, H, D). The Pallas path of the registered
    ``_attention_decode`` op (documented-ulp vs the lax path: fused
    fp32 softmax; the lax path is the bitwise oracle)."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = k.shape
    qr = q.reshape(B * H, 1, D)
    kr = k.reshape(B * H, S, D)
    vr = v.reshape(B * H, S, D)
    lens = jnp.repeat(lengths.astype(jnp.int32), H)  # (B*H,)
    kern = functools.partial(_dec_kernel, sm_scale=sm_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * H,),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda b, lens: (b, 0, 0)),
            pl.BlockSpec((1, S, D), lambda b, lens: (b, 0, 0)),
            pl.BlockSpec((1, S, D), lambda b, lens: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda b, lens: (b, 0, 0)),
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, 1, D), q.dtype),
        interpret=interpret,
        name="flash_decode",
    )(lens, qr, kr, vr)
    return out.reshape(B, H, D)


# ---------------------------------------------------------------------------
# the differentiable entry

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, sm_scale, causal, impl, mask=None):
    if impl == "xla":
        return _ref_attention(q, k, v, sm_scale, causal, k.shape[2], mask)
    return _pallas_forward(q, k, v, sm_scale, causal,
                           impl == "interpret", mask=mask)


def _flash_fwd(q, k, v, sm_scale, causal, impl, mask=None):
    fits = impl != "xla" and choose_tiles(
        q.shape[2], k.shape[2], max(q.shape[3], v.shape[3]),
        q.dtype.itemsize, backward=True)
    if not fits:  # the scan backward recomputes from q, k, v alone
        return (_flash(q, k, v, sm_scale, causal, impl, mask),
                (q, k, v, None, None))
    o, lse = _pallas_forward(q, k, v, sm_scale, causal,
                             impl == "interpret", with_lse=True, mask=mask)
    return o, (q, k, v, o, lse)


def _scan_backward(q, k, v, do, sm_scale, causal):
    """Backward by q-chunk recompute (lax.scan): peak extra memory is
    O(chunk·S_k) instead of materializing the full S_q×S_k attention
    matrix. The ``xla`` path's backward, the oracle of the kernel's, and
    where a shape the kernel cannot hold ends up."""
    S_q, S_k = q.shape[2], k.shape[2]
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    chunk = min(512, S_q)
    pad = (-S_q) % chunk
    qp = _pad_rows(q, pad).astype(jnp.float32)
    # zero do on padding → padded rows contribute nothing
    dop = _pad_rows(do, pad).astype(jnp.float32)
    nchunk = (S_q + pad) // chunk
    B, H, _, D = q.shape
    qc = qp.reshape(B, H, nchunk, chunk, D).transpose(2, 0, 1, 3, 4)
    doc = dop.reshape(B, H, nchunk, chunk, v.shape[3]).transpose(
        2, 0, 1, 3, 4)
    kid = jnp.arange(S_k)[None, :]
    off = S_k - S_q  # bottom-right causal alignment

    def step(carry, xs):
        dk_acc, dv_acc, ci = carry
        qb, dob = xs  # (B, H, chunk, D)
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, kf) * sm_scale
        if causal:
            qid = ci * chunk + jnp.arange(chunk)[:, None] + off
            s = jnp.where((kid <= qid)[None, None], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        dv_acc += jnp.einsum("bhqk,bhqd->bhkd", p, dob)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dob, vf)
        ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
        dqb = jnp.einsum("bhqk,bhkd->bhqd", ds, kf) * sm_scale
        dk_acc += jnp.einsum("bhqk,bhqd->bhkd", ds, qb) * sm_scale
        return (dk_acc, dv_acc, ci + 1), dqb

    (dk, dv, _), dqs = lax.scan(
        step, (jnp.zeros_like(kf), jnp.zeros_like(vf), 0), (qc, doc))
    dq = dqs.transpose(1, 2, 0, 3, 4).reshape(B, H, S_q + pad, D)[
        :, :, :S_q]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_bwd(sm_scale, causal, impl, mask, res, do):
    """Counts, at trace time, which backward it lowered: the kernel, or
    the scan (the ``xla`` path, and any shape whose dq the kernel cannot
    keep in VMEM — loudly, in ``kernels.counters()``)."""
    q, k, v, o, lse = res
    with jax.named_scope("flash_bwd"):
        if lse is None:
            _count("flash_bwd_scan")
            if mask is None and q.shape[1] == k.shape[1]:
                return _scan_backward(q, k, v, do, sm_scale, causal)
            # a mask spec or grouped heads: the oracle's own derivative
            return jax.vjp(lambda q, k, v: _ref_attention(
                q, k, v, sm_scale, causal, k.shape[2], mask), q, k, v)[1](do)
        _count("flash_bwd_pallas")
        return _pallas_backward(q, k, v, o, lse, do, sm_scale, causal,
                                impl == "interpret", mask=mask)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, sm_scale=None, causal=False, use_pallas=None,
                    mask=None):
    """Scaled dot-product attention over (B, H, S, D) tensors; k and v
    may hold H / group heads, query head h then reads head h // group,
    and v's heads may be wider than q's and k's (the result is v's
    width).

    use_pallas: None = pallas on TPU / XLA elsewhere; True forces the
    kernel (interpreted off-TPU — slow, for testing); False forces XLA.
    mask: a static spec of which scores live (``BlockDiffusionMask``),
    in place of ``causal``.
    """
    if q.shape[1] % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(f"{q.shape[1]} query heads over {k.shape[1]} key "
                         f"and {v.shape[1]} value heads")
    if mask is not None and causal:
        raise ValueError("give either causal=True or a mask spec")
    if causal and q.shape[-2] > k.shape[-2]:
        # bottom-right-aligned causal with S_q > S_k gives query rows a
        # negative offset — rows with zero visible keys would come out of
        # the all-masked online-softmax as an unnormalized average of V
        raise ValueError(
            "flash_attention(causal=True) requires S_q <= S_k, got "
            f"S_q={q.shape[-2]} S_k={k.shape[-2]}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if use_pallas is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    elif use_pallas:
        impl = "pallas" if jax.default_backend() == "tpu" else "interpret"
    else:
        impl = "xla"
    with jax.named_scope("attn"):
        if mask is None:    # the call as it always was
            return _flash(q, k, v, float(sm_scale), bool(causal), impl)
        return _flash(q, k, v, float(sm_scale), False, impl, mask)
