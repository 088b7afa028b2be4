"""The flash kernels under ``SlidingWindowMask`` and past a whole head's
dq: the spec's element rule against its row intervals against a dense
mask, its tile table (dead tiles on both sides of a query tile's band,
two partly masked patterns at square tiles), both kernels interpreted on
the CPU against ``_ref_attention`` under the window spec and under
``causal`` with 7 query heads a key/value head at D=128, the backward in
segments of a head (forced at a small size through the chooser's
``budget``) against the oracle's gradients, and the chooser's pins.
(``tests/test_chip_compile.py`` compiles the same kernels for the chip
at the cell's shape.)
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from _flash_visits import (
    check_backward_visits, check_forward_visits, check_visits)
from mxnet_tpu import kernels
from mxnet_tpu.kernels import flash_attention as fa
from mxnet_tpu.kernels.flash_attention import (
    DEAD, FIRST, PARTIAL, PATTERN, WHOLE, SlidingWindowMask,
    choose_backward, choose_tiles, flash_attention, mask_tile_table)


def dense_window(S, w):
    i = onp.arange(S)
    return (i[None] <= i[:, None]) & (i[:, None] - i[None] < w)


@pytest.mark.parametrize("S,w", [(256, 64), (384, 128), (256, 1), (128, 128),
                                 (256, 1000), (512, 200)])
def test_element_rule_row_intervals_and_dense_mask_agree(S, w):
    spec = SlidingWindowMask(S, w)
    assert spec.size == S and hash(spec) == hash(SlidingWindowMask(S, w))
    ids = jnp.arange(S, dtype=jnp.int32)
    want = dense_window(S, w)
    assert (onp.asarray(spec.element(ids[:, None], ids[None])) == want).all()
    iv = spec.row_intervals()
    assert iv.shape == (S, 1, 2)
    rows = onp.zeros_like(want)
    for r in range(S):
        rows[r, iv[r, 0, 0]:iv[r, 0, 1]] = True
    assert (rows == want).all()
    # the live pairs the benchmark's flops file counts
    m = min(w, S)
    assert want.sum() == m * (m + 1) // 2 + (S - m) * m
    with pytest.raises(ValueError):
        SlidingWindowMask(S, 0)


@pytest.mark.parametrize("S,w,bq,bk", [
    (1024, 256, 128, 128), (1024, 256, 256, 256), (2048, 512, 256, 512),
    (1024, 384, 128, 256), (16384, 4096, 1024, 1024),
    (16384, 4096, 512, 512), (16384, 4096, 256, 512)])
def test_tile_table_has_dead_tiles_on_both_sides(S, w, bq, bk):
    sub = fa._strip_size(bq, bk)
    table, patterns = mask_tile_table(SlidingWindowMask(S, w), bq, bk, sub)
    nq, nk = S // bq, S // bk
    dense = dense_window(S, w) if S <= 2048 else None
    kinds = table & (WHOLE | PARTIAL)
    if dense is not None:
        count = dense.reshape(nq, bq, nk, bk).sum((1, 3))
        want = onp.where(count == 0, DEAD,
                         onp.where(count == bq * bk, WHOLE, PARTIAL))
        assert (kinds == want).all()
    live = kinds != DEAD
    # the last q tile: dead k tiles before its band, none after; q tiles
    # in between: dead tiles on both sides
    assert not live[-1, 0] and live[-1, -1]
    assert (~live[:, 0] & ~live[:, -1]).any() and live.any(1).all()
    for row in live:            # a band: one run of live tiles
        at = onp.nonzero(row)[0]
        assert (onp.diff(at) == 1).all()
    assert (((table & FIRST) != 0).sum(1) == 1).all()
    assert ((table & FIRST) != 0)[onp.arange(nq), live.argmax(1)].all()
    # both kernels visit exactly the live tiles, in the order the
    # rectangle swept them, and write every block of their results
    check_visits(table)
    number = table // PATTERN
    if bq == bk and w % bk == 0 and bq > sub:
        # the diagonal's triangle and the band's trailing edge
        assert len(patterns) == 2 and set(onp.unique(number)) == {0, 1, 2}
        r = bq // sub
        assert set(patterns) == {
            tuple((0, i + 1) for i in range(r)),
            tuple((i, r) for i in range(r))}
    assert len(patterns) <= fa._MAX_PATTERNS


def test_segment_bands_of_the_cells_window():
    """At the cell's backward tiles a segment of 4,096 query rows walks
    the 16 k tiles of its band, not all 32; the first segment's band is
    pulled back to start at tile 0."""
    table, _ = mask_tile_table(SlidingWindowMask(16384, 4096), 512, 512, 256)
    first, width = fa._segment_bands(table, 4)
    assert width == 16 and first.tolist() == [0, 0, 8, 16]
    first, width = fa._segment_bands(table, 1)
    assert width == 32 and first.tolist() == [0]
    first, width = fa._segment_bands(table, 8)
    assert width == 12 and first.tolist() == [0, 0, 0, 4, 8, 12, 16, 20]


def test_the_cells_walks_count_what_the_issue_states():
    """A head of a window layer of the cell smallthinker21b-train-s16384:
    70 visits forward for the rectangle's 256 steps; backward 36, 72, 72
    and 72 live tiles in the four segments for 4 x 128 steps, and 8
    visits that only write zeros: the k tiles 8 to 15 of the first
    segment's band, which no query of its 4,096 sees and
    ``_sum_segments`` adds all the same. 330 a layer."""
    spec = SlidingWindowMask(16384, 4096)
    fwd, _ = mask_tile_table(spec, 1024, 1024, 512)
    bwd, _ = mask_tile_table(spec, 512, 512, 256)
    assert fwd.size == 256 and check_forward_visits(fwd) == 70
    assert check_backward_visits(bwd, 4) == ([36, 72, 72, 72], [8, 0, 0, 0])
    qt, kt, kind, edge, slot = fa._visits(bwd, fa._segment_bands(bwd, 4))
    assert fa._visits(fwd)[0].size + qt.size == 330
    zero = kind == DEAD
    assert kt[zero].tolist() == list(range(8, 16)) == slot[zero].tolist()
    # they copy no q tile: each names the one of the visit before it
    assert (qt[zero] == qt[onp.nonzero(zero)[0][0] - 1]).all()
    # one segment (a whole head's dq in one block): every k tile is live
    assert check_backward_visits(bwd, 1) == ([252], [0])


def _qkv(S, H, HKV, D, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (1, H, S, D), dtype),
            jax.random.normal(ks[1], (1, HKV, S, D), dtype),
            jax.random.normal(ks[2], (1, HKV, S, D), dtype),
            jax.random.normal(ks[3], (1, H, S, D), dtype))


def _rel(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.fixture
def cap_tiles(monkeypatch):
    def cap(n):
        monkeypatch.setattr(fa, "_FWD_CAPS", (n, n))
        monkeypatch.setattr(fa, "_BWD_CAPS", (n, n))
    return cap


@pytest.mark.parametrize("window", [128, 0], ids=["window_128", "causal"])
def test_kernels_with_seven_query_heads_a_kv_head_at_d128(cap_tiles, window):
    """Forward and backward through ``flash_attention`` (interpreted) at
    tiles of 128 over 384 positions: dead, whole and partly masked tiles
    all occur, 7 query heads read each of 2 key/value heads."""
    cap_tiles(128)
    S, H, HKV, D = 384, 14, 2, 128
    q, k, v, do = _qkv(S, H, HKV, D)
    mask = SlidingWindowMask(S, window) if window else None
    before = kernels.counters()

    def attend(pallas):
        return jax.vjp(lambda *a: flash_attention(
            *a, mask=mask, causal=mask is None, use_pallas=pallas), q, k, v)

    (got, got_vjp), (want, want_vjp) = attend(True), attend(False)
    assert _rel(got, want) < 2e-5
    for g, w in zip(got_vjp(do), want_vjp(do)):
        assert g.shape == w.shape and _rel(g, w) < 2e-5
    after = kernels.counters()
    assert after["flash_bwd_pallas"] == before.get("flash_bwd_pallas", 0) + 1
    if window:      # counted as the block-diffusion spec is
        assert after["flash_mask_pallas"] > before.get("flash_mask_pallas", 0)
        assert after["flash_mask_subtiles_tile"] > before.get(
            "flash_mask_subtiles_tile", 0)
    # a head's grid steps, both passes: the window's 5 live tiles of 9
    # and no other; the causal rectangle's 9, of which 6 are live
    steps, live = (after[name] - before.get(name, 0) for name in
                   ("flash_grid_steps", "flash_grid_steps_live"))
    assert (steps, live) == ((10, 10) if window else (18, 12))


@pytest.mark.parametrize("S,window,heads,zeros", [
    (1024, 256, (2, 1), 2), (1024, 0, (2, 1), 0), (768, 384, (6, 2), 4),
    (1024, 0, (4, 2), 0), (1024, -4, (2, 1), 8)],
    ids=["window", "causal", "window_grouped", "causal_grouped",
         "block_diffusion"])
def test_backward_in_segments_equals_the_oracles_gradients(S, window, heads,
                                                           zeros):
    """A budget under which the whole head's dq fits beside no tile: the
    chooser cuts the head into segments, the kernel walks each (under
    a spec only the live tiles of its band of k tiles) and the sums over
    segments and group are the oracle's gradients. ``zeros`` k tiles of
    a segment's band have no live q tile in it and are written all the
    same: under the window the last ones of the first segments' bands,
    under block diffusion (``window`` its block length, negated) in four
    segments also the noised keys' tiles between a noised segment's own
    and the clean ones, and ahead of the clean queries' segments."""
    H, HKV = heads
    D = 64
    budget = fa.tile_vmem_bytes(128, 128, S // 2, D, 4, True) - 1
    assert fa._tiles_within(S, S, D, 4, True, budget) is None
    bq, bk, rows = choose_backward(S, S, D, 4, budget)
    assert rows < S and S % rows == 0 and rows % bq == 0
    assert choose_tiles(S, S, D, 4, True, budget) == (bq, bk)
    q, k, v, do = _qkv(S, H, HKV, D, seed=3)
    mask = SlidingWindowMask(S, window) if window > 0 else \
        fa.BlockDiffusionMask(S // 2, -window) if window else None
    sm = D ** -0.5
    o, lse = fa._pallas_forward(q, k, v, sm, mask is None, True,
                                with_lse=True, bq=bq, bk=bk, mask=mask)
    before = kernels.counters().get("flash_bwd_q_segments", 0)
    grid = {name: kernels.counters().get(name, 0)
            for name in ("flash_grid_steps", "flash_grid_steps_live")}
    got = fa._pallas_backward(q, k, v, o, lse, do, sm, mask is None, True,
                              bq=bq, bk=bk, mask=mask, rows=rows)
    assert kernels.counters()["flash_bwd_q_segments"] == before + S // rows
    if mask is not None:    # the visits that only write zeros are steps
        steps, live = (kernels.counters()[name] - grid[name] for name in grid)
        assert steps - live == sum(check_backward_visits(mask_tile_table(
            mask, bq, bk, fa._strip_size(bq, bk))[0], S // rows)[1]) == zeros
    want = jax.vjp(lambda *a: fa._ref_attention(
        *a, sm, mask is None, S, mask), q, k, v)[1](do)
    for g, w in zip(got, want):
        assert g.shape == w.shape and _rel(g, w) < 2e-5
    # one segment is the kernel as it was
    whole = fa._pallas_backward(q, k, v, o, lse, do, sm, mask is None, True,
                                bq=bq, bk=bk, mask=mask)
    for g, w in zip(got, whole):
        assert _rel(g, w) < 2e-6


def test_chooser_pins():
    """Shapes whose dq fits keep their tiles and are one segment; the new
    cell's head lowers to the kernel in four."""
    assert choose_tiles(2048, 2048, 64, 2) == (1024, 1024)
    assert choose_backward(2048, 2048, 64, 2) == (512, 512, 2048)
    assert choose_tiles(8192, 8192, 128, 2) == (1024, 1024)
    assert choose_backward(8192, 8192, 128, 2) == (256, 512, 8192)
    assert choose_tiles(16384, 16384, 128, 2) == (1024, 1024)
    assert choose_tiles(16384, 16384, 128, 2, backward=True) == (512, 512)
    assert choose_backward(16384, 16384, 128, 2) == (512, 512, 4096)
    bq, bk, rows = choose_backward(16384, 16384, 128, 2)
    assert fa.tile_vmem_bytes(bq, bk, rows, 128, 2, True) \
        <= fa._VMEM_BUDGET_BYTES
    assert fa.vmem_bytes(16384, 16384, 128, 2) <= fa._VMEM_BUDGET_BYTES
    # where nothing reaches the caps, the fewest segments beside which
    # anything fits
    small = fa.tile_vmem_bytes(128, 128, 256, 128, 4, True)
    assert choose_backward(1024, 1024, 128, 4, small) == (128, 128, 256)
    assert choose_backward(1024, 1024, 128, 4, 1 << 16) is None
