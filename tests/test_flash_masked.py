"""The flash kernels under a static mask spec and with grouped heads:
``BlockDiffusionMask`` over ``[xt ; x0]`` against a dense mask built from
the three rules, forward and backward in interpret mode on the CPU, at
tiles that make dead, whole and partly masked tiles all occur, and at
tiles that hold several strips; the table of tile kinds and the patterns
of strips against brute force over the element rule; and ``causal=True``
left as it was. (``tests/test_chip_compile.py`` compiles
the same kernels for the chip at the cell's shape.)
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from _flash_visits import (
    check_backward_visits, check_forward_visits, check_visits)
from mxnet_tpu import kernels
from mxnet_tpu.kernels import flash_attention as fa
from mxnet_tpu.kernels.flash_attention import (
    DEAD, FIRST, PARTIAL, PATTERN, WHOLE, BlockDiffusionMask,
    flash_attention, mask_tile_table)

TOL = {"float32": 2e-5, "bfloat16": 8e-2}


def dense_mask(L, b):
    """(2L, 2L) bool from the three rules, in plain numpy."""
    i = onp.arange(2 * L)
    noisy, blk = i < L, (i % L) // b
    qn, kn, qb, kb = noisy[:, None], noisy[None], blk[:, None], blk[None]
    return (qn & kn & (qb == kb)) | (qn & ~kn & (kb < qb)) \
        | (~qn & ~kn & (kb <= qb))


def dense_attention(q, k, v, mask):
    """softmax(q k^T / sqrt(d) + mask) v in float32, query head h reading
    key/value head h // group."""
    f32 = jnp.float32
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a.astype(f32), group, axis=1) for a in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(f32), k) / q.shape[-1] ** 0.5
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.fixture
def cap_tiles(monkeypatch):
    def cap(nq, nk=None):
        monkeypatch.setattr(fa, "_FWD_CAPS", (nq, nk or nq))
        monkeypatch.setattr(fa, "_BWD_CAPS", (nq, nk or nq))
    return cap


@pytest.fixture
def strips_of(monkeypatch):
    """Strips of the given size for the test."""
    return lambda sub: monkeypatch.setattr(fa, "_STRIP", sub)


@pytest.mark.parametrize("L,b", [(256, 4), (128, 1), (384, 32), (96, 96)])
def test_element_rule_is_the_three_rules(L, b):
    spec = BlockDiffusionMask(L, b)
    ids = jnp.arange(2 * L, dtype=jnp.int32)
    got = onp.asarray(spec.element(ids[:, None], ids[None]))
    want = dense_mask(L, b)
    assert (got == want).all()
    assert want.sum() == L * L + L * b       # the live pairs of 4 L^2
    iv = spec.row_intervals()
    rows = onp.zeros_like(want)
    for r in range(2 * L):
        for lo, hi in iv[r]:
            rows[r, lo:hi] = True
    assert (rows == want).all()


TILES = [(256, 4, 128, 128), (256, 4, 256, 128), (256, 4, 128, 256),
         (512, 4, 512, 512), (384, 32, 128, 128), (128, 1, 128, 128),
         (512, 8, 256, 512), (4096, 4, 1024, 1024), (4096, 4, 512, 512),
         (4096, 4, 256, 512), (512, 4, 256, 256), (512, 128, 512, 256),
         (384, 32, 384, 256)]


@pytest.mark.parametrize("L,b,bq,bk", TILES)
def test_tile_table_against_brute_force(L, b, bq, bk):
    table, _ = mask_tile_table(BlockDiffusionMask(L, b), bq, bk, 128)
    S = 2 * L
    count = dense_mask(L, b).reshape(S // bq, bq, S // bk, bk).sum((1, 3))
    want = onp.where(count == 0, DEAD,
                     onp.where(count == bq * bk, WHOLE, PARTIAL))
    assert ((table & (WHOLE | PARTIAL)) == want).all()
    assert table.max() < (fa._MAX_PATTERNS + 1) * PATTERN
    live = want != DEAD
    # FIRST sits on each q tile's first live k tile and nowhere else
    assert (((table & FIRST) != 0).sum(1) == 1).all()
    assert ((table & FIRST) != 0)[onp.arange(S // bq), live.argmax(1)].all()
    # both kernels visit exactly the live tiles, in the order the
    # rectangle swept them, and write every block of their results
    check_visits(table)


def brute_patterns(L, b, bq, bk, sub=128):
    """Per PARTIAL tile (i, j), from the dense mask: which of its
    sub x sub sub-tiles hold a live score, (bq / sub, bk / sub) bool."""
    S = 2 * L
    live = dense_mask(L, b).reshape(S // bq, bq // sub, sub,
                                    S // bk, bk // sub, sub).any((2, 5))
    count = dense_mask(L, b).reshape(S // bq, bq, S // bk, bk).sum((1, 3))
    return {(i, j): live[i, :, j]
            for i, j in zip(*onp.nonzero(count % (bq * bk)))}


#: distinct patterns a kernel gets a branch for under blocks of 4, by
#: (bq, bk, sub): the band and the block-causal diagonal at square tiles,
#: each at two offsets where a k tile holds two q tiles (at one strip a
#: tile the diagonal's lower one is the band's, and its upper one is the
#: whole tile), the band's two halves where a q tile holds two k tiles
#: (the diagonal's hulls are all of it), none where a tile is one sub-tile
N_PATTERNS = {(128, 128, 128): 0, (256, 128, 128): 2, (128, 256, 128): 2,
              (512, 512, 128): 2, (256, 512, 128): 4, (1024, 1024, 128): 2,
              (256, 256, 128): 2, (256, 256, 256): 0, (512, 512, 256): 2,
              (256, 512, 256): 2, (1024, 1024, 256): 2}


@pytest.mark.parametrize("sub", [128, 256])
@pytest.mark.parametrize("L,b,bq,bk", TILES)
def test_patterns_against_brute_force(strips_of, L, b, bq, bk, sub):
    """Every live score lies inside its strip's hull, every sub-tile
    outside a hull is dead, a hull is tight, and the numbered patterns are
    the few that spare work."""
    strips_of(sub)
    if bq % sub or bk % sub:
        assert fa._strip_size(bq, bk) == 128
        return
    assert fa._strip_size(bq, bk) == sub
    table, patterns = mask_tile_table(BlockDiffusionMask(L, b), bq, bk, sub)
    want = brute_patterns(L, b, bq, bk, sub)
    number = table // PATTERN
    assert ((number > 0) <= ((table & PARTIAL) != 0)).all()
    assert number.max() == len(patterns) <= fa._MAX_PATTERNS
    assert len(set(patterns)) == len(patterns)
    if b == 4:
        assert len(patterns) == N_PATTERNS[bq, bk, sub]
    at = onp.arange(bk // sub)
    for (i, j), live in want.items():
        n = number[i, j]
        if n == 0:      # computed whole
            if len(patterns) < fa._MAX_PATTERNS:    # for nothing is spared
                assert live[:, [0, -1]].all()
            continue
        hulls = patterns[n - 1]
        assert len(hulls) == bq // sub
        for r, (lo, hi) in enumerate(hulls):
            assert not (live[r] & ~((at >= lo) & (at < hi))).any()
            assert (lo, hi) == (0, 0) if not live[r].any() \
                else live[r, lo] and live[r, hi - 1]


def test_a_spec_with_many_patterns_keeps_the_whole_tile_for_the_rest(
        monkeypatch):
    """At (256, 512) in strips of 128 the spec has four patterns; with
    room for two the two that spare the most keep their branches and the
    other tiles are computed whole."""
    spec = BlockDiffusionMask(4096, 4)
    table4, four = mask_tile_table(spec, 256, 512, 128)
    monkeypatch.setattr(fa, "_MAX_PATTERNS", 2)
    mask_tile_table.cache_clear()
    try:
        table2, two = mask_tile_table(spec, 256, 512, 128)
    finally:
        mask_tile_table.cache_clear()
    assert len(four) == 4 and two == four[:2]
    assert (table2 % PATTERN == table4 % PATTERN).all()
    n4, n2 = table4 // PATTERN, table2 // PATTERN
    assert (n2 == onp.where(n4 <= 2, n4, 0)).all() and (n4 > 2).any()


def subtile_counts(L, b, bq, bk, sub):
    """(computed, held) sub-tiles of ``sub`` in the PARTIAL tiles of one
    pass, from brute force: a strip computes its hull."""
    want = brute_patterns(L, b, bq, bk, sub)
    held = len(want) * (bq // sub) * (bk // sub)
    hull = lambda row: (row.size - row[::-1].argmax() - row.argmax()
                        if row.any() else 0)
    return sum(hull(row) for live in want.values() for row in live), held


def test_the_cell_shape_counts_what_the_issue_states():
    assert subtile_counts(4096, 4, 1024, 1024, 128) == (32 + 288, 768)
    assert subtile_counts(4096, 4, 256, 512, 128) == (32 + 48 + 112, 384)
    assert subtile_counts(4096, 4, 1024, 1024, 256) == (16 + 80, 192)
    assert subtile_counts(4096, 4, 256, 512, 256) == (16 + 16 + 32, 96)


def test_the_cells_walks_count_what_the_issue_states():
    """A head of the cell sdar30b-train-bd-s4096: 24 visits forward for
    the rectangle's 64 steps, 160 backward for 512, every k tile with a
    live q tile (no visit that only writes zeros): 184 a layer."""
    spec = BlockDiffusionMask(4096, 4)
    fwd, _ = mask_tile_table(spec, 1024, 1024, 512)
    bwd, _ = mask_tile_table(spec, 256, 512, 256)
    assert fwd.size == 64 and check_forward_visits(fwd) == 24
    assert bwd.size == 512
    assert check_backward_visits(bwd, 1) == ([160], [0])
    assert fa._visits(fwd)[0].size + fa._visits(
        bwd, fa._segment_bands(bwd, 1))[0].size == 184
    # two segments of 4,096 rows (PERF.md section 7, row 13): the clean
    # queries' band holds the noised keys' tiles, which none of them sees
    assert check_backward_visits(
        mask_tile_table(spec, 512, 512, 512)[0], 2) == ([44, 36], [0, 8])


def test_all_three_kinds_occur_at_the_tested_tiles():
    kinds = mask_tile_table(BlockDiffusionMask(256, 4), 128, 128, 128)[0] \
        & (WHOLE | PARTIAL)
    assert {DEAD, WHOLE, PARTIAL} == set(kinds.reshape(-1).tolist())
    # the dead quadrant: no clean query sees a noised key
    assert (kinds[2:, :2] == DEAD).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("hq,hkv,d", [(8, 2, 128), (4, 4, 64)])
@pytest.mark.parametrize("L,caps,sub", [
    (256, (128, 128), 128),     # a tile is one sub-tile: no strips
    (512, (256, 256), 128),     # two strips a tile: the band, the diagonal
    (512, (512, 512), 128),     # four
    (512, (256, 128), 128),     # the band's strips with no live key, in
    (512, (512, 256), 256),     # the tile that assigns dq
    (512, (512, 512), 256),
    (512, (1024, 1024), 512),   # one tile, dead quadrant and all
])
def test_masked_flash_matches_the_dense_mask(cap_tiles, strips_of, L, caps,
                                             sub, hq, hkv, d, dtype):
    """8 query heads over 2 key/value heads of 128 (and no grouping at
    64), blocks of 4: L=256 at tiles of 128, 4 x 4 tiles of all three
    kinds, and L=512 at tiles that hold several strips of 128 to 512;
    forward and the fused backward, and the sub-tiles both counted."""
    strips_of(sub)
    b = 4
    cap_tiles(*caps)
    spec = BlockDiffusionMask(L, b)
    rs = onp.random.RandomState(0)
    mk = lambda h: jnp.asarray(rs.randn(1, h, 2 * L, d).astype("f"), dtype)
    q, k, v, do = mk(hq), mk(hkv), mk(hkv), mk(hq)
    f32 = jnp.float32

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(f32)
                                * do.astype(f32)).sum()

    before = kernels.counters()
    run = lambda q, k, v: flash_attention(q, k, v, mask=spec, use_pallas=True)
    out = run(q, k, v)
    got = jax.grad(loss(run), (0, 1, 2))(q, k, v)
    after = kernels.counters()
    assert after["flash_mask_pallas"] > before.get("flash_mask_pallas", 0)
    assert after["flash_bwd_pallas"] == before.get("flash_bwd_pallas", 0) + 1
    assert after.get("flash_bwd_scan", 0) == before.get("flash_bwd_scan", 0)
    # two forward traces and one backward, each at the tiles the chooser
    # gives under the caps (the budget may hold the backward's below them)
    S, size = 2 * L, q.dtype.itemsize
    fwd, bwd = (subtile_counts(L, b, *tiles, fa._strip_size(*tiles))
                for tiles in (fa.choose_tiles(S, S, d, size, backward=back)
                              for back in (False, True)))
    for at, name in enumerate(("live", "tile")):
        name = "flash_mask_subtiles_" + name
        assert after.get(name, 0) - before.get(name, 0) \
            == 2 * fwd[at] + bwd[at], name
    assert (fwd[0] < fwd[1]) == (caps != (128, 128))

    mask = jnp.asarray(dense_mask(L, b))
    ref = lambda q, k, v: dense_attention(q, k, v, mask)
    want_out = ref(q, k, v)
    want = jax.grad(loss(ref), (0, 1, 2))(
        q.astype(f32), k.astype(f32), v.astype(f32))
    assert out.shape == q.shape and out.dtype == q.dtype
    assert float(jnp.abs(out.astype(f32) - want_out).max()) < TOL[dtype]
    for name, g, w, like in zip("qkv", got, want, (q, k, v)):
        assert g.shape == like.shape and g.dtype == like.dtype, name
        err = float(jnp.abs(g.astype(f32) - w).max())
        # dk, dv sum a group's eight heads
        assert err < TOL[dtype] * (1 if name == "q" else hq // hkv), \
            (name, err)


def test_plain_path_takes_the_spec_and_the_groups_too():
    """``use_pallas=False``: the dense oracle and its own derivative."""
    L, b, d = 64, 4, 32
    spec = BlockDiffusionMask(L, b)
    rs = onp.random.RandomState(1)
    mk = lambda h: jnp.asarray(rs.randn(2, h, 2 * L, d).astype("f"))
    q, k, v = mk(4), mk(2), mk(2)
    mask = jnp.asarray(dense_mask(L, b))
    loss = lambda fn: lambda q, k, v: (fn(q, k, v) ** 2).sum()
    got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, mask=spec, use_pallas=False)), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: dense_attention(q, k, v, mask)),
                    (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert float(jnp.abs(g - w).max()) < 1e-4


@dataclasses.dataclass(frozen=True)
class CausalSpec:
    """The causal diagonal said as a mask spec, for the comparison
    below."""

    size: int

    def element(self, qid, kid):
        return kid <= qid

    def row_intervals(self):
        r = onp.arange(self.size)
        return onp.stack([onp.stack([0 * r, r + 1], -1),
                          onp.stack([0 * r, 0 * r], -1)], 1)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_causal_is_bitwise_what_the_spec_path_gives(cap_tiles, dtype):
    """``causal=True`` decides its tiles from the grid indices, a spec
    from its table: the same tiles in the same order with the same
    arithmetic, so the two agree bit for bit, forward and backward.
    (That ``causal=True`` traces to the jaxpr it traced to before the
    spec path existed was checked against the parent commit when the
    spec path was written: PERF.md, PR 30.)"""
    cap_tiles(128)
    S, h, d = 384, 2, 64
    rs = onp.random.RandomState(2)
    q, k, v, do = (jnp.asarray(rs.randn(1, h, S, d).astype("f"), dtype)
                   for _ in range(4))

    def both(**how):
        f = lambda q, k, v: flash_attention(q, k, v, use_pallas=True, **how)
        out, vjp = jax.vjp(f, q, k, v)
        return (out,) + vjp(do)

    for a, b in zip(both(causal=True), both(mask=CausalSpec(S))):
        assert a.dtype == b.dtype and bool(jnp.array_equal(a, b))


def test_causal_lowers_without_tables():
    """The causal call carries no prefetched table and no grouped index
    map: three operands forward, six backward, under the old names."""
    q = jax.ShapeDtypeStruct((2, 4, 512, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, use_pallas=True).astype(jnp.float32).sum(),
        (0, 1, 2)))(q, q, q)
    calls = {}

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                calls[eqn.params["name"]] = eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert sorted(calls) == ["flash_bwd", "flash_fwd"]
    assert len(calls["flash_fwd"].invars) == 3
    assert len(calls["flash_bwd"].invars) == 6
    for eqn in calls.values():
        assert eqn.params["grid_mapping"].num_index_operands == 0


@pytest.mark.parametrize("how", ["causal", "window"])
@pytest.mark.parametrize("dv", [64, 128])
def test_value_width_reaches_only_the_value_sized_blocks(how, dv):
    """A value head of ``Dv`` widens v, o, do, dv and their float32
    scratch alone; q, k, dq, dk keep D = 64. At ``Dv == D``, every call
    of the four older cells, each block is the width it had before wide
    value heads existed."""
    d = 64
    q = jax.ShapeDtypeStruct((1, 4, 1024, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 2, 1024, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 2, 1024, dv), jnp.bfloat16)
    kw = ({"causal": True} if how == "causal"
          else {"mask": fa.SlidingWindowMask(1024, 256)})
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, use_pallas=True, **kw).astype(jnp.float32).sum(),
        (0, 1, 2)))(q, k, v)
    calls = {}

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                calls[eqn.params["name"]] = eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)

    def widths(eqn):
        gm = eqn.params["grid_mapping"]
        blocks = [bm.block_aval.shape[-1] for bm in gm.block_mappings]
        scratch = [a.shape[-1] for a in gm.scratch_avals]
        return blocks, scratch

    # q, k, v in; o and the lse row out; (bq, 1) x 2 and o's accumulator
    blocks, scratch = widths(calls["flash_fwd"])
    assert blocks[:4] == [d, d, dv, dv] and scratch == [1, 1, dv]
    # q, k, v, do, lse, delta in; dq, dk, dv out; dk's and dv's
    # accumulators
    blocks, scratch = widths(calls["flash_bwd"])
    assert [blocks[i] for i in (0, 1, 2, 3, 6, 7, 8)] == \
        [d, d, dv, dv, d, d, dv]
    assert scratch == [d, dv]


def test_what_the_entry_refuses():
    q = jnp.zeros((1, 4, 256, 32))
    kv = jnp.zeros((1, 2, 256, 32))
    spec = BlockDiffusionMask(128, 4)
    with pytest.raises(ValueError, match="either causal"):
        flash_attention(q, kv, kv, causal=True, mask=spec)
    with pytest.raises(ValueError, match="query heads"):
        flash_attention(q, kv[:, :1].repeat(3, 1), kv[:, :1].repeat(3, 1))
    with pytest.raises(ValueError, match="mask over"):
        flash_attention(q, kv, kv, mask=BlockDiffusionMask(64, 4),
                        use_pallas=True)
    with pytest.raises(ValueError, match="no multiple"):
        BlockDiffusionMask(130, 4)
