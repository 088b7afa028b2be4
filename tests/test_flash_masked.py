"""The flash kernels under a static mask spec and with grouped heads:
``BlockDiffusionMask`` over ``[xt ; x0]`` against a dense mask built from
the three rules, forward and backward in interpret mode on the CPU, at
tiles that make dead, whole and partly masked tiles all occur; the table
of tile kinds against brute force over the element rule; and
``causal=True`` left as it was. (``tests/test_chip_compile.py`` compiles
the same kernels for the chip at the cell's shape.)
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import kernels
from mxnet_tpu.kernels import flash_attention as fa
from mxnet_tpu.kernels.flash_attention import (
    DEAD, FIRST, PARTIAL, WHOLE, BlockDiffusionMask, flash_attention,
    mask_tile_table)

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
    def cap(n):
        monkeypatch.setattr(fa, "_FWD_CAPS", (n, n))
        monkeypatch.setattr(fa, "_BWD_CAPS", (n, n))
    return cap


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


@pytest.mark.parametrize("L,b,bq,bk", [
    (256, 4, 128, 128), (256, 4, 256, 128), (256, 4, 128, 256),
    (512, 4, 512, 512), (384, 32, 128, 128), (128, 1, 128, 128),
    (512, 8, 256, 512), (4096, 4, 1024, 1024), (4096, 4, 512, 512)])
def test_tile_table_against_brute_force(L, b, bq, bk):
    table = mask_tile_table(BlockDiffusionMask(L, b), bq, bk)
    S = 2 * L
    count = dense_mask(L, b).reshape(S // bq, bq, S // bk, bk).sum((1, 3))
    want = onp.where(count == 0, DEAD,
                     onp.where(count == bq * bk, WHOLE, PARTIAL))
    assert ((table & (WHOLE | PARTIAL)) == want).all()
    live = want != DEAD
    # FIRST sits on each q tile's first live k tile and nowhere else
    assert (((table & FIRST) != 0).sum(1) == 1).all()
    assert ((table & FIRST) != 0)[onp.arange(S // bq), live.argmax(1)].all()
    # a dead tile names a live block of its row (forward) or column
    # (backward), a live one names itself
    for axis in (1, 0):
        fetch = fa._fetch_table(table, axis)
        own = onp.arange(table.shape[axis])
        own = own[None] if axis == 1 else own[:, None]
        assert (fetch[live] == onp.broadcast_to(own, live.shape)[live]).all()
        named = onp.take_along_axis(live, fetch, axis)
        assert named.all()


def test_all_three_kinds_occur_at_the_tested_tiles():
    kinds = mask_tile_table(BlockDiffusionMask(256, 4), 128, 128) \
        & (WHOLE | PARTIAL)
    assert {DEAD, WHOLE, PARTIAL} == set(kinds.reshape(-1).tolist())
    # the dead quadrant: no clean query sees a noised key
    assert (kinds[2:, :2] == DEAD).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("hq,hkv,d", [(8, 2, 128), (4, 4, 64)])
def test_masked_flash_matches_the_dense_mask(cap_tiles, hq, hkv, d, dtype):
    """8 query heads over 2 key/value heads of 128 (and no grouping at
    64), L=256 in blocks of 4 at tiles of 128: 4 x 4 tiles of all three
    kinds, forward and the fused backward."""
    L, b = 256, 4
    cap_tiles(128)
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
