"""The grouped matrix product and its two backward products
(``kernels/grouped_matmul.py``) against a per-expert loop, in interpret
mode on the CPU, under the imbalance a router really gives: an empty
group, one group that takes every row, sizes off the tile, rows that no
group owns. (``tests/test_chip_compile.py`` puts the kernels to the
chip's compiler at the cell's widths; ``chip_smoke.py --phases masked``
runs them there.)
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import kernels
from mxnet_tpu.kernels import grouped_matmul as gm

M, K, N, G = 1024, 256, 384, 5

#: name -> group sizes over the M rows
SIZES = {
    "empty_groups_and_off_tile": [0, 300, 0, 513, 100],
    "one_group_takes_every_row": [0, 0, 1024, 0, 0],
    "nothing_routed": [0, 0, 0, 0, 0],
    "whole_tiles": [256, 256, 256, 128, 128],
    "a_few_rows_each": [1, 2, 3, 4, 5],
    "straddles_every_tile": [130, 255, 257, 126, 256],
}

TOL = {"float32": 2e-5, "bfloat16": 3e-2}


def _operands(dtype, seed=0):
    rs = onp.random.RandomState(seed)
    lhs = jnp.asarray(rs.randn(M, K).astype("f"), dtype)
    rhs = jnp.asarray(rs.randn(G, K, N).astype("f") * 0.1, dtype)
    dout = jnp.asarray(rs.randn(M, N).astype("f"), dtype)
    return lhs, rhs, dout


def _loop(lhs, rhs, dout, sizes):
    """out, dlhs, drhs by a plain loop over the groups, in float32."""
    f32 = jnp.float32
    lhs, rhs, dout = (a.astype(f32) for a in (lhs, rhs, dout))
    out, dlhs = jnp.zeros((M, N), f32), jnp.zeros((M, K), f32)
    drhs = []
    start = 0
    for g, size in enumerate(sizes):
        rows = slice(start, start + size)
        out = out.at[rows].set(lhs[rows] @ rhs[g])
        dlhs = dlhs.at[rows].set(dout[rows] @ rhs[g].T)
        drhs.append(lhs[rows].T @ dout[rows])
        start += size
    return out, dlhs, jnp.stack(drhs)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_products_match_the_per_expert_loop(case, dtype):
    lhs, rhs, dout = _operands(dtype)
    sizes = SIZES[case]
    gs = jnp.asarray(sizes, jnp.int32)
    before = kernels.counters().get("moe_gmm_pallas", 0)

    def run(lhs, rhs):
        out, vjp = jax.vjp(lambda a, b: gm.grouped_matmul(
            a, b, gs, use_pallas=True), lhs, rhs)
        return (out,) + vjp(dout)

    with jax.default_matmul_precision("highest"):
        got = run(lhs, rhs)
        want = _loop(lhs, rhs, dout, sizes)
    assert kernels.counters()["moe_gmm_pallas"] == before + 1
    for name, g, w in zip(("out", "dlhs", "drhs"), got, want):
        assert g.shape == w.shape and g.dtype == lhs.dtype, name
        scale = max(float(jnp.abs(w).max()), 1.0)
        err = float(jnp.abs(g.astype(jnp.float32) - w).max()) / scale
        assert err < TOL[dtype], (name, err)
    # rows that no group owns give zeros, exactly
    assert not bool(jnp.any(got[0][sum(sizes):]))
    assert not bool(jnp.any(got[1][sum(sizes):]))


@pytest.mark.parametrize("case", sorted(SIZES))
def test_plain_twin_is_ragged_dot_and_agrees(case):
    """``use_pallas=False`` is ``jax.lax.ragged_dot``; both count which
    one a trace lowered."""
    lhs, rhs, dout = _operands("float32", seed=1)
    gs = jnp.asarray(SIZES[case], jnp.int32)
    before = kernels.counters().get("moe_gmm_plain", 0)
    with jax.default_matmul_precision("highest"):
        plain = gm.grouped_matmul(lhs, rhs, gs, use_pallas=False)
        kern = gm.grouped_matmul(lhs, rhs, gs, use_pallas=True)
    assert kernels.counters()["moe_gmm_plain"] == before + 1
    assert float(jnp.abs(plain - kern).max()) < 2e-5


@pytest.mark.parametrize("sizes", sorted(SIZES.values()))
def test_visits_walk_every_tile_once_per_group_it_holds(sizes):
    """The walk against brute force: the live visits are exactly the
    (tile, group) pairs that share a row, in row order, the rows no group
    owns walked as group G."""
    group, tile, starts, ends, live = (onp.asarray(a) for a in gm.visits(
        jnp.asarray(sizes, jnp.int32), M))
    owner = onp.repeat(onp.arange(G + 1), sizes + [M - sum(sizes)])
    want = sorted({(r // gm.ROWS, int(owner[r])) for r in range(M)})
    n = int(live[0])
    assert list(zip(tile[:n].tolist(), group[:n].tolist())) == want
    assert len(group) == M // gm.ROWS + G        # the static grid
    assert (group[n:] == group[n - 1]).all() and (tile[n:] == tile[n - 1]).all()
    assert (ends - starts).tolist() == sizes + [M - sum(sizes)]


def test_gate_refuses_what_the_kernels_cannot_take():
    assert gm.eligible(16384, 2048, 1536, 2)
    assert gm.eligible(16384, 768, 2048, 2)
    assert not gm.eligible(1000, 256, 384, 4)       # rows off the tile
    assert not gm.eligible(1024, 200, 384, 4)       # width off the lanes
    assert not gm.eligible(1024, 1 << 16, 128, 4)   # contraction too long
    lhs, rhs, _ = _operands("float32")
    with pytest.raises(ValueError, match="cannot take"):
        gm.grouped_matmul(lhs[:1000], rhs, jnp.zeros(G, jnp.int32),
                          use_pallas=True)


@pytest.mark.parametrize("k,n,itemsize", [
    (2048, 1536, 2), (768, 2048, 2), (2048, 1536, 4), (768, 2048, 4)])
def test_chosen_tiles_divide_the_widths_and_shrink_with_the_type(
        k, n, itemsize):
    tn, tkn, tk = gm.choose_tiles(k, n, itemsize)
    assert n % tn == 0 and k % tkn == 0 and k % tk == 0
    wide = gm.choose_tiles(k, n, 2)
    assert all(a <= b for a, b in zip((tn, tkn, tk), wide))
