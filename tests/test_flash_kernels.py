"""The flash attention kernels, forward and backward, against the plain
XLA oracle: interpret mode on the CPU at small shapes. Real multi-tile
sweeps on both grid axes are forced by capping the chooser at 128, since
the public path always asks the chooser for its tiles.
(``tests/test_chip_compile.py`` puts the same kernels to the chip's
compiler at real widths; ``chip_smoke.py kernels`` runs them there.)
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import kernels
from mxnet_tpu.kernels import cost_model
from mxnet_tpu.kernels import flash_attention as fa
from mxnet_tpu.kernels.flash_attention import (
    _pallas_forward, _ref_attention, choose_tiles, flash_attention)

H, D = 2, 32

#: name -> (S_q, S_k, cap on the chooser's tiles or None)
CASES = {
    "multi_tile_384": (384, 384, 128),  # 3 x 3 tiles of 128
    "ragged_100": (100, 100, None),     # padded to one 128 tile
    "sq_lt_sk": (100, 300, 128),        # bottom-right alignment, 1 x 3
}

#: max |kernel - oracle| allowed; bf16 carries 8 bits, the gradients here
#: reach ~4, and p and ds are rounded to bf16 as MXU operands on top of
#: the inputs' own rounding
TOL = {"float32": 1e-5, "bfloat16": 6e-2}


def _qkv(case, dtype, seed=0):
    s_q, s_k, cap = CASES[case]
    rs = onp.random.RandomState(seed)
    mk = lambda s: jnp.asarray(rs.randn(1, H, s, D).astype("f"), dtype)
    return mk(s_q), mk(s_k), mk(s_k), mk(s_q), cap


@pytest.fixture
def cap_tiles(monkeypatch):
    def cap(n):
        if n:
            monkeypatch.setattr(fa, "_FWD_CAPS", (n, n))
            monkeypatch.setattr(fa, "_BWD_CAPS", (n, n))
    return cap


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_reference(cap_tiles, causal, case, dtype):
    q, k, v, do, cap = _qkv(case, dtype)
    cap_tiles(cap)
    scale = 1.0 / D ** 0.5
    f32 = jnp.float32

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(f32)
                                * do.astype(f32)).sum()

    before = kernels.counters().get("flash_bwd_pallas", 0)
    got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, use_pallas=True)), (0, 1, 2))(q, k, v)
    assert kernels.counters()["flash_bwd_pallas"] == before + 1
    # the oracle differentiates plain attention on the same (rounded)
    # inputs, in float32
    want = jax.grad(loss(lambda q, k, v: _ref_attention(
        q, k, v, scale, causal, k.shape[2])), (0, 1, 2))(
            q.astype(f32), k.astype(f32), v.astype(f32))
    for name, g, w in zip("qkv", got, want):
        assert g.dtype == q.dtype and g.shape == w.shape
        err = float(jnp.abs(g.astype(f32) - w).max())
        assert err < TOL[dtype], (name, err)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("causal", [False, True])
def test_forward_lse_is_logsumexp_of_masked_scores(causal, case):
    q, k, v, _, cap = _qkv(case, "float32", seed=1)
    s_q, s_k = q.shape[2], k.shape[2]
    scale = 1.0 / D ** 0.5
    tiles = dict(bq=cap, bk=cap) if cap else {}
    o, lse = _pallas_forward(q, k, v, scale, causal, True, with_lse=True,
                             **tiles)
    assert lse.dtype == jnp.float32
    assert lse.shape == (1, H, 1, fa._pad128(s_q))  # lane-dense rows
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        qid = jnp.arange(s_q)[:, None] + (s_k - s_q)
        s = jnp.where(jnp.arange(s_k)[None, :] <= qid, s, -jnp.inf)
    want = jax.scipy.special.logsumexp(s, axis=-1)  # (1, H, S_q)
    assert float(jnp.abs(lse[:, :, 0, :s_q] - want).max()) < 1e-5
    ref = _ref_attention(q, k, v, scale, causal, s_k)
    assert float(jnp.abs(o - ref).max()) < 1e-5


#: the shapes tests/test_chip_compile.py compiles, and the benchmark's cell
SHAPES = [
    ((8, 12, 512, 64), 2), ((8, 12, 512, 64), 4), ((2, 16, 2048, 128), 2),
    ((4, 4, 100, 64), 4), ((2, 32, 2048, 64), 2),
]


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("shape,itemsize", SHAPES)
def test_chooser_tiles_fit_the_budget(shape, itemsize, backward):
    _, _, s, d = shape
    bq, bk = choose_tiles(s, s, d, itemsize, backward)
    padded = fa._pad128(s)
    for b in (bq, bk):
        assert b % 128 == 0 and padded % b == 0 and 128 <= b <= padded
    caps = fa._BWD_CAPS if backward else fa._FWD_CAPS
    # as large as the cap and the sequence allow at these shapes
    assert (bq, bk) == (min(caps[0], padded), min(caps[1], padded))
    assert fa.tile_vmem_bytes(bq, bk, s, d, itemsize, backward) \
        <= cost_model._VMEM_BUDGET_BYTES
    # the gate prices the same tiles
    assert cost_model.pallas_vmem_bytes("attention", (s, d), itemsize) \
        == fa.vmem_bytes(s, s, d, itemsize)
    assert cost_model.pallas_fits_vmem("attention", (s, d), itemsize)


def test_chooser_shrinks_with_the_budget():
    full = choose_tiles(2048, 2048, 64, 2)
    small = choose_tiles(2048, 2048, 64, 2, budget=2 << 20)
    assert small is not None and small != full
    assert small[0] <= full[0] and small[1] <= full[1]
    assert fa.tile_vmem_bytes(*small, 2048, 64, 2) <= 2 << 20
    assert choose_tiles(2048, 2048, 64, 2, budget=1 << 16) is None
    # tiles divide the padded sequence: 5 x 128 has no divisor between
    # 128 and the backward's cap of 512
    assert choose_tiles(640, 640, 64, 2, backward=True) == (128, 128)
    assert choose_tiles(768, 768, 64, 2, backward=True) == (384, 384)


def test_backward_past_a_whole_heads_dq_lowers_the_kernel_in_segments():
    """The (S_q, D) float32 dq of one head at S 16384, D 128 is alone over
    the budget (8 MiB, double-buffered): the backward cuts the head into
    segments whose dq fits, lowers the kernel and says so in the
    counters; the gate admits the shape. Traced only: nothing this large
    runs here. (Until PR 34 this shape took the scan.)"""
    s, d = 16384, 128
    assert fa._tiles_within(s, s, d, 2, True,
                            cost_model._VMEM_BUDGET_BYTES) is None
    assert choose_tiles(s, s, d, 2, backward=True) == (512, 512)
    assert fa.choose_backward(s, s, d, 2) == (512, 512, 4096)
    assert choose_tiles(s, s, d, 2) is not None
    assert cost_model._pallas_refusal("attention", (s, d), 2) is None
    x = jax.ShapeDtypeStruct((1, 1, s, d), jnp.bfloat16)
    before = kernels.counters()
    text = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, use_pallas=True).sum().astype(jnp.float32),
        (0, 1, 2)))(x, x, x))
    after = kernels.counters()
    assert after.get("flash_bwd_scan", 0) == before.get("flash_bwd_scan", 0)
    assert after["flash_bwd_pallas"] == before.get("flash_bwd_pallas", 0) + 1
    assert after["flash_bwd_q_segments"] == before.get(
        "flash_bwd_q_segments", 0) + 4
    assert "name=flash_fwd" in text and "name=flash_bwd" in text
    assert "scan[" not in text


def test_backward_that_fits_no_tile_takes_the_scan_and_counts(monkeypatch):
    """Where not even a 128 x 128 tile fits beside a segment of 128 rows
    the chooser gives nothing, the forward keeps no lse and the backward
    lowers the scan, loudly."""
    monkeypatch.setattr(fa, "choose_tiles",
                        lambda *a, backward=False, **kw: None if backward
                        else choose_tiles(*a, **kw))
    assert fa.choose_backward(256, 256, 64, 4, 1 << 16) is None
    q, k, v, _, _ = _qkv("ragged_100", "float32")
    before = kernels.counters()
    text = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, use_pallas=True).sum(), (0, 1, 2)))(q, k, v))
    after = kernels.counters()
    assert after.get("flash_bwd_scan", 0) == before.get("flash_bwd_scan",
                                                        0) + 1
    assert after.get("flash_bwd_pallas", 0) == before.get(
        "flash_bwd_pallas", 0)
    assert "scan[" in text and "name=flash_bwd" not in text


def test_xla_path_keeps_the_scan_and_counts_it():
    q, k, v, _, _ = _qkv("ragged_100", "float32")
    before = kernels.counters().get("flash_bwd_scan", 0)
    jax.grad(lambda q: flash_attention(q, k, v, causal=True,
                                       use_pallas=False).sum())(q)
    assert kernels.counters()["flash_bwd_scan"] == before + 1
