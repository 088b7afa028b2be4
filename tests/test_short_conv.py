"""``kernels.short_conv``: LFM2's gated short convolution as two Pallas
kernels, interpreted, against the ``jax.numpy`` twin (y; dB, dC and dx,
the three column blocks of d(B|C|x); the weight's gradient) over several
tiles of positions, the convolution's halo checked by hand across a tile
boundary and at the sequence's edges, what the kernels refuse, and the
counters of the path a ``MoEDecoderLM`` takes."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import kernels
from mxnet_tpu.kernels import short_conv as sc

TOL = {jnp.float32: 2e-6, jnp.bfloat16: 1e-2}


def _case(dtype, b=2, s=384, e=256, taps=3, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    bcx = jax.random.normal(ks[0], (b, s, 3 * e)).astype(dtype)
    conv_w = (jax.random.normal(ks[1], (taps, e)) * 0.5).astype(dtype)
    return bcx, conv_w


def _rel(got, want):
    got, want = onp.asarray(got, "float64"), onp.asarray(want, "float64")
    return onp.linalg.norm(got - want) / max(onp.linalg.norm(want), 1e-30)


def _run(bcx, conv_w, use_pallas, seed=5):
    y, vjp = jax.vjp(lambda x, w: sc.short_conv(x, w, use_pallas=use_pallas),
                     bcx, conv_w)
    dy = jax.random.normal(jax.random.PRNGKey(seed), y.shape).astype(y.dtype)
    d, dw = vjp(dy)
    e = conv_w.shape[1]
    return {"y": y, "dB": d[..., :e], "dC": d[..., e:2 * e],
            "dx": d[..., 2 * e:], "dw": dw}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("s,rows", [(384, 128), (1536, 512)],
                         ids=["three_tiles_of_128", "not_a_whole_1024"])
def test_kernels_match_the_plain_twin(dtype, s, rows):
    """Forward values and the VJP, the three column blocks of d(B|C|x)
    and the weight's gradient, of the kernels (interpreted, two
    sequences, three tiles: 384 positions in tiles of 128, and 1,536,
    which is no whole number of the largest tile, in tiles of 512)
    against the twin. float32 agrees to rounding; in bfloat16 both round
    the same float32 values once."""
    bcx, conv_w = _case(dtype, s=s)
    assert sc.tiles(s, 256, bcx.dtype.itemsize, False)[0] == rows
    want, got = _run(bcx, conv_w, False), _run(bcx, conv_w, True)
    for name in want:
        a, b = got[name], want[name]
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) < TOL[dtype], (name, _rel(a, b))


@pytest.mark.parametrize("at", [0, 126, 254, 383])
def test_the_convolution_by_hand_across_tiles(at):
    """With B = x = 1 at position ``at`` alone and C = 1 everywhere, y is
    ``w_2, w_1, w_0`` at ``at, at + 1, at + 2`` and nothing else: across
    the boundaries of the 128-position tiles (126 and 254 spill into the
    next tile), with nothing before the first position (the first tile's
    halo is zero) and nothing wrapped from the sequence's end (383)."""
    s, e, taps = 384, 128, 3
    bcx = jnp.zeros((1, s, 3 * e)).at[0, at, :e].set(1.0) \
        .at[0, at, 2 * e:].set(1.0).at[0, :, e:2 * e].set(1.0)
    w = jnp.tile(jnp.asarray([[0.5], [-2.0], [3.0]]), (1, e))
    y = onp.asarray(sc.short_conv(bcx, w, use_pallas=True))[0]
    want = onp.zeros((s, e))
    for j in range(taps):
        if at + j < s:
            want[at + j] = w[taps - 1 - j, 0]
    onp.testing.assert_array_equal(y, want)


def test_the_backward_halos_by_hand():
    """The adjoint across a tile boundary: dy = 1 at position 128 alone
    (the second tile's first row), C = 1, B = x = 1 everywhere: du is
    ``w_2, w_1, w_0`` at 128, 127, 126, so dB = dx = du reaches back
    into the first tile, and dC at 128 is z there, the sum of the
    taps."""
    s, e = 384, 128
    bcx = jnp.ones((1, s, 3 * e))
    w = jnp.tile(jnp.asarray([[0.5], [-2.0], [3.0]]), (1, e))
    _, vjp = jax.vjp(lambda x: sc.short_conv(x, w, use_pallas=True), bcx)
    (d,) = vjp(jnp.zeros((1, s, e)).at[0, 128].set(1.0))
    d = onp.asarray(d)[0]
    du = onp.zeros(s)
    du[[128, 127, 126]] = [3.0, -2.0, 0.5]
    for part in (d[:, :e], d[:, 2 * e:]):       # dB, dx
        onp.testing.assert_array_equal(part, onp.tile(du[:, None], (1, e)))
    dc = onp.zeros(s)
    dc[128] = 1.5
    onp.testing.assert_array_equal(d[:, e:2 * e],
                                   onp.tile(dc[:, None], (1, e)))


def test_what_the_kernels_refuse():
    """Channels off the 128 lanes, positions off the tiles and more taps
    than a halo reaches: ``eligible`` refuses, a forced call raises, and
    the default path takes the twin and counts it."""
    assert sc.eligible(8192, 2048, 3, 2)
    assert not sc.eligible(8192, 2000, 3, 2)        # channels
    assert not sc.eligible(100, 2048, 3, 2)         # positions
    assert not sc.eligible(8192, 2048, 18, 2)       # taps past the halo
    bcx = jnp.zeros((1, 100, 3 * 128), jnp.bfloat16)
    w = jnp.zeros((3, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="multiples of 128"):
        sc.short_conv(bcx, w, use_pallas=True)
    with pytest.raises(ValueError, match=r"B \| C \| x"):
        sc.short_conv(bcx[..., 1:], w)
    before = kernels.counters()
    y = sc.short_conv(bcx, w)        # the twin
    assert y.shape == (1, 100, 128)
    after = kernels.counters()
    assert after["short_conv_plain"] == before.get("short_conv_plain", 0) + 1
    assert after.get("short_conv_pallas", 0) == \
        before.get("short_conv_pallas", 0)


def test_the_cells_tiles():
    """lfm2moe24b-train-s8192's conv layers, (1, 8192) of 2,048 channels
    in bfloat16: 1,024 positions of 512 channels a forward step and of
    256 a backward step, inside the VMEM budget."""
    assert sc.tiles(8192, 2048, 2, False) == (1024, 512)
    assert sc.tiles(8192, 2048, 2, True) == (1024, 256)
    for backward, cols in ((False, 512), (True, 256)):
        assert sc._vmem(1024, cols, 2, backward) <= sc._VMEM_BUDGET_BYTES


@pytest.mark.parametrize("backend,counted", [
    ("tpu", "short_conv_pallas"), ("cpu", "short_conv_plain")])
def test_each_conv_layer_counts_its_path(backend, counted, monkeypatch):
    """A ``MoEDecoderLM`` of the LFM2 pattern (conv, conv, attention,
    conv) traced for the TPU takes the kernels in each conv layer, once a
    layer; on the CPU the twin."""
    from mxnet_tpu import models, nd

    conv = {"short_conv": {"taps": 3}}
    net = models.MoEDecoderLM(
        vocab_size=64, embed_dim=128, num_layers=4, num_heads=2,
        num_kv_heads=1, head_dim=64, num_experts=8, expert_dim=32, top_k=2,
        experts_held=(0, 4), attention=[conv, conv, "causal", conv],
        mlp=[{"dense": 64}] * 2 + ["moe"] * 2, score="sigmoid",
        expert_bias=1e-3, tie_embeddings=True)
    net.initialize()
    tokens = onp.zeros((1, 128), "int32")
    net(nd.array(tokens, dtype="int32"))         # deferred shapes
    other = ({"short_conv_pallas", "short_conv_plain"} - {counted}).pop()
    before = kernels.counters()
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: backend)
        jax.eval_shape(lambda t: net(nd.NDArray(t)).data,
                       jax.ShapeDtypeStruct(tokens.shape, jnp.int32))
    after = kernels.counters()
    assert after.get(counted, 0) - before.get(counted, 0) == 3
    assert after.get(other, 0) == before.get(other, 0)
