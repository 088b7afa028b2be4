"""``kernels.delta_prologue``: the two Pallas kernels between Gated
DeltaNet's q|k|v|z projection and the delta rule, interpreted, against the
``jax.numpy`` twin (q, k, v; d(qkvz) and the convolution weight's
gradient) over several tiles of positions, the convolution's halo checked
by hand across a tile boundary and at the sequence's edges, what the
kernels refuse, and the counters of the path a ``MoEDecoderLM`` takes."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import kernels
from mxnet_tpu.kernels import delta_prologue as dp

# (key heads, value heads, head size)
HEADS = {
    # the Qwen3-Next layout at small widths: a key head serves 2 value heads
    "grouped": (2, 4, 128),
    "one_to_one": (1, 1, 128),
}
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 1e-2}


def _case(heads, dtype, b=2, s=384, taps=4, seed=0):
    hk, hv, d = HEADS[heads]
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    qkvz = (jax.random.normal(ks[0], (b, s, 2 * (hk + hv) * d)) * 2) \
        .astype(dtype)
    conv_w = (jax.random.normal(ks[1], (taps, (2 * hk + hv) * d)) * 0.5) \
        .astype(dtype)
    return qkvz, conv_w, (hk, hv, d, d)


def _rel(got, want):
    got, want = onp.asarray(got, "float64"), onp.asarray(want, "float64")
    return onp.linalg.norm(got - want) / max(onp.linalg.norm(want), 1e-30)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", list(HEADS))
def test_kernels_match_the_plain_twin(heads, dtype):
    """Forward values and the VJP, d(qkvz) and the weight's gradient, of
    the kernels (interpreted, three tiles of 128 positions, two
    sequences) against the twin. float32 agrees to rounding; in bfloat16
    the forward is the same rounding of the same float32 values, and the
    twin's backward rounds each tap's cotangent, the kernels once."""
    qkvz, conv_w, sizes = _case(heads, dtype)
    assert dp.tiles(qkvz.shape[1], dp.Layout(*sizes, 4),
                    qkvz.dtype.itemsize)[0] == 128

    def run(use_pallas):
        out, vjp = jax.vjp(lambda x, w: dp.delta_prologue(
            x, w, *sizes, use_pallas=use_pallas), qkvz, conv_w)
        cot = tuple(jax.random.normal(jax.random.PRNGKey(i + 5), a.shape)
                    .astype(a.dtype) for i, a in enumerate(out))
        return out + vjp(cot)

    want, got = run(False), run(True)
    for name, a, b in zip(("q", "k", "v", "dqkvz", "dconv_w"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) < TOL[dtype], (name, _rel(a, b))
    # the rule's layout: (B, H, S, d), a key head's q scaled by dk ** -0.5
    hk, hv, d, _ = sizes
    assert got[0].shape == (2, hk, 384, d) and got[2].shape == (2, hv, 384, d)
    norms = onp.linalg.norm(onp.asarray(got[0], "float32"), axis=-1)
    onp.testing.assert_allclose(norms, d ** -0.5, rtol=1e-2)
    # z's columns get no cotangent from the prologue
    assert not onp.asarray(got[3][..., (2 * hk + hv) * d:]).any()


@pytest.mark.parametrize("at", [0, 125, 253, 380])
def test_the_convolution_by_hand_across_tiles(at):
    """An impulse at position ``at`` of every q|k|v channel reaches the
    next ``taps`` positions as ``silu(w_3), silu(w_2), ...`` (v has no
    norm) and nothing else: across the boundaries of the 128-position
    tiles (125 and 253 spill into the next tile), with nothing before the
    first position and nothing wrapped from the sequence's end (380)."""
    hk, hv, d, s, taps = 1, 1, 128, 384, 4
    qkvz = jnp.zeros((1, s, 2 * (hk + hv) * d)).at[0, at, :3 * d].set(1.0)
    w = jnp.arange(1, taps + 1, dtype=jnp.float32)[:, None] \
        * jnp.ones((1, 3 * d))
    v = dp.delta_prologue(qkvz, w, hk, hv, d, d, use_pallas=True)[2]
    want = onp.zeros((s,), "float32")
    for j in range(taps):
        if at + taps - 1 - j < s:
            want[at + taps - 1 - j] = jax.nn.silu(float(j + 1))
    onp.testing.assert_allclose(onp.asarray(v[0, 0, :, 0]), want, rtol=1e-6)
    onp.testing.assert_allclose(onp.asarray(v[0, 0, :, :]),
                                onp.repeat(want[:, None], d, 1), rtol=1e-6)


def test_what_the_kernels_cannot_take_is_refused_or_left_to_the_twin():
    lay = dp.Layout(16, 32, 128, 128, 4)
    assert dp.eligible(8192, lay, 2)
    assert not dp.eligible(8192, lay._replace(k_dim=64, v_dim=64), 2)
    assert not dp.eligible(8192, lay._replace(v_dim=256), 2)
    assert not dp.eligible(8200, lay, 2)    # no whole tiles of positions
    assert not dp.eligible(8192, lay._replace(taps=20), 2)
    qkvz = jnp.zeros((1, 256, 2 * (2 + 4) * 64), jnp.bfloat16)
    w = jnp.zeros((4, (2 * 2 + 4) * 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="multiples of 128"):
        dp.delta_prologue(qkvz, w, 2, 4, 64, 64, use_pallas=True)
    with pytest.raises(ValueError, match="columns"):
        dp.delta_prologue(qkvz[..., 1:], w, 2, 4, 64, 64)
    before = kernels.counters()
    q, k, v = dp.delta_prologue(qkvz, w, 2, 4, 64, 64)    # the twin
    assert (q.shape, v.shape) == ((1, 2, 256, 64), (1, 4, 256, 64))
    after = kernels.counters()
    assert after["delta_prologue_plain"] == \
        before.get("delta_prologue_plain", 0) + 1
    assert after.get("delta_prologue_pallas", 0) == \
        before.get("delta_prologue_pallas", 0)


def test_the_cells_tiles():
    """qwen3next80b-train-s8192's linear layer, (1, 8192) of 16 key and
    32 value heads of 128 in bfloat16: 1,024 positions of 2 heads a grid
    step, inside the VMEM budget."""
    lay = dp.Layout(16, 32, 128, 128, 4)
    assert dp.tiles(8192, lay, 2) == (1024, 2)
    assert dp._vmem_bytes(1024, 2, lay, 2) <= dp._VMEM_BUDGET_BYTES


def _qwen3next_pattern():
    from mxnet_tpu import models, nd

    linear = {"gated_delta": dict(num_k_heads=1, num_v_heads=2,
                                  head_k_dim=128, head_v_dim=128)}
    net = models.MoEDecoderLM(
        vocab_size=64, embed_dim=64, num_layers=4, num_heads=2,
        num_kv_heads=1, head_dim=128, num_experts=4, expert_dim=32,
        top_k=2, attention=[linear] * 3 + ["causal"], rotary_dim=32,
        output_gate=True)
    net.initialize()
    tokens = onp.zeros((1, 128), "int32")
    net(nd.array(tokens, dtype="int32"))         # deferred shapes
    return net, tokens


@pytest.mark.parametrize("backend,counted", [
    ("tpu", "delta_prologue_pallas"), ("cpu", "delta_prologue_plain")])
def test_each_linear_layer_counts_its_path(backend, counted, monkeypatch):
    """A ``MoEDecoderLM`` of the Qwen3-Next pattern (three Gated DeltaNet
    layers, one gated full layer) traced for the TPU takes the kernels in
    each linear layer, once a layer; on the CPU the twin."""
    from mxnet_tpu import nd

    net, tokens = _qwen3next_pattern()
    other = ({"delta_prologue_pallas", "delta_prologue_plain"}
             - {counted}).pop()
    before = kernels.counters()
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: backend)
        jax.eval_shape(lambda t: net(nd.NDArray(t)).data,
                       jax.ShapeDtypeStruct(tokens.shape, jnp.int32))
    after = kernels.counters()
    assert after.get(counted, 0) - before.get(counted, 0) == 3
    assert after.get(other, 0) == before.get(other, 0)
