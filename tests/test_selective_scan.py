"""The selective-scan kernels (``kernels/selective_scan.py``) in interpret
mode against their plain twin: the forward, and the VJP of every input,
at two chunk sizes and a length that is no multiple of the chunk."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import kernels
from mxnet_tpu.kernels import selective_scan as ss
from mxnet_tpu.kernels.cost_model import _VMEM_BUDGET_BYTES


def _inputs(b, s, ch, n, dtype, seed=0):
    """Inputs of a Mamba layer's sizes: dt and its bias such that delta =
    softplus(dt + bias) spans [1e-3, 0.1] as the family's initialiser
    draws it, A = -(1..n) a channel."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    u = jax.random.normal(ks[0], (b, s, ch)).astype(dtype)
    z = jax.random.normal(ks[1], (b, s, ch)).astype(dtype)
    delta = jnp.exp(jax.random.uniform(ks[2], (b, s, ch), jnp.float32,
                                       jnp.log(1e-3), jnp.log(0.1)))
    bias = 0.5 * jax.random.normal(ks[6], (ch,))
    dt = (jnp.log(jnp.expm1(delta)) - bias).astype(dtype)   # softplus^-1
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (ch, n)) \
        * jnp.exp(0.1 * jax.random.normal(ks[3], (ch, n)))
    bc = jax.random.normal(ks[4], (2, b, s, n)).astype(dtype)
    d = 1.0 + 0.1 * jax.random.normal(ks[5], (ch,))
    return u, dt, a, bc[0], bc[1], d, z, bias


NAMES = "u dt A B C D z dt_bias".split()


def _rel(x, y):
    x, y = (onp.asarray(v, onp.float64) for v in (x, y))
    return float(onp.linalg.norm(x - y) / max(onp.linalg.norm(y), 1e-30))


@pytest.mark.parametrize("s,chunk,ch", [
    (64, 32, 256),      # two chunks of two groups
    (80, 64, 256),      # one whole chunk and a padded one
    (48, 256, 256),     # one chunk shorter than the default
    (384, 128, 256),    # three chunks of 128 rows
    (768, 256, 256),    # three chunks of the default 256
    (300, 128, 256),    # two whole chunks of 128 and a padded third
    (64, 32, 384),      # three channel tiles of 128 lanes
], ids=["64-32", "80-64", "48-256", "384-128", "768-256", "300-128",
        "64-32-384"])
def test_kernels_match_the_twin_forward_and_vjp(s, chunk, ch):
    """float32 throughout: the kernel and the twin differ by the order of
    float32 sums only (a position's read-out over 16 states; dB and dC
    over the channels, a tile's lanes folded and transposed; du and d(dt)
    over the states in halving steps), so 1e-5 of each result's norm:
    what a sum taken in bfloat16 would miss by far."""
    args = _inputs(2, s, ch, 16, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(9), (2, s, ch))

    def loss(use, *xs):
        g = ss.selective_scan(*xs, chunk=chunk, use_pallas=use)
        return jnp.sum(g * w), g

    before = kernels.counters()
    (lk, gk), dk = jax.value_and_grad(loss, range(1, 9), has_aux=True)(
        True, *args)
    (lp, gp), dp = jax.value_and_grad(loss, range(1, 9), has_aux=True)(
        False, *args)
    after = kernels.counters()
    assert _rel(gk, gp) < 1e-5
    for name, x, y in zip(NAMES, dk, dp):
        assert x.shape == y.shape and x.dtype == y.dtype, name
        assert _rel(x, y) < 1e-5, (name, _rel(x, y))
    rows = ss.chunk_rows(s, chunk)
    assert after["ssm_scan_pallas"] - before.get("ssm_scan_pallas", 0) == 1
    assert after["ssm_scan_chunks"] - before.get("ssm_scan_chunks", 0) \
        == 2 * (-(-s // rows))


def test_z_read_in_place_from_the_input_projections_result():
    """As a Mamba layer calls it: dt bfloat16 (the dt projection's
    result), z the second half of the input projection's [u | z] read in
    place. Against the twin on the same arguments, forward and the VJP of
    all eight, the wide array's first half getting no gradient from z;
    float32 elsewhere, so 1e-5 again."""
    u, dt, a, b, c, d, z, bias = _inputs(1, 48, 128, 16, jnp.float32,
                                         seed=7)
    dt = dt.astype(jnp.bfloat16)
    wide = jnp.concatenate([u, z], axis=-1)
    w = jax.random.normal(jax.random.PRNGKey(4), (1, 48, 128))

    def loss(use, u, dt, a, b, c, d, wide, bias):
        g = ss.selective_scan(u, dt, a, b, c, d, wide, bias, z_col=128,
                              chunk=32, use_pallas=use)
        return jnp.sum(g * w), g

    args = (u, dt, a, b, c, d, wide, bias)
    (_, gk), dk = jax.value_and_grad(loss, range(1, 9), has_aux=True)(
        True, *args)
    (_, gp), dp = jax.value_and_grad(loss, range(1, 9), has_aux=True)(
        False, *args)
    assert _rel(gk, gp) < 1e-5
    for name, x, y in zip(NAMES, dk, dp):
        assert x.shape == y.shape and x.dtype == y.dtype, name
        assert _rel(x, y) < 1e-5, (name, _rel(x, y))
    assert not onp.asarray(dk[6][..., :128]).any()
    # the same scan with z an array of its own
    assert _rel(gk, ss.selective_scan(u, dt, a, b, c, d, z, bias,
                                      use_pallas=False)) < 1e-5


def test_bfloat16_inputs_keep_a_float32_state():
    """bfloat16 u, z, B, C: the kernel rounds only g and the gradients it
    writes, the twin the same, so they agree to bfloat16's rounding of
    the results (2^-8 of the norm)."""
    args = _inputs(1, 64, 128, 16, jnp.bfloat16, seed=3)

    def loss(use, *xs):
        g = ss.selective_scan(*xs, chunk=32, use_pallas=use)
        return jnp.sum(g.astype(jnp.float32) ** 2), g

    (_, gk), dk = jax.value_and_grad(loss, range(1, 9), has_aux=True)(
        True, *args)
    (_, gp), dp = jax.value_and_grad(loss, range(1, 9), has_aux=True)(
        False, *args)
    assert gk.dtype == jnp.bfloat16
    assert _rel(gk, gp) < 2 ** -8
    for name, x, y in zip(NAMES, dk, dp):
        assert _rel(x, y) < 2 ** -7, (name, _rel(x, y))


def test_the_twin_is_the_recurrence_as_written():
    """The twin against the recurrence stepped position by position in
    numpy float64, softplus included."""
    u, dt, a, b, c, d, z, bias = (onp.asarray(x, onp.float64) for x in
                                  _inputs(1, 12, 128, 16, jnp.float32,
                                          seed=5))
    h = onp.zeros((128, 16))
    want = onp.zeros((1, 12, 128))
    for t in range(12):
        delta = onp.log1p(onp.exp(dt[0, t] + bias))
        h = onp.exp(delta[:, None] * a) * h \
            + (delta * u[0, t])[:, None] * b[0, t][None]
        y = h @ c[0, t] + d * u[0, t]
        want[0, t] = y * z[0, t] / (1 + onp.exp(-z[0, t]))
    got = ss.selective_scan(*(jnp.asarray(x, jnp.float32) for x in
                              (u, dt, a, b, c, d, z, bias)),
                            use_pallas=False)
    assert _rel(got, want) < 1e-6


def test_eligible_and_the_refusal():
    """The Phi-4-mini-flash cell's scan (5,120 channels, 16 states,
    bfloat16) fits the backward's blocks and scratch, the stored gradient
    of each position's state among them; at 32 states that store
    (256 x 32 x 256 float32, 8.4 MB) takes the backward over the budget at
    the default chunk, which it fitted without the store, and not at a
    chunk of 128."""
    assert ss.eligible(5120, 16, 2) and ss.lanes_of(5120) == 256
    assert ss.vmem_bytes(256, 256, 16, 2) <= _VMEM_BUDGET_BYTES
    assert ss.vmem_bytes(256, 256, 32, 2) > _VMEM_BUDGET_BYTES
    assert not ss.eligible(5120, 32, 2) and ss.eligible(5120, 32, 2, 128)
    assert ss.lanes_of(384) == 128 and not ss.eligible(100, 16, 2)
    args = _inputs(1, 16, 96, 16, jnp.float32)
    with pytest.raises(ValueError, match="in multiples of 128"):
        ss.selective_scan(*args, use_pallas=True)
    before = kernels.counters().get("ssm_scan_plain", 0)
    ss.selective_scan(*args)        # off-TPU: the twin
    assert kernels.counters()["ssm_scan_plain"] == before + 1
