"""``kernels.gated_delta``: the chunked gated delta rule against the
recurrence position by position (output and all five gradients, float32),
its four Pallas kernels interpreted against the ``jax.numpy`` twin (the
preparation and its hand-written VJP output by output, the walks through
the whole rule), the edges of decay and beta, and the counters."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax import lax

from mxnet_tpu import kernels
from mxnet_tpu.kernels import gated_delta as gd
from mxnet_tpu.kernels.gated_delta import gated_delta_rule

TOL = 2e-5      # float32 on both sides: sums in another order


def recurrence(q, k, v, g, beta):
    """The rule as written, one position at a time; key head j serves
    the value heads from j * Hv / Hk on."""
    rep = v.shape[1] // k.shape[1]
    q, k = jnp.repeat(q, rep, 1), jnp.repeat(k, rep, 1)

    def head(q, k, v, g, beta):
        def step(m, x):
            qt, kt, vt, gt, bt = x
            m = jnp.exp(gt) * m
            d = bt * (vt - m.T @ kt)
            m = m + jnp.outer(kt, d)
            return m, m.T @ qt

        m0 = jnp.zeros((k.shape[-1], v.shape[-1]), jnp.float32)
        return lax.scan(step, m0, (q, k, v, g, beta))[1]

    return jax.vmap(jax.vmap(head))(q, k, v, g, beta)


def inputs(b, hk, hv, s, dk, dv, seed=0, decay=2.0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, hk, s, dk))
    k = jax.random.normal(ks[1], (b, hk, s, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, hv, s, dv))
    g = -jax.random.uniform(ks[3], (b, hv, s)) * decay
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, hv, s)))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)


def _rel(got, want):
    got, want = onp.asarray(got, "float64"), onp.asarray(want, "float64")
    return onp.linalg.norm(got - want) / max(onp.linalg.norm(want), 1e-30)


def _value_and_grads(fn, args, weight):
    return jax.value_and_grad(
        lambda *a: (fn(*a).astype(jnp.float32) * weight).sum(),
        (0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("s,chunk", [(128, 64), (100, 64), (96, 16)],
                         ids=["whole_chunks", "ragged", "chunk_16"])
def test_chunked_twin_matches_the_recurrence(s, chunk):
    """Output and the gradients of q, k, v, g and beta, at a sequence
    that is a multiple of the chunk and at one that is not."""
    args = inputs(2, 2, 4, s, 16, 24)
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, 4, s, 24))
    want_o = recurrence(*args)
    got_o = gated_delta_rule(*args, chunk=chunk, use_pallas=False)
    assert got_o.shape == want_o.shape
    assert _rel(got_o, want_o) < TOL
    _, want = _value_and_grads(recurrence, args, weight)
    _, got = _value_and_grads(
        lambda *a: gated_delta_rule(*a, chunk=chunk, use_pallas=False),
        args, weight)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert _rel(a, b) < TOL, name


@pytest.mark.parametrize("s,hk", [(320, 1), (192, 2)],
                         ids=["two_steps_ragged", "one_step"])
def test_kernels_interpreted_match_the_twin(s, hk):
    """``gdn_fwd`` and ``gdn_bwd`` under interpret=True against the
    ``lax.scan`` walk: the same algebra, so to rounding; 320 positions
    are two grid steps of 256 with the tail padded."""
    args = inputs(1, hk, 2, s, 128, 128, seed=3)
    weight = jax.random.normal(jax.random.PRNGKey(5), (1, 2, s, 128))
    before = kernels.counters()
    got_o, got = _value_and_grads(
        lambda *a: gated_delta_rule(*a, use_pallas=True), args, weight)
    after = kernels.counters()
    want_o, want = _value_and_grads(
        lambda *a: gated_delta_rule(*a, use_pallas=False), args, weight)
    assert abs(float(got_o) - float(want_o)) < 1e-4 * abs(float(want_o))
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert _rel(a, b) < 1e-5, name
    # one trace of the kernels, forward and backward: the chunks a head
    # walks, padded to whole steps
    chunks = -(-s // gd.step_rows(s, 64)) * gd.step_rows(s, 64) // 64
    assert after["gdn_pallas"] == before.get("gdn_pallas", 0) + 1
    assert after["gdn_chunks"] == before.get("gdn_chunks", 0) + 2 * chunks
    assert after.get("gdn_plain", 0) == before.get("gdn_plain", 0)


def test_kernels_match_the_recurrence_in_bfloat16():
    """bfloat16 operands, float32 accumulation and decays: the kernels
    stay within bfloat16's rounding of the float32 recurrence."""
    args = inputs(1, 1, 2, 256, 128, 128, seed=4, dtype=jnp.bfloat16)
    f32 = tuple(a.astype(jnp.float32) for a in args)
    got = gated_delta_rule(*args, use_pallas=True)
    assert got.dtype == jnp.bfloat16
    assert _rel(got.astype(jnp.float32), recurrence(*f32)) < 2e-2


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["twin", "kernels"])
def test_strong_decay_and_beta_at_its_ends(use_pallas):
    """g near -20 a position (a chunk's sum near -1,300: every decay but
    the diagonal's underflows to 0) and beta exactly 0 and 1: no inf, no
    nan, forward or backward, and the recurrence's numbers."""
    q, k, v, g, beta = inputs(1, 1, 2, 128, 128, 128, seed=6)
    g = jnp.full_like(g, -20.0).at[:, 1, ::3].set(0.0)
    beta = jnp.where(jnp.arange(128) % 2 == 0, 0.0, 1.0) \
        * jnp.ones_like(beta)
    args = (q, k, v, g, beta)
    weight = jnp.ones((1, 2, 128, 128))
    o, grads = _value_and_grads(
        lambda *a: gated_delta_rule(*a, use_pallas=use_pallas), args, weight)
    want_o, want = _value_and_grads(recurrence, args, weight)
    assert onp.isfinite(float(o))
    for name, a, b in zip("q k v g beta".split(), grads, want):
        assert onp.isfinite(onp.asarray(a)).all(), name
        assert _rel(a, b) < 1e-4, name
    assert abs(float(o) - float(want_o)) <= 1e-4 * abs(float(want_o))


@pytest.mark.parametrize("exact", [True, False])
def test_unit_lower_inverse_and_its_derivative(exact):
    """``(I + a)^-1`` by the finite Neumann product, and its own
    derivative, two products of the result, against autodiff through
    ``jnp.linalg.inv``."""
    a = 0.3 * jnp.tril(
        jax.random.normal(jax.random.PRNGKey(1), (2, 3, 64, 64)), -1)
    weight = jax.random.normal(jax.random.PRNGKey(2), a.shape)
    eye = jnp.eye(64)
    inv = gd._unit_lower_inverse(a, exact)
    assert float(jnp.abs(inv @ (eye + a) - eye).max()) < 1e-4
    want = jax.grad(lambda a: (jnp.linalg.inv(eye + a) * weight).sum())(a)
    got = jax.grad(lambda a: (gd._unit_lower_inverse(a, exact)
                              * weight).sum())(a)
    assert _rel(got, want) < 1e-4


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_preparation_kernels_match_jax_numpy(dtype, tol):
    """``gdn_prep_fwd`` against ``_prepare`` output by output, ``T`` kept
    and handed back in giving the same, and ``gdn_prep_bwd`` (the VJP
    written by hand) against autodiff through ``_prepare``, for one
    cotangent of each of the six outputs at once."""
    q, k, v, g, beta = inputs(1, 1, 2, 256, 128, 128, seed=8, dtype=dtype)
    want = gd._prepare(q, k, v, g, beta, 64)
    got, t = gd._prepare_forward(q, k, v, g, beta, 64, True)
    again, same = gd._prepare_forward(q, k, v, g, beta, 64, True, t)
    assert same is t and t.shape == (1, 2, 4, 64, 64)
    names = "W U q~ k~ P e".split()
    for name, a, b, c in zip(names, got, want, again):
        if name == "e":     # a chunk's scalar along the lanes
            assert a.shape == (1, 2, 4, 1, 128)
            a, c = a[..., 0, 0], c[..., 0, 0]
        assert a.dtype == b.dtype and _rel(a, b) < tol, name
        assert _rel(c, b) < tol, name
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    cots = [jax.random.normal(kk, w.shape).astype(w.dtype)
            for kk, w in zip(ks, want)]
    _, pull = jax.vjp(lambda *a: gd._prepare(*a, 64), q, k, v, g, beta)
    want_grads = pull(tuple(cots))
    de = jnp.zeros((1, 2, 4, 1, 128)).at[..., 0, 3].set(cots[5])
    got_grads = gd._prepare_backward(q, k, v, g, beta, t,
                                     cots[:5] + [de], 64, True)
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        assert a.shape == b.shape, name
        assert _rel(a, b) < (3 * tol if dtype == jnp.bfloat16 else 1e-4), name


def test_gate_refuses_what_the_kernels_cannot_hold():
    assert gd.eligible(128, 128, 64, 2) and gd.eligible(128, 256, 64, 4)
    assert not gd.eligible(16, 16, 64, 4)       # off the 128 lanes
    assert not gd.eligible(128, 128, 8, 2)      # half a bfloat16 tile
    assert not gd.eligible(2048, 2048, 64, 4)   # blocks past VMEM
    args = inputs(1, 1, 1, 64, 16, 16)
    before = kernels.counters()
    gated_delta_rule(*args)                     # the twin, counted
    after = kernels.counters()
    assert after["gdn_plain"] == before.get("gdn_plain", 0) + 1
    with pytest.raises(ValueError, match="cannot take"):
        gated_delta_rule(*args, use_pallas=True)
    with pytest.raises(ValueError):
        gated_delta_rule(args[0], args[1], args[2], args[3][:, :, :8],
                         args[4])
