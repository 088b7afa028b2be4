"""``pick`` takes its element by a mask and a sum, not by a gather: the
value and the gradient are ``take_along_axis``'s to the bit, nothing
unpicked reaches either, and neither trace holds a gather or a scatter
(PERF.md section 6, PR 37: at one sequence a batch XLA:TPU keeps the
gather's scatter-add, into a flat array the size of the logits)."""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import gluon, kernels, nd
from mxnet_tpu.ndarray.ops_index import pick
from mxnet_tpu.ndarray.ops_nn import softmax_cross_entropy


def _case(axis, dtype, index_dtype, seed=0):
    rng = onp.random.RandomState(seed)
    data = jnp.asarray(rng.randn(3, 5, 7).astype("f")).astype(dtype)
    shape = list(data.shape)
    n = shape.pop(axis)
    index = jnp.asarray(rng.randint(0, n, shape)).astype(index_dtype)
    return data, index


def _gathered(data, index, axis, keepdims):
    out = jnp.take_along_axis(
        data, jnp.expand_dims(index.astype(jnp.int32), axis), axis=axis)
    return out if keepdims else jnp.squeeze(out, axis)


def _bits(a):
    return onp.asarray(a).view(f"u{a.dtype.itemsize}")


@pytest.mark.parametrize("index_dtype", ["int32", "float32"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("keepdims", [False, True])
@pytest.mark.parametrize("axis", [-1, 0, 1])
def test_value_and_gradient_are_the_gathers_bit_for_bit(axis, keepdims, dtype,
                                                        index_dtype):
    data, index = _case(axis, dtype, index_dtype)
    want = _gathered(data, index, axis, keepdims)
    got = pick(data, index, axis=axis, keepdims=keepdims)
    assert got.dtype == want.dtype and got.shape == want.shape
    onp.testing.assert_array_equal(_bits(got), _bits(want))
    # a cotangent that differs place by place, in the data's dtype
    w = jnp.asarray(onp.random.RandomState(1).randn(*want.shape)
                    .astype("f")).astype(dtype)

    def through(f):
        return jax.grad(lambda d: jnp.sum(
            (f(d) * w).astype(jnp.float32)))(data)

    g_want = through(lambda d: _gathered(d, index, axis, keepdims))
    g_got = through(lambda d: pick(d, index, axis=axis, keepdims=keepdims))
    assert g_got.dtype == data.dtype
    onp.testing.assert_array_equal(_bits(g_got), _bits(g_want))


@pytest.mark.parametrize("mode", ["clip", "wrap"])
@pytest.mark.parametrize("axis", [-1, 0])
def test_mode_on_indices_out_of_range(axis, mode):
    """MXNet's ``pick``: ``clip`` (the default) holds an index in
    ``[0, n - 1]``, ``wrap`` takes it modulo ``n``."""
    x = onp.random.RandomState(2).randn(4, 6).astype("f")
    n, rows = x.shape[axis], x.shape[1 + axis]      # (6, 4) or (4, 6)
    index = onp.array([-7, -1, 0, n - 1, n, 3 * n + 2][:rows])
    held = index % n if mode == "wrap" else onp.clip(index, 0, n - 1)
    want = onp.take_along_axis(x, onp.expand_dims(held, axis), axis)
    got = nd.pick(nd.array(x), nd.array(index.astype("f")), axis=axis,
                  keepdims=True, mode=mode).asnumpy()
    onp.testing.assert_array_equal(got, want)
    if mode == "clip":      # the default
        onp.testing.assert_array_equal(
            nd.pick(nd.array(x), nd.array(index.astype("f")), axis=axis,
                    keepdims=True).asnumpy(), want)


@pytest.mark.parametrize("bad", [-onp.inf, onp.inf, onp.nan],
                         ids=["-inf", "inf", "nan"])
def test_an_unpicked_inf_or_nan_reaches_neither_value_nor_gradient(bad):
    """A select, never a multiply: ``0 * inf`` would be NaN."""
    x = onp.random.RandomState(3).randn(4, 6).astype("f")
    index = jnp.asarray([1, 0, 5, 2])
    x[onp.arange(4), [0, 3, 4, 5]] = bad
    data = jnp.asarray(x)
    value, grad = jax.value_and_grad(
        lambda d: jnp.sum(pick(d, index, axis=-1) * 3.0))(data)
    assert onp.isfinite(value)
    onp.testing.assert_array_equal(
        onp.asarray(grad), 3.0 * onp.eye(6, dtype="f")[onp.asarray(index)])
    onp.testing.assert_array_equal(
        onp.asarray(pick(data, index, axis=-1)),
        x[onp.arange(4), onp.asarray(index)])


def _primitives(jaxpr, seen=None):
    seen = set() if seen is None else seen
    for eqn in jaxpr.eqns:
        seen.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, seen)
    return seen


@pytest.mark.parametrize("what", ["value", "gradient"])
def test_trace_holds_no_gather_and_no_scatter(what):
    data, index = _case(-1, "float32", "int32")

    def value(d):
        return jnp.sum(pick(jax.nn.log_softmax(d, axis=-1), index, axis=-1))

    fn = value if what == "value" else jax.grad(value)
    names = _primitives(jax.make_jaxpr(fn)(data).jaxpr)
    assert "select_n" in names
    assert not [n for n in names if "gather" in n or "scatter" in n], names


@pytest.mark.parametrize("from_logits", [False, True])
def test_sparse_cross_entropy_is_the_dense_labels_form(from_logits):
    rng = onp.random.RandomState(4)
    pred = rng.randn(6, 9).astype("f")
    if from_logits:
        pred = onp.asarray(jax.nn.log_softmax(jnp.asarray(pred), axis=-1))
    label = rng.randint(0, 9, (6,))
    sparse = gluon.loss.SoftmaxCrossEntropyLoss(from_logits=from_logits)
    dense = gluon.loss.SoftmaxCrossEntropyLoss(sparse_label=False,
                                               from_logits=from_logits)
    one_hot = onp.eye(9, dtype="f")[label]
    onp.testing.assert_array_equal(
        sparse(nd.array(pred), nd.array(label.astype("f"))).asnumpy(),
        dense(nd.array(pred), nd.array(one_hot)).asnumpy())


def test_softmax_cross_entropy_op_goes_through_pick():
    """One sparse cross-entropy lowering in the framework, not two."""
    rng = onp.random.RandomState(5)
    x = jnp.asarray(rng.randn(5, 8).astype("f"))
    label = jnp.asarray(rng.randint(0, 8, (5,)).astype("f"))
    before = kernels.counters().get("pick_masked", 0)
    names = _primitives(jax.make_jaxpr(jax.grad(
        lambda d: softmax_cross_entropy(d, label)))(x).jaxpr)
    assert kernels.counters()["pick_masked"] == before + 1
    assert not [n for n in names if "gather" in n or "scatter" in n], names
    want = -jnp.sum(jnp.take_along_axis(
        jax.nn.log_softmax(x, axis=-1), label.astype(jnp.int32)[:, None], 1))
    onp.testing.assert_array_equal(
        onp.asarray(softmax_cross_entropy(x, label)), onp.asarray(want))


def test_counter_counts_one_a_traced_pick():
    data, index = _case(-1, "float32", "int32")
    before = kernels.counters()
    step = jax.jit(jax.grad(  # graft-lint: allow(jit-nocache)
        lambda d: jnp.sum(pick(d, index, axis=-1))))
    step(data)
    step(data)                  # the compiled step traces nothing again
    assert kernels.counters()["pick_masked"] \
        == before.get("pick_masked", 0) + 1
