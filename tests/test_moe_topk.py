"""Drop-free top-k routing over the experts a layer holds
(``parallel/moe.py`` ``top_k_router`` / ``expert_ffn`` /
``expert_parallel_ffn`` and ``gluon.contrib.nn.TopKMoE``) against the
dense sum it stands for: no assignment lost whatever the buffer holds,
renormalised weights, the held share, the counts the compiled step
publishes, and the same layer over an 'ep' mesh axis. Float32 on the
CPU's 8 virtual devices.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import autograd, nd, parallel
from mxnet_tpu.gluon.contrib.nn import TopKMoE
from mxnet_tpu.parallel.moe import (expert_ffn, expert_parallel_ffn,
                                    top_k_router)

T, D, F, E, K = 96, 16, 24, 8, 3


def _weights(seed=0, experts=E):
    rs = onp.random.RandomState(seed)
    x = jnp.asarray(rs.randn(T, D).astype("f"))
    gate_w = jnp.asarray(rs.randn(D, experts).astype("f"))
    w13 = jnp.asarray(rs.randn(experts, D, 2 * F).astype("f") * 0.3)
    w2 = jnp.asarray(rs.randn(experts, F, D).astype("f") * 0.3)
    return x, gate_w, w13, w2


def _dense(x, idx, gates, w13, w2, first=0):
    """sum over a token's choices that land on the held experts of
    gate * (silu(x Wgate) * (x Wup)) Wdown, every expert on every row."""
    h = jnp.einsum("td,edf->tef", x, w13)
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(h[..., :F]) * h[..., F:], w2)
    held = first + jnp.arange(w13.shape[0])
    weight = jnp.sum(jnp.where(idx[:, :, None] == held[None, None],
                               gates[:, :, None], 0.0), axis=1)
    return jnp.einsum("te,ted->td", weight, y)


@pytest.mark.parametrize("norm", [True, False])
def test_router_takes_the_k_largest_and_renormalises(norm):
    x, gate_w, _, _ = _weights()
    idx, gates = top_k_router(x, gate_w, K, norm)
    probs = onp.asarray(jax.nn.softmax(x @ gate_w, axis=-1))
    want = onp.argsort(-probs, axis=-1)[:, :K]
    assert (onp.sort(onp.asarray(idx), -1) == onp.sort(want, -1)).all()
    assert idx.dtype == jnp.int32 and gates.dtype == jnp.float32
    picked = onp.take_along_axis(probs, onp.asarray(idx), -1)
    if norm:
        assert onp.allclose(onp.asarray(gates).sum(-1), 1.0, atol=1e-6)
        picked = picked / picked.sum(-1, keepdims=True)
    assert onp.allclose(onp.asarray(gates), picked, atol=1e-6)


@pytest.mark.parametrize("capacity_factor", [1.25, 0.3, 0.01])
def test_no_assignment_is_lost_whatever_the_buffer_holds(capacity_factor):
    """Every expert held: the layer is the dense sum over the top-k. A
    buffer smaller than the assignments takes further passes (0.01: one
    tile of rows a pass), and still drops none."""
    x, gate_w, w13, w2 = _weights(1)
    idx, gates = top_k_router(x, gate_w, K)
    y, rows = expert_ffn(x, idx, gates, w13, w2,
                         capacity_factor=capacity_factor)
    want = _dense(x, idx, gates, w13, w2)
    assert float(jnp.abs(y - want).max()) < 1e-5 * float(jnp.abs(want).max())
    assert int(rows.sum()) == T * K
    assert (onp.asarray(rows) == onp.bincount(
        onp.asarray(idx).reshape(-1), minlength=E)).all()


def test_one_expert_taking_every_token_overflows_into_more_passes():
    """The worst imbalance: every token's first choice is expert 5. With
    2 of 8 experts held the buffer expects a quarter of the assignments
    and gets far more; none is dropped."""
    x, gate_w, w13, w2 = _weights(2)
    idx, gates = top_k_router(x, gate_w, K)
    idx = idx.at[:, 0].set(5)       # whatever the logits said
    y, rows = expert_ffn(x, idx, gates, w13[4:6], w2[4:6], (4, 2), E)
    want = _dense(x, idx, gates, w13[4:6], w2[4:6], first=4)
    assert int(rows[1]) >= T        # and those whose later choice it was
    assert float(jnp.abs(y - want).max()) < 1e-5 * float(jnp.abs(want).max())


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_rows_are_summed_by_token_across_tile_boundaries(seed):
    """The sum that stands in for a scatter: up to 8 rows a token in a
    buffer of four tiles, padding rows among them, against numpy's
    ``add.at``; tokens whose rows straddle a tile boundary among them."""
    from mxnet_tpu.parallel import moe

    rs = onp.random.RandomState(seed)
    t, cap, d, most = 300, 4 * moe._RUN_TILE, 16, 8
    counts = rs.randint(0, most + 1, t)
    tok = onp.repeat(onp.arange(t), counts)[:cap - 37]
    tok = tok[rs.permutation(tok.size)]
    valid = onp.arange(cap) < tok.size
    tok = onp.concatenate([tok, onp.zeros(cap - tok.size, "int64")])
    vals = rs.randn(cap, d).astype("f")
    want = onp.zeros((t, d), "f")
    onp.add.at(want, tok[valid], vals[valid])
    runs = moe._token_runs(jnp.asarray(tok, jnp.int32), jnp.asarray(valid), t)
    got = moe._sum_by_token(jnp.asarray(vals), runs, most)
    assert onp.abs(onp.asarray(got) - want).max() < 1e-5
    # runs do cross the tile boundaries in this draw
    stok = onp.asarray(runs[1]).reshape(4, -1)
    assert (stok[1:, 0] == stok[:-1, -1]).any()


@pytest.mark.parametrize("capacity_factor", [1.25, 0.3])
def test_gradients_are_the_dense_sums(capacity_factor):
    """x, the gates and both expert weights, through the gathers that
    stand in for autodiff's scatters and through the overflow passes'
    recomputation."""
    x, gate_w, w13, w2 = _weights(3)
    idx, gates = top_k_router(x, gate_w, K)
    cot = jnp.asarray(onp.random.RandomState(4).randn(T, D).astype("f"))

    def layer(x, gates, w13, w2):
        return jnp.sum(expert_ffn(x, idx, gates, w13[2:6], w2[2:6], (2, 4),
                                  E, capacity_factor)[0] * cot)

    def dense(x, gates, w13, w2):
        return jnp.sum(_dense(x, idx, gates, w13[2:6], w2[2:6], 2) * cot)

    got = jax.grad(layer, (0, 1, 2, 3))(x, gates, w13, w2)
    want = jax.grad(dense, (0, 1, 2, 3))(x, gates, w13, w2)
    for g, w in zip(got, want):
        assert float(jnp.abs(g - w).max()) < 2e-5 * float(jnp.abs(w).max())


def test_router_gradient_reaches_the_gate_weights():
    x, gate_w, w13, w2 = _weights(5)

    def layer(gate_w):
        idx, gates = top_k_router(x, gate_w, K)
        return jnp.sum(expert_ffn(x, idx, gates, w13, w2)[0] ** 2)

    def dense(gate_w):
        probs = jax.nn.softmax(x @ gate_w, axis=-1)
        gates, idx = jax.lax.top_k(probs, K)
        gates = gates / gates.sum(-1, keepdims=True)
        return jnp.sum(_dense(x, idx, gates, w13, w2) ** 2)

    got, want = jax.grad(layer)(gate_w), jax.grad(dense)(gate_w)
    assert float(jnp.abs(want).max()) > 0
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())


@pytest.mark.parametrize("first,count", [(0, 2), (2, 4), (6, 2), (0, 8)])
def test_a_held_share_is_its_part_of_the_sum(first, count):
    x, gate_w, w13, w2 = _weights(6)
    idx, gates = top_k_router(x, gate_w, K)
    held = slice(first, first + count)
    y, rows = expert_ffn(x, idx, gates, w13[held], w2[held], (first, count),
                         E)
    want = _dense(x, idx, gates, w13[held], w2[held], first)
    assert float(jnp.abs(y - want).max()) < 1e-5 * float(jnp.abs(want).max())
    counts = onp.bincount(onp.asarray(idx).reshape(-1), minlength=E)
    assert (onp.asarray(rows) == counts[held]).all()


def test_expert_parallel_layer_is_the_one_device_layer():
    """Every expert held and an 'ep' axis: each device computes its
    slice's part for the axis's tokens and the parts are summed back."""
    x, gate_w, w13, w2 = _weights(7)
    idx, gates = top_k_router(x, gate_w, K)
    want, want_rows = expert_ffn(x, idx, gates, w13, w2)
    mesh = parallel.make_mesh({"ep": 4}, devices=jax.devices()[:4])
    y, rows = expert_parallel_ffn(x, gate_w, w13, w2, K, mesh)
    assert float(jnp.abs(y - want).max()) < 1e-5 * float(jnp.abs(want).max())
    assert (onp.asarray(rows) == onp.asarray(want_rows)).all()


def test_gluon_block_holds_its_share_and_counts_its_rows():
    x, gate_w, w13, w2 = _weights(8)
    blk = TopKMoE(E, F, K, experts_held=(2, 4))
    blk.initialize()
    xin = nd.array(onp.asarray(x).reshape(4, T // 4, D))
    blk(xin)
    for p, v in zip(blk.collect_params().values(),
                    (gate_w, w13[2:6], w2[2:6])):
        p.set_data(nd.array(onp.asarray(v)))
    idx, gates = top_k_router(x, gate_w, K)
    want = _dense(x, idx, gates, w13[2:6], w2[2:6], 2)
    out = blk(xin)              # inference: the rows stay as they were
    assert out.shape == xin.shape
    assert float(jnp.abs(out.data.reshape(T, D) - want).max()) \
        < 1e-5 * float(jnp.abs(want).max())
    assert float(blk.expert_rows.data().data.sum()) == 0
    with autograd.record():
        blk(xin)
    counts = onp.bincount(onp.asarray(idx).reshape(-1), minlength=E)
    assert (onp.asarray(blk.expert_rows.data().data) == counts[2:6]).all()
    assert blk.expert_rows.grad_req == "null"


@pytest.mark.parametrize("held", [(3, 6), (-1, 2), (0, 0)])
def test_gluon_block_refuses_a_share_outside_the_experts(held):
    with pytest.raises(ValueError):
        TopKMoE(E, F, K, experts_held=held)


@pytest.mark.parametrize("telemetry", ["1", "0"])
def test_trainer_publishes_the_counts_the_step_made(monkeypatch, telemetry):
    """``moe/steps``, ``moe/assignments_held`` and ``moe/max_expert_rows``
    come from what the compiled step itself counted: with the learning
    rate at 0 every step routes alike, so they are the reference count
    of one forward times the steps published. With ``MXNET_TELEMETRY=0``
    the step reads nothing back and the counters stand still."""
    from mxnet_tpu.gluon.loss import L2Loss
    from mxnet_tpu.telemetry import metrics

    monkeypatch.setenv("MXNET_TELEMETRY", telemetry)
    x, gate_w, w13, w2 = _weights(9)
    blk = TopKMoE(E, F, K, experts_held=(2, 4))
    blk.initialize()
    xin = onp.asarray(x).reshape(4, T // 4, D)
    blk(nd.array(xin))
    blk.gate_weight.set_data(nd.array(onp.asarray(gate_w)))
    trainer = parallel.SPMDTrainer(
        blk, L2Loss(), optimizer="sgd",
        optimizer_params={"learning_rate": 0.0},
        mesh=parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1]))

    def snap():
        if "moe" in metrics.snapshot():
            return metrics.family_snapshot("moe")
        return {"steps": 0, "assignments_held": 0}

    before = snap()
    steps = 4
    for _ in range(steps):
        # a step publishes what earlier steps have finished counting
        float(trainer.step(nd.array(xin),
                           nd.array(onp.zeros_like(xin))).asscalar())
    after = snap()
    idx, _ = top_k_router(x, gate_w, K)
    counts = onp.bincount(onp.asarray(idx).reshape(-1), minlength=E)[2:6]
    published = after["steps"] - before["steps"]
    assert published == (steps - 1 if telemetry == "1" else 0)
    assert after["assignments_held"] - before["assignments_held"] \
        == published * counts.sum()
    if published:
        assert after["max_expert_rows"] == counts.max()
    assert not trainer._stats_pending or telemetry == "1"


# ---------------------------------------------------------------------------
# ReGLU experts routed from another input than their rows (PR 34)

def _dense_act(x, idx, gates, w13, w2, act, first=0):
    """``_dense`` with the gate's activation ``act``."""
    h = jnp.einsum("td,edf->tef", x, w13)
    y = jnp.einsum("tef,efd->ted", act(h[..., :F]) * h[..., F:], w2)
    held = first + jnp.arange(w13.shape[0])
    weight = jnp.sum(jnp.where(idx[:, :, None] == held[None, None],
                               gates[:, :, None], 0.0), axis=1)
    return jnp.einsum("te,ted->td", weight, y)


@pytest.mark.parametrize("activation,act,capacity_factor", [
    ("relu", jax.nn.relu, 1.5), ("relu", jax.nn.relu, 0.3),
    ("silu", jax.nn.silu, 1.5)])
def test_activation_and_a_separate_router_input(activation, act,
                                                capacity_factor):
    """idx and gates from the router's own input ``r``, the rows from
    ``x``: forward and every gradient (x, r through the gates, the gate
    weights, both expert weights) against a dense loop, the overflow
    passes' recomputation included."""
    x, gate_w, w13, w2 = _weights(11)
    r = jnp.asarray(onp.random.RandomState(12).randn(T, D).astype("f"))
    cot = jnp.asarray(onp.random.RandomState(13).randn(T, D).astype("f"))

    def layer(x, r, gate_w, w13, w2):
        idx, gates = top_k_router(r, gate_w, K)
        return jnp.sum(expert_ffn(x, idx, gates, w13[2:6], w2[2:6], (2, 4),
                                  E, capacity_factor,
                                  activation=activation)[0] * cot)

    def dense(x, r, gate_w, w13, w2):
        probs = jax.nn.softmax(r @ gate_w, axis=-1)
        gates, idx = jax.lax.top_k(probs, K)
        gates = gates / gates.sum(-1, keepdims=True)
        return jnp.sum(_dense_act(x, idx, gates, w13[2:6], w2[2:6], act, 2)
                       * cot)

    args = (x, r, gate_w, w13, w2)
    assert abs(float(layer(*args)) - float(dense(*args))) \
        < 1e-5 * abs(float(dense(*args)))
    got = jax.grad(layer, range(5))(*args)
    want = jax.grad(dense, range(5))(*args)
    for g, w in zip(got, want):
        assert float(jnp.abs(w).max()) > 0
        assert float(jnp.abs(g - w).max()) < 2e-5 * float(jnp.abs(w).max())
    # routed by x itself the layer is not this one
    idx_x, gates_x = top_k_router(x, gate_w, K)
    idx_r, _ = top_k_router(r, gate_w, K)
    assert (onp.asarray(idx_x) != onp.asarray(idx_r)).any()


def test_unknown_activation_is_refused():
    x, gate_w, w13, w2 = _weights(14)
    idx, gates = top_k_router(x, gate_w, K)
    with pytest.raises(ValueError, match="activation"):
        expert_ffn(x, idx, gates, w13, w2, activation="gelu")
    with pytest.raises(ValueError, match="activation"):
        TopKMoE(E, F, K, activation="gelu")


def test_expert_parallel_layer_routes_by_the_router_input():
    x, gate_w, w13, w2 = _weights(15)
    r = jnp.asarray(onp.random.RandomState(16).randn(T, D).astype("f"))
    idx, gates = top_k_router(r, gate_w, K)
    want, want_rows = expert_ffn(x, idx, gates, w13, w2, activation="relu")
    mesh = parallel.make_mesh({"ep": 4}, devices=jax.devices()[:4])
    y, rows = expert_parallel_ffn(x, gate_w, w13, w2, K, mesh,
                                  activation="relu", router_input=r)
    assert float(jnp.abs(y - want).max()) < 1e-5 * float(jnp.abs(want).max())
    assert (onp.asarray(rows) == onp.asarray(want_rows)).all()


def test_gluon_block_routes_by_its_second_input_and_differentiates_it():
    """``TopKMoE(activation="relu")(x, router_input)``: the output is the
    dense ReGLU sum under the router's choices on ``router_input``, and
    the tape carries the router's gradient back to it."""
    x, gate_w, w13, w2 = _weights(17)
    r = onp.random.RandomState(18).randn(T, D).astype("f")
    blk = TopKMoE(E, F, K, experts_held=(2, 4), activation="relu")
    blk.initialize()
    xin = nd.array(onp.asarray(x).reshape(4, T // 4, D))
    rin = nd.array(r.reshape(4, T // 4, D))
    blk(xin, rin)
    for p, v in zip(blk.collect_params().values(),
                    (gate_w, w13[2:6], w2[2:6])):
        p.set_data(nd.array(onp.asarray(v)))
    idx, gates = top_k_router(jnp.asarray(r), gate_w, K)
    want = _dense_act(x, idx, gates, w13[2:6], w2[2:6], jax.nn.relu, 2)
    rin.attach_grad()
    xin.attach_grad()
    with autograd.record():
        out = blk(xin, rin)
        loss = (out * out).sum()
    loss.backward()
    assert float(jnp.abs(out.data.reshape(T, D) - want).max()) \
        < 1e-5 * float(jnp.abs(want).max())

    def dense(x, r):
        probs = jax.nn.softmax(r @ gate_w, axis=-1)
        gates, idx = jax.lax.top_k(probs, K)
        gates = gates / gates.sum(-1, keepdims=True)
        return jnp.sum(_dense_act(x, idx, gates, w13[2:6], w2[2:6],
                                  jax.nn.relu, 2) ** 2)

    want_dx, want_dr = jax.grad(dense, (0, 1))(x, jnp.asarray(r))
    for got, w in ((xin.grad, want_dx), (rin.grad, want_dr)):
        assert float(jnp.abs(w).max()) > 0
        assert float(jnp.abs(got.data.reshape(T, D) - w).max()) \
            < 2e-5 * float(jnp.abs(w).max())
    counts = onp.bincount(onp.asarray(idx).reshape(-1), minlength=E)
    assert (onp.asarray(blk.expert_rows.data().data) == counts[2:6]).all()
