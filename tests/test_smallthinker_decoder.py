"""``models.MoEDecoderLM`` under the layer pattern of SmallThinker (a
global layer without positions, then window layers with RoPE; no q/k
norm; ReGLU experts routed from the layer's normed input) against the
plain reference the benchmark keeps
(``benchmarks/reference/smallthinker-21b-a3b.py``), in float32 on the
CPU at a small size: logits, loss and every gradient leaf; the rows each
held expert got; eight shares of 8 experts adding up to the reference's
uncut layer of 64; and what the pattern's arguments refuse. The model is
built, fed and bound to the reference's leaves by the benchmark's own
builder (``benchmarks/models/smallthinker-21b-a3b.py``), so what is
tested here is what a run of the cell compares.
"""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import autograd, models, nd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")

CFG = dict(hidden_size=64, head_dim=16, num_attention_heads=6,
           num_key_value_heads=2, moe_ffn_hidden_size=32,
           num_hidden_layers=4, moe_num_primary_experts=8, router_experts=8,
           experts_first=0, moe_num_active_primary_experts=3,
           norm_topk_prob=True, vocab_size=96, rms_norm_eps=1e-6,
           rope_theta=1.5e6, init_std=0.02, sliding_window_size=16,
           rope_layout=[0, 1, 1, 1] * 13,
           sliding_window_layout=[0, 1, 1, 1] * 13)
TRAFFIC = dict(seq=48)
TOL = 5e-6      # float32 on both sides: sums in another order


def _bench_module(kind):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    name = "tests_smallthinker_" + kind
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, kind, "smallthinker-21b-a3b.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _bench_module("reference")


@pytest.fixture(scope="module")
def model():
    return _bench_module("models")


def _rel(got, want):
    got, want = onp.asarray(got, "float64"), onp.asarray(want, "float64")
    return onp.abs(got - want).max() / max(onp.abs(want).max(), 1e-30)


@pytest.fixture(scope="module", params=[(2, 4)],
                ids=["experts_2_to_5_held"])
def both_sides(request, model, ref):
    """One batch through the program (gluon autograd) and through the
    reference (jax.grad), from the same seeded weights."""
    first, held = request.param
    cfg = dict(CFG, experts_first=first, moe_num_primary_experts=held)
    x, y = model.make_batch(cfg, TRAFFIC, 2, onp.random.default_rng(7))
    params, aux = ref.init(cfg, jax.random.PRNGKey(3))
    # weights large enough that the router's choices are no near-ties
    params = {k: v * 5 if v.ndim > 1 else v for k, v in params.items()}
    net = model.build_net(cfg)
    net.initialize()
    net(nd.array(x, dtype="int32"))
    leaves = list(ref.leaf_shapes(cfg))
    named = list(net.collect_params().items())
    assert len(named) == len(leaves)
    every = dict(params, **aux)
    for (_, p), leaf in zip(named, leaves):
        p.set_data(nd.array(onp.asarray(model.to_program(leaf, every[leaf]))))
    by_leaf = dict(zip(leaves, (p for _, p in named)))
    loss_block = model.loss_block(cfg)
    with autograd.record():
        logits = net(nd.array(x, dtype="int32"))
        loss = loss_block(logits, nd.array(y)).mean()
    loss.backward()
    (want_loss, want_aux), want_grads = jax.value_and_grad(
        lambda p: ref.loss(cfg, p, aux, (x, y)), has_aux=True)(params)
    want_logits, _ = ref.forward(cfg, params, aux, jnp.asarray(x), True)
    return dict(cfg=cfg, model=model, by_leaf=by_leaf, logits=logits,
                loss=loss, want_logits=want_logits, want_loss=want_loss,
                want_grads=want_grads, want_aux=want_aux, net=net)


def test_the_net_is_the_pattern(both_sides):
    blocks = list(both_sides["net"].blocks._children.values())
    assert [(b.attn._kind, b.attn._size, b.attn._rope) for b in blocks] == \
        [("causal", None, False)] + [("window", 16, True)] * 3
    for b in blocks:
        assert not b.attn._qk_norm and not hasattr(b.attn, "q_norm")
        assert b._route_by_layer_input and b.moe._act == "relu"


def test_logits_and_loss_match_the_reference(both_sides):
    s = both_sides
    assert s["logits"].shape == (2, TRAFFIC["seq"], CFG["vocab_size"])
    assert _rel(s["logits"].data, s["want_logits"]) < TOL
    assert abs(float(s["loss"].asscalar()) - float(s["want_loss"])) \
        < TOL * float(s["want_loss"])


def test_every_gradient_leaf_matches_the_reference(both_sides, ref):
    s = both_sides
    trained = {k for k, (_, kind) in ref.leaf_shapes(s["cfg"]).items()
               if kind != "state"}
    assert trained == set(s["want_grads"]) and len(trained) == 4 * 7 + 3
    for leaf in sorted(trained):
        got = s["by_leaf"][leaf].grad().data
        want = s["model"].to_program(leaf, s["want_grads"][leaf])
        assert got.shape == want.shape, leaf
        assert float(jnp.abs(want).max()) > 0, leaf
        assert _rel(got, want) < TOL, leaf


def test_rows_per_held_expert_match_the_reference(both_sides):
    s = both_sides
    for i in range(CFG["num_hidden_layers"]):
        leaf = f"l{i}.moe.rows"
        got = onp.asarray(s["by_leaf"][leaf].data().data)
        want = onp.asarray(s["want_aux"][leaf])
        assert (got == want).all() and got.sum() > 0
        if s["cfg"]["moe_num_primary_experts"] == CFG["router_experts"]:
            assert got.sum() == 2 * TRAFFIC["seq"] * 3


def test_eight_shares_of_8_experts_add_up_to_the_uncut_layer(ref):
    """The deployment's cut: 64 routed experts, 6 a token, 8 a chip. The
    parts of the result that the eight shares' expert layers give
    (``TopKMoE`` told which 8 it holds, ReGLU, routed from another input
    than its rows) add up to what the reference gives for the whole
    layer of 64; each share counts its own rows of the one routing."""
    from mxnet_tpu.gluon.contrib.nn import TopKMoE

    cfg = dict(CFG, router_experts=64, moe_num_primary_experts=64,
               moe_num_active_primary_experts=6)
    e, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    rs = onp.random.RandomState(21)
    m, n = (rs.randn(2, 40, e).astype("f") for _ in range(2))
    router = rs.randn(e, 64).astype("f")
    w13 = rs.randn(64, e, 2 * f).astype("f") * 0.3
    w2 = rs.randn(64, f, e).astype("f") * 0.3
    prec = sys.modules[ref.__name__].Prec("float32")
    idx, gates = ref.route(jnp.asarray(n), jnp.asarray(router), cfg, prec)
    want = ref._experts(jnp.asarray(m), idx, gates, jnp.asarray(w13),
                        jnp.asarray(w2), 0, prec)
    counts = onp.bincount(onp.asarray(idx).reshape(-1), minlength=64)
    total = 0
    for first in range(0, 64, 8):
        blk = TopKMoE(64, f, 6, experts_held=(first, 8), activation="relu")
        blk.initialize()
        blk(nd.array(m), nd.array(n))
        for p, v in zip(blk.collect_params().values(),
                        (router, w13[first:first + 8], w2[first:first + 8])):
            p.set_data(nd.array(v))
        with autograd.pause(train_mode=True):
            part = blk(nd.array(m), nd.array(n)).data
        assert (onp.asarray(blk.expert_rows.data().data)
                == counts[first:first + 8]).all()
        assert _rel(part, want) > 1e-3      # no share alone is the layer
        total = total + part
    assert counts.sum() == 2 * 40 * 6
    assert _rel(total, want) < TOL


@pytest.mark.parametrize("bad,match", [
    (dict(attention=["causal"] * 3), "entries"),
    (dict(rope=[True, False]), "entries"),
    (dict(attention={"window": 0}), "attention"),
    (dict(attention={"stride": 4}), "attention"),
    (dict(attention=["causal", {"block_length": 4}, "causal", "causal"]),
     "block-diffusion"),
    (dict(router_input="attention"), "router_input"),
    (dict(activation="gelu"), "activation")])
def test_pattern_arguments_are_checked(bad, match):
    with pytest.raises(ValueError, match=match):
        models.MoEDecoderLM(96, 64, 4, 4, 2, 16, 8, 32, 2, **bad)


def test_a_window_as_long_as_the_sequence_is_causal_attention():
    """``{"window": w}`` with w >= S sees every earlier key: the layer
    lowers plain causal attention, no spec."""
    args = (96, 64, 2, 4, 2, 16, 8, 32, 2)
    x = nd.array(onp.random.RandomState(3).randint(0, 96, (2, 24)),
                 dtype="int32")
    outs = []
    for attention in ("causal", {"window": 24}):
        net = models.MoEDecoderLM(*args, attention=attention)
        net.initialize()
        net(x)
        if outs:
            for p, q in zip(net.collect_params().values(), first):
                p.set_data(q.data())
        else:
            first = list(net.collect_params().values())
        outs.append(net(x).data)
    assert _rel(outs[1], outs[0]) == 0
