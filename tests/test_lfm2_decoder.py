"""``models.MoEDecoderLM`` in LFM2-24B-A2B's layer pattern (two leading
dense conv layers, then [attention, conv, conv, conv] of expert layers,
the head tied to the embedding) against the plain reference the
benchmark keeps (``benchmarks/reference/lfm2-24b-a2b.py``), in float32
on the CPU at a small size; and the pieces the pattern brought:
``GatedShortConv`` with and without its kernel pair, the dense SwiGLU,
the sigmoid router with its selection bias and the bias's update (in a
training forward and in ``SPMDTrainer``'s compiled step), the shares of
a layer's experts adding up to the uncut layer, the tied head, and the
defaults building the program the four existing ``MoEDecoderLM``
configurations had."""
import functools
import hashlib
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import autograd, kernels, models, nd, parallel
from mxnet_tpu.gluon.contrib.nn import TopKMoE

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")

LAYERS = ["conv", "conv", "full_attention", "conv", "conv", "conv"]
CFG = dict(hidden_size=64, head_dim=16, num_attention_heads=4,
           num_key_value_heads=2, conv_L_cache=3, layer_types=LAYERS,
           intermediate_size=96, moe_intermediate_size=32,
           num_hidden_layers=6, num_dense_layers=2, num_experts=4,
           router_experts=16, experts_first=4, num_experts_per_tok=4,
           norm_topk_prob=True, routed_scaling_factor=1.0,
           use_expert_bias=True, expert_bias_rate=1e-3,
           tie_word_embeddings=True, vocab_size=96, norm_eps=1e-5,
           rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
           init_std=0.02)
TRAFFIC = dict(seq=40)
TOL = 2e-5      # float32 on both sides: sums in another order


def _bench_module(kind):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    name = "tests_lfm2_" + kind
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, kind, "lfm2-24b-a2b.py"))
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


def _weights(ref, cfg, seed, bias=None):
    """The reference's weights from a seed, large enough that the
    router's choices are no near-ties; ``bias`` sets every expert
    layer's selection bias."""
    params, aux = ref.init(cfg, jax.random.PRNGKey(seed))
    params = {k: v * 5 if v.ndim > 1 else v for k, v in params.items()}
    if bias is not None:
        aux = {k: jnp.asarray(bias) if k.endswith(".bias") else v
               for k, v in aux.items()}
    return params, aux


def _bind(model, ref, cfg, net, params, aux):
    leaves = list(ref.leaf_shapes(cfg))
    named = list(net.collect_params().items())
    assert len(named) == len(leaves)
    every = dict(params, **aux)
    for (_, p), leaf in zip(named, leaves):
        p.set_data(nd.array(onp.asarray(model.to_program(leaf, every[leaf]))))
    return dict(zip(leaves, (p for _, p in named)))


def _net(model, ref, cfg, params, aux, x):
    net = model.build_net(cfg)
    net.initialize()
    net(nd.array(x, dtype="int32"))
    return net, _bind(model, ref, cfg, net, params, aux)


# a bias that moves the choice, in steps of the rate 1e-3: the experts
# held here favoured by 0.3
BIAS = onp.where(onp.arange(16) // 4 == 1, 300, -100).astype("f")


@pytest.fixture(scope="module", params=["zero_bias", "nonzero_bias"])
def both_sides(request, model, ref):
    """One batch through the program (gluon autograd, a training forward)
    and through the reference (jax.grad), from the same seeded weights
    and selection bias."""
    bias = None if request.param == "zero_bias" else BIAS
    x, y = model.make_batch(CFG, TRAFFIC, 2, onp.random.default_rng(7))
    params, aux = _weights(ref, CFG, 3, bias)
    net, by_leaf = _net(model, ref, CFG, params, aux, x)
    loss_block = model.loss_block(CFG)
    with autograd.record():
        logits = net(nd.array(x, dtype="int32"))
        loss = loss_block(logits, nd.array(y)).mean()
    loss.backward()
    (want_loss, want_aux), want_grads = jax.value_and_grad(
        lambda p: ref.loss(CFG, p, aux, (x, y)), has_aux=True)(params)
    want_logits, _ = ref.forward(CFG, params, aux, jnp.asarray(x), True)
    return dict(by_leaf=by_leaf, model=model, logits=logits, loss=loss,
                want_logits=want_logits, want_loss=want_loss,
                want_grads=want_grads, want_aux=want_aux, aux=aux,
                net=net)


def test_logits_loss_and_state_match_the_reference(both_sides):
    """The logits, the loss, and after the training forward every expert
    layer's rows per held expert, load over the 16 routed experts and
    selection bias moved by one step of the rule."""
    s = both_sides
    assert s["logits"].shape == (2, TRAFFIC["seq"], CFG["vocab_size"])
    assert _rel(s["logits"].data, s["want_logits"]) < TOL
    assert abs(float(s["loss"].asscalar()) - float(s["want_loss"])) \
        < TOL * float(s["want_loss"])
    assert set(s["want_aux"]) == set(s["aux"])
    for leaf, value in s["want_aux"].items():
        got = onp.asarray(s["by_leaf"][leaf].data().asnumpy())
        if leaf.endswith(".bias"):
            onp.testing.assert_allclose(got, onp.asarray(value), rtol=1e-6)
        else:
            onp.testing.assert_array_equal(got, onp.asarray(value))


_LEAVES = ["embed.w", "lnf.gamma"] + [
    f"l{i}.{name}" for i in (0, 3) for name in (
        "ln1.gamma", "attn.conv.w", "attn.in.w", "attn.out.w",
        "ln2.gamma")] + ["l0.mlp.w13", "l0.mlp.w2"] + [
    "l3." + name for name in ("moe.router.w", "moe.w13", "moe.w2")] + [
    "l2." + name for name in (
        "ln1.gamma", "attn.q_norm", "attn.k_norm", "attn.qkv.w",
        "attn.out.w", "ln2.gamma", "moe.router.w", "moe.w13", "moe.w2")]


@pytest.mark.parametrize("leaf", _LEAVES)
def test_gradient_leaf_matches_the_reference(both_sides, leaf):
    s = both_sides
    got = s["by_leaf"][leaf].grad().data
    want = s["model"].to_program(leaf, s["want_grads"][leaf])
    assert _rel(got, want) < TOL


def test_every_leaf_is_one_of_the_tested_or_a_twin_layer(ref):
    """Layers 0 and 1 are one kind (dense conv), as are 3, 4 and 5
    (expert conv): 0 and 3 are compared leaf by leaf, as is the
    attention layer 2 and what lies outside the layers."""
    named = {k for k, (_, kind) in ref.leaf_shapes(CFG).items()
             if kind != "state" and not k.startswith(("l1.", "l4.", "l5."))}
    assert named == set(_LEAVES)
    assert "head.w" not in ref.leaf_shapes(CFG)


def test_the_bias_changes_the_chosen_experts(model, ref):
    """The held experts (4-7) get more rows under the bias that favours
    them than under none, in the program as in the reference, and the
    gates come from the scores alone: the layer's result is the
    reference's under both."""
    from refcommon import Prec

    cfg = dict(CFG, num_hidden_layers=3)
    rows = {}
    for name, bias in (("none", onp.zeros(16, "f")), ("held", BIAS)):
        params, aux = _weights(ref, cfg, 5, bias)
        m = jax.random.normal(jax.random.PRNGKey(6), (2, 40, 64))
        want, state = ref.moe_layer(m, params, aux, "l2", cfg,
                                    Prec("float32"))
        layer = _bound_moe(cfg, params, aux, 4, 4, m)
        with autograd.record():
            got = layer(nd.array(onp.asarray(m)))
        assert _rel(got.data, want) < TOL
        onp.testing.assert_array_equal(layer.expert_rows.data().asnumpy(),
                                       onp.asarray(state["l2.moe.rows"]))
        rows[name] = float(state["l2.moe.rows"].sum())
    assert rows["held"] > rows["none"]


def _bound_moe(cfg, params, aux, first, held, m, rate=1e-3):
    layer = TopKMoE(cfg["router_experts"], cfg["moe_intermediate_size"],
                    cfg["num_experts_per_tok"], experts_held=(first, held),
                    score="sigmoid", expert_bias=rate)
    layer.initialize()
    layer(nd.array(onp.asarray(m)))
    order = ["router.w", "w13", "w2", "rows", "bias", "load"]
    named = list(layer.collect_params().values())
    assert len(named) == len(order)
    for p, leaf in zip(named, order):
        if leaf in ("rows", "load"):
            continue
        value = onp.asarray(dict(params, **aux)["l2.moe." + leaf])
        if leaf in ("w13", "w2") and len(value) > held:  # every expert's
            value = value[first:first + held]
        p.set_data(nd.array(value))
    return layer


def test_one_step_of_the_bias_update(ref):
    """``update_expert_bias``: an expert over its share of the load moves
    down by one step of the rate, one under it up, one at it stays; and
    a forward outside training leaves the bias and the load as they
    were."""
    from mxnet_tpu.parallel.moe import expert_load, update_expert_bias

    idx = jnp.asarray([[0, 1], [0, 2], [0, 1], [3, 1]], jnp.int32)
    load = expert_load(idx, 4)
    onp.testing.assert_array_equal(load, [1.5, 1.5, 0.5, 0.5])
    new = update_expert_bias(jnp.full((4,), 2.0), load)
    onp.testing.assert_array_equal(new, [1, 1, 3, 3])
    onp.testing.assert_array_equal(
        update_expert_bias(jnp.zeros(4), jnp.ones(4)), 0)
    layer = TopKMoE(8, 16, 2, score="sigmoid", expert_bias=1e-3)
    layer.initialize()
    x = nd.array(onp.random.default_rng(1).standard_normal((1, 12, 32))
                 .astype("f"))
    layer(x)                                # inference: nothing moves
    assert not layer.expert_bias.data().asnumpy().any()
    assert not layer.expert_load.data().asnumpy().any()
    with autograd.record():
        layer(x)
    load = layer.expert_load.data().asnumpy()
    assert load.sum() == pytest.approx(8)
    onp.testing.assert_array_equal(layer.expert_bias.data().asnumpy(),
                                   onp.sign(1 - load))


def test_the_compiled_step_moves_the_bias_and_its_build_does_not(model, ref):
    """``SPMDTrainer``: the one-sample forward that finishes the shapes
    leaves the bias at its seeded value, each compiled step moves it by
    one application of the rule from that step's load (the reference's,
    step by step), and the step publishes the bias and the load as
    ``moe/bias_steps_max`` and ``moe/load_max_over_mean``."""
    from mxnet_tpu.telemetry import metrics

    cfg = dict(CFG, num_hidden_layers=3)
    x, y = model.make_batch(cfg, TRAFFIC, 2, onp.random.default_rng(8))
    params, aux = _weights(ref, cfg, 9, BIAS)
    net = model.build_net(cfg)
    net.initialize()
    by_leaf = _bind(model, ref, cfg, net, params, aux)
    trainer = parallel.SPMDTrainer(
        net, model.loss_block(cfg), optimizer="sgd",
        optimizer_params={"learning_rate": 0.0},
        mesh=parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    trainer._ensure_built(x, y)
    onp.testing.assert_array_equal(
        by_leaf["l2.moe.bias"].data().asnumpy(), BIAS)
    names = {p.name: leaf for leaf, p in by_leaf.items()}
    state = aux
    for _ in range(2):
        trainer.step(nd.array(x, dtype="int32"), nd.array(y, dtype="int32"))
        _, state = ref.loss(cfg, params, state, (x, y))
        live = {names[n]: onp.asarray(a)
                for n, a in trainer.param_arrays().items()}
        for leaf in ("l2.moe.bias", "l2.moe.load", "l2.moe.rows"):
            onp.testing.assert_allclose(live[leaf], onp.asarray(state[leaf]),
                                        rtol=1e-6, err_msg=leaf)
    trainer._publish_stats()
    snap = metrics.family_snapshot("moe")
    assert snap["bias_steps_max"] == float(
        onp.abs(onp.asarray(state["l2.moe.bias"])).max())
    assert snap["load_max_over_mean"] == pytest.approx(
        float(onp.asarray(state["l2.moe.load"]).max()), rel=1e-6)


def test_eight_shares_add_up_to_the_uncut_layer(ref):
    """The guide's share test at the deployment's counts: a router 64
    wide, 4 experts a token, 8 shares of 8 experts, under a nonzero
    bias. The parts the shares give add up to what the reference gives
    for the whole layer; every share routes alike (the load over all 64
    and the bias's update are the same in each); and a share of the
    program is that share of the reference."""
    from refcommon import Prec

    cfg = dict(CFG, hidden_size=32, moe_intermediate_size=16,
               router_experts=64, num_experts=64, experts_first=0,
               num_hidden_layers=3)
    bias = onp.random.default_rng(3).integers(-50, 50, 64).astype("f")
    params, aux = _weights(ref, cfg, 31, bias)
    m = jax.random.normal(jax.random.PRNGKey(32), (1, 48, 32))
    prec = Prec("float32")
    whole, state = ref.moe_layer(m, params, aux, "l2", cfg, prec)
    assert float(state["l2.moe.rows"].sum()) == 48 * 4

    @jax.jit
    def share(first):
        cut = dict(params, **{
            "l2.moe." + w: jax.lax.dynamic_slice_in_dim(
                params["l2.moe." + w], first, 8)
            for w in ("w13", "w2")})
        return ref.moe_layer(m, cut, aux, "l2", cfg, prec, first, 8)

    parts = [share(8 * i) for i in range(8)]
    assert _rel(sum(p[0] for p in parts), whole) < TOL
    assert float(sum(p[1]["l2.moe.rows"].sum() for p in parts)) == 48 * 4
    for _, got in parts:
        for leaf in ("l2.moe.load", "l2.moe.bias"):
            onp.testing.assert_array_equal(got[leaf], state[leaf])
    for first in (0, 56):       # the program's share is the reference's
        layer = _bound_moe(cfg, params, aux, first, 8, m)
        with autograd.record():     # a training forward counts the rows
            got = layer(nd.array(onp.asarray(m)))
        want, want_state = share(first)
        assert _rel(got.data, want) < TOL
        onp.testing.assert_array_equal(
            layer.expert_rows.data().asnumpy(),
            onp.asarray(want_state["l2.moe.rows"]))


def test_expert_parallel_layer_routes_with_sigmoid_scores_and_a_bias():
    """``expert_parallel_ffn`` over an 'ep' axis of 4 with the sigmoid
    score and a selection bias: the one-device layer under the same
    router, and the load of every routed expert that the bias's update
    reads."""
    from mxnet_tpu.parallel.moe import (expert_ffn, expert_load,
                                        expert_parallel_ffn, top_k_router)

    rs = onp.random.RandomState(41)
    t, d, f, e, k = 32, 16, 8, 8, 2
    x = jnp.asarray(rs.randn(t, d).astype("f"))
    gate_w = jnp.asarray(rs.randn(d, e).astype("f"))
    w13 = jnp.asarray(rs.randn(e, d, 2 * f).astype("f") * 0.3)
    w2 = jnp.asarray(rs.randn(e, f, d).astype("f") * 0.3)
    bias = jnp.asarray(rs.uniform(-0.5, 0.5, e).astype("f"))
    idx, gates = top_k_router(x, gate_w, k, True, "sigmoid", bias)
    assert float(jnp.abs(gates.sum(-1) - 1.0).max()) < 1e-5
    want, want_rows = expert_ffn(x, idx, gates, w13, w2)
    mesh = parallel.make_mesh({"ep": 4}, devices=jax.devices()[:4])
    y, rows, load = expert_parallel_ffn(x, gate_w, w13, w2, k, mesh,
                                        score="sigmoid", bias=bias)
    assert _rel(y, want) < TOL
    onp.testing.assert_array_equal(rows, want_rows)
    onp.testing.assert_array_equal(load, expert_load(idx, e))


def test_the_head_is_the_embeddings_matrix(model, ref):
    """Tied: no weight of the head's own, the logits are the final norm's
    output against the embedding's rows, and an untied net of the same
    pattern has a head of (vocab, E)."""
    cfg = dict(CFG, num_hidden_layers=2)
    net = model.build_net(cfg)
    net.initialize()
    x = onp.random.default_rng(2).integers(0, 96, (1, 16)).astype("int32")
    logits = net(nd.array(x, dtype="int32"))
    assert not any("dense" in n and n.split("_")[-2].startswith("dense")
                   and p.shape == (96, 64)
                   for n, p in net.collect_params().items())
    w = net.embed.weight.data().asnumpy()
    h = net.blocks(net.embed(nd.array(x, dtype="int32")))
    want = net.ln_f(h).asnumpy() @ w.T
    assert _rel(logits.data, want) < TOL
    untied = model.build_net(dict(cfg, tie_word_embeddings=False))
    untied.initialize()
    untied(nd.array(x, dtype="int32"))
    assert untied.head.weight.shape == (96, 64)
    assert len(untied.collect_params()) == len(net.collect_params()) + 1


# ---------------------------------------------------------------------------
# the mixer and the dense MLP

def _conv_block(ref, cfg, seed):
    params, _ = ref.init(cfg, jax.random.PRNGKey(seed))
    params = {k: v * 5 if v.ndim > 1 else v for k, v in params.items()}
    order = ["conv.w", "in.w", "out.w"]
    return params, {leaf: params["l0.attn." + leaf] for leaf in order}


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["twin", "kernels"])
def test_gated_short_conv_matches_the_references_layer(ref, monkeypatch,
                                                       use_pallas):
    """The block alone on a normed input against ``conv_layer``: the
    result, and the gradient of every weight and of the input; with the
    kernel pair forced (interpreted) at 128 channels and 384 positions
    in three tiles, and with the twin at 100 positions."""
    from refcommon import Prec

    from mxnet_tpu.kernels import short_conv as sc

    e, s = (128, 384) if use_pallas else (64, 100)
    cfg = dict(CFG, hidden_size=e, num_hidden_layers=1)
    _, leaves = _conv_block(ref, cfg, 13)
    n = jax.random.normal(jax.random.PRNGKey(14), (2, s, e))
    cot = jax.random.normal(jax.random.PRNGKey(15), (2, s, e))

    def reference(n, leaves):
        p = {"l0.attn." + k: v for k, v in leaves.items()}
        return (ref.conv_layer(n, p, "l0", cfg, Prec("float32")) * cot).sum()

    want, (want_dn, want_dp) = jax.value_and_grad(reference, (0, 1))(
        n, leaves)
    block = models.GatedShortConv(e, taps=3)
    block.initialize()
    block(nd.array(onp.asarray(n)))
    named = list(block.collect_params().values())
    assert len(named) == 3
    for p, leaf in zip(named, leaves):
        p.set_data(nd.array(onp.asarray(leaves[leaf]).T))
    counted = "short_conv_pallas" if use_pallas else "short_conv_plain"
    before = kernels.counters().get(counted, 0)
    monkeypatch.setattr(sc, "short_conv", functools.partial(
        sc.short_conv, use_pallas=use_pallas))
    x = nd.array(onp.asarray(n))
    x.attach_grad()
    with autograd.record():
        total = (block(x) * nd.array(onp.asarray(cot))).sum()
    total.backward()
    assert kernels.counters()[counted] > before
    assert abs(float(total.asscalar()) - float(want)) < TOL * abs(float(want))
    assert _rel(x.grad.data, want_dn) < TOL
    for p, leaf in zip(named, leaves):
        assert _rel(p.grad().data.T, want_dp[leaf]) < TOL, leaf


def test_swiglu_is_w2_of_silu_w1_times_w3():
    block = models.SwiGLU(32, 48)
    block.initialize()
    x = onp.random.default_rng(4).standard_normal((2, 5, 32)).astype("f")
    got = block(nd.array(x)).data
    w13 = block.w13.weight.data().asnumpy()
    w2 = block.w2.weight.data().asnumpy()
    h = x @ w13.T
    want = (jax.nn.silu(h[..., :48]) * h[..., 48:]) @ w2.T
    assert _rel(got, want) < TOL


def test_arguments_are_checked():
    with pytest.raises(ValueError, match="mlp"):
        models.MoEDecoderLM(32, 16, 1, 2, 1, 8, 4, 8, 2, mlp="dense")
    with pytest.raises(ValueError, match="mlp: 1 entries"):
        models.MoEDecoderLM(32, 16, 2, 2, 1, 8, 4, 8, 2, mlp=["moe"])
    with pytest.raises(ValueError, match="score"):
        TopKMoE(8, 16, 2, score="tanh")
    with pytest.raises(ValueError, match="no attention"):
        models.GroupedQueryAttention(16, 2, 1, 8,
                                     attention={"short_conv": {"taps": 3}})


# ---------------------------------------------------------------------------
# the defaults build the program the existing configurations had

#: sha256 of the StableHLO text of ``SPMDTrainer``'s step (Adam, bfloat16)
#: for each existing ``MoEDecoderLM`` configuration at its rehearsal's
#: widths and 32 positions, and for the constructor's defaults, as the
#: program read before ``mlp=``, ``tie_embeddings=``, ``score=`` and
#: ``expert_bias=`` came
PROGRAMS = {
    "defaults":
        "df91fbda91793cf6dc6366d843b37e9e84c28d19a71f490c8a9bff1bfa6b7f00",
    "sdar-30b-a3b":
        "6b1bf7706af8a80ac6cf32a9cf006c481c17b8c7e29e55f5cfa2fca1c217a827",
    "smallthinker-21b-a3b":
        "0c979b6779fe7bc235da74b8224e803bd8908f1a8e8eb160e9a614b71fa1e357",
    "qwen3-next-80b-a3b":
        "d92dc5eb9477622e6b926dfe735a03ec83f85b7a16a1190ee7a577ebd4a14193",
}
TRAFFIC_OF = {"sdar-30b-a3b": "train-bd-s4096",
              "smallthinker-21b-a3b": "train-lm-1x16384",
              "qwen3-next-80b-a3b": "train-lm-1x8192"}


def _step_text(name):
    """The lowered step of configuration ``name`` (the benchmark's builder,
    loss block and batch), or of ``MoEDecoderLM`` with every default."""
    if name == "defaults":
        from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

        net = models.MoEDecoderLM(64, 32, 2, 4, 2, 8, 4, 16, 2)
        loss = SoftmaxCrossEntropyLoss()
        x = y = onp.zeros((1, 32), "int32")
    else:
        spec = importlib.util.spec_from_file_location(
            "tests_lfm2_defaults_" + name.replace("-", "_"),
            os.path.join(BENCH, "models", name + ".py"))
        builder = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(builder)
        with open(os.path.join(BENCH, "configs", name + ".json")) as f:
            cfg = json.load(f)
        cfg.update(cfg["rehearse"])
        with open(os.path.join(BENCH, "traffic",
                               TRAFFIC_OF[name] + ".json")) as f:
            traffic = dict(json.load(f), seq=32)
        net, loss = builder.build_net(cfg), builder.loss_block(cfg)
        x, y = builder.make_batch(cfg, traffic, 1, onp.random.default_rng(0))
    net.initialize()
    trainer = parallel.SPMDTrainer(
        net, loss, optimizer="adamw",
        optimizer_params={"learning_rate": 1e-7},
        mesh=parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1]),
        compute_dtype="bfloat16")
    trainer._ensure_built(x, y)
    return trainer._compiled.lower(
        trainer._param_vals, trainer._states, trainer._aux,
        jnp.asarray(x), jnp.asarray(y)).as_text()


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_defaults_build_the_existing_configurations_programs(name):
    digest = hashlib.sha256(_step_text(name).encode()).hexdigest()
    assert digest == PROGRAMS[name]
