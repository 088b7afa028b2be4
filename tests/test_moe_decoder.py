"""``models.MoEDecoderLM`` against the plain reference the benchmark keeps
(``benchmarks/reference/sdar-30b-a3b.py``), in float32 on the CPU at a
small size: logits, loss and every gradient leaf under block-diffusion
training; the shares of a layer's experts adding up to the uncut layer;
and the causal mode. The model is built, fed and bound to the
reference's leaves by the benchmark's own builder
(``benchmarks/models/sdar-30b-a3b.py``), so what is tested here is what
a run of the cell compares.
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

CFG = dict(hidden_size=64, head_dim=16, num_attention_heads=4,
           num_key_value_heads=2, moe_intermediate_size=32,
           num_hidden_layers=2, num_experts=8, router_experts=8,
           experts_first=0, num_experts_per_tok=2, norm_topk_prob=True,
           vocab_size=96, rms_norm_eps=1e-6, rope_theta=1e6, block_length=4,
           init_std=0.02, qk_norm_init=2.0)
TRAFFIC = dict(seq=32, block_length=4, t_range=[0.05, 1.0])
TOL = 5e-6      # float32 on both sides: sums in another order


def _bench_module(kind):
    """A file of the benchmark by path (``sdar-30b-a3b.py`` is no module
    name); the reference imports ``refcommon`` from beside it."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    name = "tests_sdar_" + kind
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, kind, "sdar-30b-a3b.py"))
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


def _bound_net(model, ref, cfg, params, aux, x):
    """The program's net holding the reference's weights, leaf by leaf
    in the order both sides build them."""
    net = model.build_net(cfg)
    net.initialize()
    net(nd.array(x, dtype="int32"))
    leaves = list(ref.leaf_shapes(cfg))
    named = list(net.collect_params().items())
    assert len(named) == len(leaves)
    every = dict(params, **aux)
    for (_, p), leaf in zip(named, leaves):
        p.set_data(nd.array(onp.asarray(model.to_program(leaf, every[leaf]))))
    return net, dict(zip(leaves, (p for _, p in named)))


def _rel(got, want):
    got, want = onp.asarray(got, "float64"), onp.asarray(want, "float64")
    return onp.abs(got - want).max() / max(onp.abs(want).max(), 1e-30)


@pytest.fixture(scope="module", params=[(0, 8), (2, 4)],
                ids=["all_8_held", "experts_2_to_5_held"])
def both_sides(request, model, ref):
    """One batch through the program (gluon autograd) and through the
    reference (jax.grad), from the same seeded weights."""
    first, held = request.param
    cfg = dict(CFG, experts_first=first, num_experts=held)
    x, y = model.make_batch(cfg, TRAFFIC, 2, onp.random.default_rng(7))
    params, aux = ref.init(cfg, jax.random.PRNGKey(3))
    # weights large enough that the router's choices are no near-ties
    params = {k: v * 5 if v.ndim > 1 else v for k, v in params.items()}
    net, by_leaf = _bound_net(model, ref, cfg, params, aux, x)
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
                want_grads=want_grads, want_aux=want_aux, y=y)


def test_logits_and_loss_match_the_reference(both_sides):
    s = both_sides
    assert s["logits"].shape == (2, TRAFFIC["seq"], CFG["vocab_size"])
    assert _rel(s["logits"].data, s["want_logits"]) < TOL
    assert abs(float(s["loss"].asscalar()) - float(s["want_loss"])) \
        < TOL * float(s["want_loss"])
    # the loss is over the masked positions of the noised half only
    assert (s["y"][:, 1] > 0).any() and (s["y"][:, 1] == 0).any()


@pytest.mark.parametrize("leaf", [
    "embed.w", "l0.ln1.gamma", "l0.attn.q_norm", "l0.attn.k_norm",
    "l0.attn.qkv.w", "l0.attn.out.w", "l0.ln2.gamma", "l0.moe.router.w",
    "l0.moe.w13", "l0.moe.w2", "l1.attn.qkv.w", "l1.moe.router.w",
    "l1.moe.w13", "l1.moe.w2", "lnf.gamma", "head.w"])
def test_gradient_leaf_matches_the_reference(both_sides, leaf):
    s = both_sides
    got = s["by_leaf"][leaf].grad().data
    want = s["model"].to_program(leaf, s["want_grads"][leaf])
    assert got.shape == want.shape
    assert float(jnp.abs(want).max()) > 0
    assert _rel(got, want) < TOL


def test_every_trained_leaf_was_compared(both_sides, ref):
    trained = {k for k, (_, kind) in ref.leaf_shapes(CFG).items()
               if kind != "state"}
    assert trained == set(both_sides["want_grads"])
    for leaf in trained:        # the leaves the parametrised test skips
        got = both_sides["by_leaf"][leaf].grad().data
        want = both_sides["model"].to_program(
            leaf, both_sides["want_grads"][leaf])
        assert _rel(got, want) < TOL, leaf


def test_rows_per_held_expert_match_the_reference(both_sides):
    """The state the step carries out of the forward: how many rows each
    held expert got, the reference's count of the same routing."""
    s = both_sides
    for i in range(CFG["num_hidden_layers"]):
        leaf = f"l{i}.moe.rows"
        got = onp.asarray(s["by_leaf"][leaf].data().data)
        want = onp.asarray(s["want_aux"][leaf])
        assert (got == want).all() and got.sum() > 0
        if s["cfg"]["num_experts"] == CFG["router_experts"]:
            assert got.sum() == 2 * 2 * TRAFFIC["seq"] \
                * CFG["num_experts_per_tok"]


def test_the_shares_add_up_to_the_uncut_layer(model, ref):
    """8 experts split as 4 shares of 2: what the four shares' expert
    layers give, added to the attention's residual counted once, is the
    uncut reference's layer output."""
    cfg = dict(CFG, num_hidden_layers=1)
    rs = onp.random.RandomState(11)
    x = rs.randn(2, 2 * TRAFFIC["seq"], cfg["hidden_size"]).astype("f")
    params, _ = ref.init(cfg, jax.random.PRNGKey(5))
    params = {k: v * 5 if v.ndim > 1 else v for k, v in params.items()}
    seq = TRAFFIC["seq"]
    pos = jnp.arange(2 * seq) % seq
    prec = sys.modules[ref.__name__].Prec("float32")
    want, want_rows = ref._layer(jnp.asarray(x), "l0", cfg, params, pos,
                                 ref.live_mask(seq, cfg["block_length"]),
                                 prec)

    def block(first, count):
        blk = models.MoEDecoderBlock(
            cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["router_experts"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], experts_held=(first, count),
            attention={"block_length": cfg["block_length"]})
        blk.initialize()
        blk(nd.array(x))
        held = slice(first, first + count)
        values = [params["l0.ln1.gamma"], params["l0.attn.q_norm"],
                  params["l0.attn.k_norm"], params["l0.attn.qkv.w"].T,
                  params["l0.attn.out.w"].T, params["l0.ln2.gamma"],
                  params["l0.moe.router.w"], params["l0.moe.w13"][held],
                  params["l0.moe.w2"][held]]
        named = list(blk.collect_params().values())
        assert len(named) == len(values) + 1        # and expert_rows
        for p, v in zip(named, values):
            p.set_data(nd.array(onp.asarray(v)))
        return blk

    shares = [block(first, 2) for first in range(0, 8, 2)]
    xin = nd.array(x)
    with autograd.pause(train_mode=True):
        outs = [blk(xin).data for blk in shares]
        one = shares[0]
        h1 = (xin + one.attn(one.ln1(xin))).data    # counted once
    total = h1 + sum(o - h1 for o in outs)
    assert _rel(total, want) < TOL
    # no share alone is the layer, and the shares' rows are the layer's
    assert _rel(outs[0], want) > 1e-3
    rows = onp.concatenate([onp.asarray(b.moe.expert_rows.data().data)
                            for b in shares])
    assert (rows == onp.asarray(want_rows)).all()


def test_causal_mode_looks_at_no_later_token():
    net = models.MoEDecoderLM(
        vocab_size=50, embed_dim=32, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=8, num_experts=4, expert_dim=16, top_k=2)
    net.initialize()
    rs = onp.random.RandomState(0)
    a = rs.randint(0, 50, (1, 24)).astype("int32")
    b = a.copy()
    b[0, 16:] = rs.randint(0, 50, 8)
    la, lb = (onp.asarray(net(nd.array(t, dtype="int32")).data)
              for t in (a, b))
    assert la.shape == (1, 24, 50)
    assert onp.allclose(la[:, :16], lb[:, :16], atol=1e-6)
    assert not onp.allclose(la[:, 16:], lb[:, 16:], atol=1e-4)


def test_noised_half_sees_its_block_and_the_clean_past_only():
    """Block diffusion through the whole model: a noised position's
    logits move with its own noised block and with the clean copy of
    earlier blocks, and with nothing else."""
    net = models.MoEDecoderLM(
        vocab_size=50, embed_dim=32, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=8, num_experts=4, expert_dim=16, top_k=2,
        attention={"block_length": 4})
    net.initialize()
    rs = onp.random.RandomState(1)
    L = 16
    base = rs.randint(0, 50, (1, 2 * L)).astype("int32")

    def logits(tokens):
        return onp.asarray(net(nd.array(tokens, dtype="int32")).data)[0]

    def changed(at):
        t = base.copy()
        t[0, at] = (t[0, at] + 1) % 50
        return onp.abs(logits(t) - logits(base)).max(-1) > 1e-6

    assert logits(base).shape == (L, 50)
    moved = changed(5)              # a noised token of block 1
    assert moved[4:8].all() and not moved[:4].any() and not moved[8:].any()
    moved = changed(L + 5)          # the clean copy of block 1
    assert not moved[:8].any() and moved[8:].all()


@pytest.mark.parametrize("attention", ["full", {"block": 4}, None])
def test_unknown_attention_spec_is_refused(attention):
    with pytest.raises(ValueError):
        models.MoEDecoderLM(
            vocab_size=10, embed_dim=8, num_layers=1, num_heads=2,
            num_kv_heads=1, head_dim=4, num_experts=2, expert_dim=4,
            top_k=1, attention=attention)
