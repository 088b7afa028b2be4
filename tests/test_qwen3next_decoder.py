"""``models.MoEDecoderLM`` under the layer pattern [gated delta x 3,
gated full attention] against the plain reference the benchmark keeps
(``benchmarks/reference/qwen3-next-80b-a3b.py``: the recurrence position
by position), in float32 on the CPU at a small size; and the pieces the
pattern brought: ``GatedDeltaNet``, RoPE on a part of the head, the
output gate, the flash kernels at D=256 in a group of 8, the tiles the
chooser gives the four cells, ``TopKMoE``'s shared expert, and the shares
of a layer's experts adding up to the uncut layer."""
import functools
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import autograd, kernels, models, nd
from mxnet_tpu.gluon.contrib.nn import TopKMoE
from mxnet_tpu.kernels import flash_attention as fa
from mxnet_tpu.kernels.qk_prologue import rope

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")

CFG = dict(hidden_size=64, head_dim=16, num_attention_heads=4,
           num_key_value_heads=2, linear_num_key_heads=2,
           linear_num_value_heads=4, linear_key_head_dim=16,
           linear_value_head_dim=16, linear_conv_kernel_dim=4,
           full_attention_interval=4, partial_rotary_factor=0.25,
           moe_intermediate_size=32, shared_expert_intermediate_size=32,
           num_hidden_layers=4, num_experts=4, router_experts=8,
           experts_first=2, num_experts_per_tok=2, norm_topk_prob=True,
           vocab_size=96, rms_norm_eps=1e-6, rope_theta=1e7, init_std=0.02)
TRAFFIC = dict(seq=80)      # not a multiple of the rule's chunk of 64
TOL = 2e-5      # float32 on both sides: sums in another order
TOL_DECAY = 2e-4    # a_log, dt_bias: through exp(cumulative sums)


def _bench_module(kind):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    name = "tests_qwen3next_" + kind
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, kind, "qwen3-next-80b-a3b.py"))
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


@pytest.fixture(scope="module")
def both_sides(model, ref):
    """One batch through the program (gluon autograd) and through the
    reference (jax.grad), from the same seeded weights."""
    x, y = model.make_batch(CFG, TRAFFIC, 2, onp.random.default_rng(7))
    params, aux = ref.init(CFG, jax.random.PRNGKey(3))
    # weights large enough that the router's choices are no near-ties
    params = {k: v * 5 if v.ndim > 1 else v for k, v in params.items()}
    net = model.build_net(CFG)
    net.initialize()
    net(nd.array(x, dtype="int32"))
    leaves = list(ref.leaf_shapes(CFG))
    named = list(net.collect_params().items())
    assert len(named) == len(leaves)
    every = dict(params, **aux)
    for (_, p), leaf in zip(named, leaves):
        p.set_data(nd.array(onp.asarray(model.to_program(leaf, every[leaf]))))
    loss_block = model.loss_block(CFG)
    with autograd.record():
        logits = net(nd.array(x, dtype="int32"))
        loss = loss_block(logits, nd.array(y)).mean()
    loss.backward()
    (want_loss, want_aux), want_grads = jax.value_and_grad(
        lambda p: ref.loss(CFG, p, aux, (x, y)), has_aux=True)(params)
    want_logits, _ = ref.forward(CFG, params, aux, jnp.asarray(x), True)
    return dict(by_leaf=dict(zip(leaves, (p for _, p in named))),
                model=model, logits=logits, loss=loss,
                want_logits=want_logits, want_loss=want_loss,
                want_grads=want_grads, want_aux=want_aux)


def test_logits_loss_and_rows_match_the_reference(both_sides):
    s = both_sides
    assert s["logits"].shape == (2, TRAFFIC["seq"], CFG["vocab_size"])
    assert _rel(s["logits"].data, s["want_logits"]) < TOL
    assert abs(float(s["loss"].asscalar()) - float(s["want_loss"])) \
        < TOL * float(s["want_loss"])
    for leaf, rows in s["want_aux"].items():
        onp.testing.assert_array_equal(
            onp.asarray(s["by_leaf"][leaf].data().asnumpy()),
            onp.asarray(rows))


_LEAVES = ["embed.w", "lnf.gamma", "head.w"] + [
    f"l{i}.{name}" for i in (0, 2) for name in (
        "ln1.gamma", "attn.conv.w", "attn.a_log", "attn.dt_bias",
        "attn.norm.gamma", "attn.qkvz.w", "attn.ba.w", "attn.out.w",
        "ln2.gamma", "moe.router.w", "moe.w13", "moe.w2",
        "moe.shared.gate.w", "moe.shared.w13", "moe.shared.w2")] + [
    "l3." + name for name in (
        "ln1.gamma", "attn.q_norm", "attn.k_norm", "attn.qkv.w",
        "attn.out.w", "ln2.gamma", "moe.router.w", "moe.w13", "moe.w2",
        "moe.shared.gate.w", "moe.shared.w13", "moe.shared.w2")]


@pytest.mark.parametrize("leaf", _LEAVES)
def test_gradient_leaf_matches_the_reference(both_sides, leaf):
    s = both_sides
    got = s["by_leaf"][leaf].grad().data
    want = s["model"].to_program(leaf, s["want_grads"][leaf])
    tol = TOL_DECAY if leaf.endswith(("a_log", "dt_bias")) else TOL
    assert _rel(got, want) < tol


def test_every_leaf_is_one_of_the_tested_or_a_twin_layer(ref):
    """Layers 0, 1 and 2 are one kind: 0 and 2 are compared leaf by
    leaf, as is the full layer 3 and what lies outside the layers."""
    named = {k for k, (_, kind) in ref.leaf_shapes(CFG).items()
             if kind != "state" and not k.startswith("l1.")}
    assert named == set(_LEAVES)


def test_gated_delta_net_matches_the_references_layer(ref):
    """The block alone on a normed input against ``linear_layer``."""
    from refcommon import Prec

    cfg = dict(CFG, num_hidden_layers=1)
    params, _ = ref.init(cfg, jax.random.PRNGKey(11))
    params = {k: v * 5 if v.ndim > 1 else v for k, v in params.items()}
    n = jax.random.normal(jax.random.PRNGKey(12), (2, 100, 64))
    want = ref.linear_layer(n, params, "l0", cfg, Prec("float32"))
    block = models.GatedDeltaNet(64, 2, 4, 16, 16, conv_kernel=4)
    block.initialize()
    block(nd.array(onp.asarray(n)))
    order = ["conv.w", "a_log", "dt_bias", "norm.gamma", "qkvz.w", "ba.w",
             "out.w"]
    named = list(block.collect_params().values())
    assert len(named) == len(order)
    for p, leaf in zip(named, order):
        p.set_data(nd.array(onp.asarray(params["l0.attn." + leaf]).T
                            if leaf.endswith(".w") else
                            onp.asarray(params["l0.attn." + leaf])))
    assert _rel(block(nd.array(onp.asarray(n))).data, want) < TOL


def test_gated_delta_net_with_prologue_kernels_matches_the_reference(
        ref, monkeypatch):
    """The block with the convolution, SiLU, l2 norms and layout as the
    kernel pair (``kernels/delta_prologue.py``, forced and interpreted;
    heads of 128 lanes, a key head serving two value heads, 384 positions
    in three tiles) against ``linear_layer``: the result, and the
    gradient of every weight and of the input."""
    from refcommon import Prec

    from mxnet_tpu.kernels import delta_prologue as dp

    cfg = dict(CFG, num_hidden_layers=1, linear_key_head_dim=128,
               linear_value_head_dim=128)
    params, _ = ref.init(cfg, jax.random.PRNGKey(13))
    params = {k: v * 5 if v.ndim > 1 else v for k, v in params.items()}
    n = jax.random.normal(jax.random.PRNGKey(14), (1, 384, 64))
    cot = jax.random.normal(jax.random.PRNGKey(15), (1, 384, 64))
    order = ["conv.w", "a_log", "dt_bias", "norm.gamma", "qkvz.w", "ba.w",
             "out.w"]
    leaves = {leaf: params["l0.attn." + leaf] for leaf in order}

    def reference(n, leaves):
        p = {"l0.attn." + k: v for k, v in leaves.items()}
        return (ref.linear_layer(n, p, "l0", cfg, Prec("float32"))
                * cot).sum()

    want, (want_dn, want_dp) = jax.value_and_grad(reference, (0, 1))(
        n, leaves)
    block = models.GatedDeltaNet(64, 2, 4, 128, 128, conv_kernel=4)
    block.initialize()
    block(nd.array(onp.asarray(n)))
    named = list(block.collect_params().values())
    for p, leaf in zip(named, order):
        value = onp.asarray(leaves[leaf])
        p.set_data(nd.array(value.T if leaf.endswith(".w") else value))
    before = kernels.counters().get("delta_prologue_pallas", 0)
    monkeypatch.setattr(dp, "delta_prologue", functools.partial(
        dp.delta_prologue, use_pallas=True))
    x = nd.array(onp.asarray(n))
    x.attach_grad()
    with autograd.record():
        total = (block(x) * nd.array(onp.asarray(cot))).sum()
    total.backward()
    assert kernels.counters()["delta_prologue_pallas"] > before
    assert abs(float(total.asscalar()) - float(want)) < TOL * abs(float(want))
    assert _rel(x.grad.data, want_dn) < TOL
    for p, leaf in zip(named, order):
        got = p.grad().data
        tol = TOL_DECAY if leaf in ("a_log", "dt_bias") else TOL
        assert _rel(got.T if leaf.endswith(".w") else got,
                    want_dp[leaf]) < tol, leaf


# ---------------------------------------------------------------------------
# GroupedQueryAttention: RoPE on a part of the head, the output gate

def _attention_block(**kw):
    block = models.GroupedQueryAttention(32, 2, 1, 16, rope_theta=1e4,
                                         qk_norm=False, **kw)
    block.initialize()
    x = onp.random.default_rng(3).standard_normal((1, 24, 32)).astype("f")
    block(nd.array(x))
    w = {n.rsplit("_", 2)[-2]: p.data().asnumpy()
         for n, p in block.collect_params().items()}
    return block, x, w["dense0"], w["dense1"]


def _by_hand(x, w_qkv, w_out, rotary, gated):
    h, d, s = 2, 16, x.shape[1]
    qkv = jnp.asarray(x) @ w_qkv.T
    if gated:
        qg = qkv[..., :2 * h * d].reshape(1, s, h, 2 * d)
        q, gate = qg[..., :d], qg[..., d:].reshape(1, s, h * d)
        k, v = qkv[..., 2 * h * d:(2 * h + 1) * d], qkv[..., (2 * h + 1) * d:]
    else:
        q = qkv[..., :h * d].reshape(1, s, h, d)
        k, v = qkv[..., h * d:(h + 1) * d], qkv[..., (h + 1) * d:]
    q = q.transpose(0, 2, 1, 3)
    k, v = k[:, None], v[:, None]
    pos = jnp.arange(s)

    def turn(a):
        return jnp.concatenate(
            [rope(a[..., :rotary], pos, 1e4), a[..., rotary:]],
            -1)

    o = fa._ref_attention(turn(q), turn(k), v, d ** -0.5, True, s)
    o = o.transpose(0, 2, 1, 3).reshape(1, s, h * d)
    if gated:
        o = o * jax.nn.sigmoid(gate)
    return o @ w_out.T


@pytest.mark.parametrize("rotary", [4, 16])
def test_rope_on_the_leading_dims_only(rotary):
    block, x, w_qkv, w_out = _attention_block(rotary_dim=rotary)
    want = _by_hand(x, w_qkv, w_out, rotary, False)
    assert _rel(block(nd.array(x)).data, want) < TOL
    if rotary < 16:     # and it is not RoPE on the whole head
        assert _rel(_by_hand(x, w_qkv, w_out, 16, False), want) > 1e-3


def test_rotary_dim_is_checked():
    with pytest.raises(ValueError, match="rotary_dim"):
        models.GroupedQueryAttention(32, 2, 1, 16, rotary_dim=32)
    with pytest.raises(ValueError, match="rotary_dim"):
        models.GroupedQueryAttention(32, 2, 1, 16, rotary_dim=5)


def test_output_gate_multiplies_the_heads_results():
    block, x, w_qkv, w_out = _attention_block(output_gate=True)
    assert w_qkv.shape == ((2 * 2 + 2) * 16, 32)    # q twice as wide
    want = _by_hand(x, w_qkv, w_out, 16, True)
    assert _rel(block(nd.array(x)).data, want) < TOL


def test_defaults_keep_the_projection_and_the_whole_heads_rope():
    block, x, w_qkv, w_out = _attention_block()
    assert w_qkv.shape == ((2 + 2) * 16, 32)
    assert _rel(block(nd.array(x)).data,
                _by_hand(x, w_qkv, w_out, 16, False)) < TOL


# ---------------------------------------------------------------------------
# the flash kernels at D=256, 8 query heads a key/value head

def test_flash_passes_interpreted_at_head_size_256_in_a_group_of_8():
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (1, 8, 256, 256))
    k = jax.random.normal(ks[1], (1, 1, 256, 256))
    v = jax.random.normal(ks[2], (1, 1, 256, 256))
    do = jax.random.normal(ks[3], (1, 8, 256, 256))

    def grads(fn):
        o, pull = jax.vjp(fn, q, k, v)
        return (o,) + pull(do)

    got = grads(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, use_pallas=True))
    want = grads(lambda q, k, v: fa._ref_attention(
        q, k, v, 256 ** -0.5, True, 256))
    for name, a, b in zip("o dq dk dv".split(), got, want):
        assert a.shape == b.shape
        assert _rel(a, b) < 2e-5, name


@pytest.mark.parametrize("s,d,forward,backward", [
    (2048, 64, (1024, 1024), (512, 512, 2048)),     # opt1.3b-train-s2048
    (8192, 128, (1024, 1024), (256, 512, 8192)),    # sdar30b-train-bd-s4096
    (16384, 128, (1024, 1024), (512, 512, 4096)),   # smallthinker21b
    (8192, 256, (512, 1024), (512, 512, 2048)),     # qwen3next80b
], ids=["opt", "sdar", "smallthinker", "qwen3next"])
def test_the_cells_shapes_keep_their_tiles(s, d, forward, backward):
    """The three accepted cells' tiles as PRs 29-35 left them, and both
    passes of the new cell's full layer have tiles (four segments of
    2,048 query rows backward): what the v5e compiler took."""
    assert fa.choose_tiles(s, s, d, 2) == forward
    assert fa.choose_backward(s, s, d, 2) == backward
    assert fa.choose_tiles(s, s, d, 2, backward=True) == backward[:2]


# ---------------------------------------------------------------------------
# the shared expert, and the shares of a layer

def _moe_params(ref, cfg, seed):
    params, _ = ref.init(dict(cfg, num_hidden_layers=1),
                         jax.random.PRNGKey(seed))
    return {k: v * 5 if v.ndim > 1 else v for k, v in params.items()
            if k.startswith("l0.moe.")}


def _bound_moe(cfg, params, first, held, x):
    layer = TopKMoE(cfg["router_experts"], cfg["moe_intermediate_size"],
                    cfg["num_experts_per_tok"], experts_held=(first, held),
                    shared_expert=cfg["shared_expert_intermediate_size"])
    layer.initialize()
    layer(nd.array(x))
    order = ["router.w", "w13", "w2", "rows", "shared.gate.w",
             "shared.w13", "shared.w2"]
    named = list(layer.collect_params().values())
    assert len(named) == len(order)
    for p, leaf in zip(named, order):
        if leaf == "rows":
            continue
        value = onp.asarray(params["l0.moe." + leaf])
        if leaf in ("w13", "w2"):
            value = value[first:first + held]
        p.set_data(nd.array(value))
    return layer, dict(zip(order, named))


def test_shared_expert_forward_and_gradients_against_a_dense_loop(ref):
    """``TopKMoE(shared_expert=)`` holding every expert: the result and
    the gradient of every weight and of the input against a loop over
    the experts plus the shared one, written here."""
    cfg = dict(CFG, router_experts=8, num_experts=8, experts_first=0)
    params = _moe_params(ref, cfg, 21)
    x = onp.random.default_rng(5).standard_normal((2, 24, 64)).astype("f")
    weight = onp.random.default_rng(6).standard_normal(x.shape).astype("f")

    def dense(x, p):
        probs = jax.nn.softmax(x @ p["l0.moe.router.w"], -1)
        gates, idx = jax.lax.top_k(probs, 2)
        gates = gates / gates.sum(-1, keepdims=True)

        def ffn(w13, w2):
            h = x @ w13
            f = w2.shape[0]
            return (jax.nn.silu(h[..., :f]) * h[..., f:]) @ w2

        y = sum(jnp.where(idx == e, gates, 0.0).sum(-1)[..., None]
                * ffn(p["l0.moe.w13"][e], p["l0.moe.w2"][e])
                for e in range(8))
        return y + jax.nn.sigmoid(x @ p["l0.moe.shared.gate.w"]) * ffn(
            p["l0.moe.shared.w13"], p["l0.moe.shared.w2"])

    want, (want_dx, want_dp) = jax.value_and_grad(
        lambda x, p: (dense(x, p) * weight).sum(), (0, 1))(
        jnp.asarray(x), params)
    layer, by_leaf = _bound_moe(cfg, params, 0, 8, x)
    xin = nd.array(x)
    xin.attach_grad()
    with autograd.record():
        out = layer(xin)
        total = (out * nd.array(weight)).sum()
    total.backward()
    assert _rel(out.data, dense(jnp.asarray(x), params)) < TOL
    assert abs(float(total.asscalar()) - float(want)) < TOL * abs(float(want))
    assert _rel(xin.grad.data, want_dx) < TOL
    for leaf, p in by_leaf.items():
        if leaf != "rows":
            assert _rel(p.grad().data, want_dp["l0.moe." + leaf]) < TOL, leaf


def test_no_shared_expert_by_default():
    layer = TopKMoE(8, 32, 2)
    assert [n.rsplit("_", 1)[-1] for n in layer.collect_params()] == \
        ["weight", "w13", "w2", "rows"]


def test_thirty_two_shares_add_up_to_the_uncut_layer(ref):
    """The guide's share test at the deployment's counts: a router 512
    wide, 10 experts a token, 32 shares of 16 experts. The parts the
    shares give, the shared expert counted once, add up to what the
    reference gives for the whole layer; and a share of the program is
    that share of the reference."""
    from refcommon import Prec

    cfg = dict(CFG, hidden_size=32, moe_intermediate_size=16,
               shared_expert_intermediate_size=16, router_experts=512,
               num_experts=512, experts_first=0, num_experts_per_tok=10)
    params = _moe_params(ref, cfg, 31)
    m = jax.random.normal(jax.random.PRNGKey(32), (1, 48, 32))
    prec = Prec("float32")
    whole, rows = ref.moe_layer(m, params, "l0", cfg, prec)
    assert float(rows.sum()) == 48 * 10

    @functools.partial(jax.jit, static_argnums=1)
    def share(first, shared):
        cut = dict(params, **{
            "l0.moe." + w: jax.lax.dynamic_slice_in_dim(
                params["l0.moe." + w], first, 16)
            for w in ("w13", "w2")})
        return ref.moe_layer(m, cut, "l0", cfg, prec, first, 16, shared)

    parts = [share(16 * i, i == 0) for i in range(32)]
    assert _rel(sum(p[0] for p in parts), whole) < TOL
    assert float(sum(p[1].sum() for p in parts)) == 48 * 10
    for first in (0, 496):      # the program's share is the reference's
        layer, by_leaf = _bound_moe(cfg, params, first, 16, onp.asarray(m))
        with autograd.record():     # a training forward counts the rows
            got = layer(nd.array(onp.asarray(m)))
        want, want_rows = share(first, True)
        assert _rel(got.data, want) < TOL
        onp.testing.assert_array_equal(
            by_leaf["rows"].data().asnumpy(), onp.asarray(want_rows))
