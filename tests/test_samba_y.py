"""``models.SambaYLM`` against the plain reference the benchmark keeps
(``benchmarks/reference/phi4-mini-flash-3.8b.py``: the scan position by
position, attention dense), in float32 on the CPU at the configuration's
``rehearse`` sizes; the cross-decoder's two hand-overs (the memory a GMU
reads, the key/value set a cross layer reads) carrying gradient back to
the layers that made them; and differential attention against a dense
two-softmax formula under both masks."""
import importlib.util
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import autograd, models, nd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = "phi4-mini-flash-3.8b"
TRAFFIC = dict(seq=40)      # no multiple of the scan's group of 16
# float32 on both sides, sums in another order: the scan's read-out and
# its states over 40 positions, attention by the flash path's blocks
TOL = 2e-5
# the Mamba leaves whose gradient passes exp(delta A) and softplus (a
# relative 1e-6 of delta moves them by more), and lambda's four vectors,
# whose gradient is one sum of signed terms over every position, head and
# channel of A_2 v times the cotangent, which cancel to a few percent
TOL_SUMMED = 2e-4


def _bench_module(kind):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    name = "tests_samba_y_" + kind
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, kind, NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _cfg():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    cfg["vocab_size"] = 96
    return cfg


def _rel(got, want):
    got, want = onp.asarray(got, "float64"), onp.asarray(want, "float64")
    return onp.abs(got - want).max() / max(onp.abs(want).max(), 1e-30)


def _seeded_net(model, ref, cfg, key=3):
    params, _ = ref.init(cfg, jax.random.PRNGKey(key))
    net = model.build_net(cfg)
    net.initialize()
    leaves = list(ref.leaf_shapes(cfg))
    named = list(net.collect_params().items())
    assert len(named) == len(leaves)
    for (_, p), leaf in zip(named, leaves):
        p.set_data(nd.array(onp.asarray(model.to_program(leaf,
                                                         params[leaf]))))
    return net, params, dict(zip(leaves, (p for _, p in named)))


@pytest.fixture(scope="module")
def both_sides():
    """One batch through the program (gluon autograd) and through the
    reference (jax.grad), from the same seeded weights."""
    model, ref, cfg = _bench_module("models"), _bench_module("reference"), \
        _cfg()
    x, y = model.make_batch(cfg, TRAFFIC, 2, onp.random.default_rng(7))
    net, params, by_leaf = _seeded_net(model, ref, cfg)
    loss_block = model.loss_block(cfg)
    with autograd.record():
        logits = net(nd.array(x, dtype="int32"))
        loss = loss_block(logits, nd.array(y)).mean()
    loss.backward()
    (want_loss, _), want_grads = jax.value_and_grad(
        lambda p: ref.loss(cfg, p, {}, (x, y)), has_aux=True)(params)
    want_logits, _ = ref.forward(cfg, params, {}, jnp.asarray(x), True)
    return dict(model=model, cfg=cfg, by_leaf=by_leaf, logits=logits,
                loss=loss, want_logits=want_logits, want_loss=want_loss,
                want_grads=want_grads)


def test_logits_and_loss_match_the_reference(both_sides):
    s = both_sides
    assert s["logits"].shape == (2, TRAFFIC["seq"], s["cfg"]["vocab_size"])
    assert _rel(s["logits"].data, s["want_logits"]) < TOL
    assert abs(float(s["loss"].asscalar()) - float(s["want_loss"])) \
        < TOL * float(s["want_loss"])


_SUMMED_LEAVES = (".attn.a_log", ".attn.dt.w", ".attn.dt.b", ".attn.x.w",
                  ".attn.conv.w", ".attn.conv.b", ".attn.lq1", ".attn.lk1",
                  ".attn.lq2", ".attn.lk2")


def test_every_leafs_gradient_matches_the_reference(both_sides):
    s = both_sides
    assert set(s["by_leaf"]) == set(s["want_grads"])
    for leaf, p in s["by_leaf"].items():
        got = s["model"].to_program(leaf, onp.asarray(
            s["want_grads"][leaf]))
        tol = TOL_SUMMED if leaf.endswith(_SUMMED_LEAVES) else TOL
        assert _rel(p.grad().asnumpy(), got) < tol, leaf


def _blocks(net):
    return list(net.blocks._children.values())


def test_the_full_layers_keys_and_values_get_the_cross_layers_gradient():
    """The cross layer alone, reading layer 3's q|k|v: the full layer's
    k and v columns get gradient through it, its q columns and output
    projection none (nothing else of layer 3 is used)."""
    model, ref, cfg = _bench_module("models"), _bench_module("reference"), \
        _cfg()
    net, _, by_leaf = _seeded_net(model, ref, cfg)
    blocks = _blocks(net)
    tokens = nd.array(onp.arange(2 * 24).reshape(2, 24) % 96, dtype="int32")
    with autograd.record():
        x, memory, kv = net.embed(tokens), None, None
        for blk in blocks[:3]:
            x, memory, kv = blk(x, memory, kv)
        _, _, kv = blocks[3](x, memory, kv)
        out = blocks[5].attn(blocks[5].ln1(x), kv)
        out.sum().backward()
    h, hkv, d = (cfg[k] for k in ("num_attention_heads",
                                  "num_key_value_heads", "head_dim"))
    g = by_leaf["l3.attn.qkv.w"].grad().asnumpy()      # (out, in)
    assert not g[:h * d].any()
    assert onp.abs(g[h * d:(h + hkv) * d]).max() > 0       # k
    assert onp.abs(g[(h + hkv) * d:]).max() > 0             # v
    assert not by_leaf["l3.attn.out.w"].grad().asnumpy().any()
    assert onp.abs(by_leaf["l5.attn.qkv.w"].grad().asnumpy()).max() > 0


def test_the_memory_layers_input_projection_gets_the_gmus_gradient():
    """The GMU alone, reading layer 2's gated scan output: the Mamba
    layer's W_in (both halves: u through the scan, z through the gate)
    gets gradient through it, its out_proj none."""
    model, ref, cfg = _bench_module("models"), _bench_module("reference"), \
        _cfg()
    net, _, by_leaf = _seeded_net(model, ref, cfg)
    blocks = _blocks(net)
    tokens = nd.array(onp.arange(2 * 24).reshape(2, 24) % 96, dtype="int32")
    with autograd.record():
        x, memory, kv = net.embed(tokens), None, None
        for blk in blocks[:2]:
            x, memory, kv = blk(x, memory, kv)
        _, memory, _ = blocks[2](x, memory, kv)
        out = blocks[4].attn(blocks[4].ln1(x), memory)
        out.sum().backward()
    di = cfg["mamba_expand"] * cfg["hidden_size"]
    g = by_leaf["l2.attn.in.w"].grad().asnumpy()        # (2 Di, E)
    assert onp.abs(g[:di]).max() > 0 and onp.abs(g[di:]).max() > 0
    assert onp.abs(by_leaf["l2.attn.a_log"].grad().asnumpy()).max() > 0
    assert not by_leaf["l2.attn.out.w"].grad().asnumpy().any()
    assert onp.abs(by_leaf["l4.attn.in.w"].grad().asnumpy()).max() > 0


@pytest.mark.parametrize("window", [None, 5], ids=["causal", "window"])
def test_differential_attention_is_two_dense_softmaxes(window):
    """``DifferentialAttention`` against the formula written densely in
    numpy float64: two softmax maps over one v of twice the head width,
    their difference by lambda, a head's RMSNorm, (1 - lambda_init)."""
    e, h, hkv, d, s, depth = 32, 8, 4, 8, 12, 17
    attention = "causal" if window is None else {"window": window}
    blk = models.DifferentialAttention(e, h, hkv, d, depth, attention)
    blk.initialize()
    rs = onp.random.default_rng(11)
    vals = {}
    for name, p in blk.collect_params().items():
        v = rs.standard_normal(p.shape) * (0.3 if len(p.shape) > 1 else 0.5)
        p.set_data(nd.array(v.astype("float32")))
        vals[name] = v
    x = rs.standard_normal((2, s, e)).astype("float32")
    out, qkv = blk(nd.array(x))

    def param(suffix):
        return [v for k, v in vals.items() if k.endswith(suffix)][0]

    wq, bq = param("dense0_weight"), param("dense0_bias")
    wo, bo = param("dense1_weight"), param("dense1_bias")
    lam = {n: param(n)
           for n in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2",
                     "subln_gamma")}
    xf = x.astype("float64")
    proj = xf @ wq.T + bq
    onp.testing.assert_allclose(qkv.asnumpy(), proj, rtol=1e-4, atol=1e-4)
    q = proj[..., :h * d].reshape(2, s, h // 2, 2, d)
    k = proj[..., h * d:(h + hkv) * d].reshape(2, s, hkv // 2, 2, d)
    v = proj[..., (h + hkv) * d:].reshape(2, s, hkv // 2, 2 * d)
    i, j = onp.arange(s)[:, None], onp.arange(s)[None]
    live = (j <= i) & ((i - j < window) if window else True)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lamv = math.exp(lam["lambda_q1"] @ lam["lambda_k1"]) \
        - math.exp(lam["lambda_q2"] @ lam["lambda_k2"]) + lam0
    want = onp.zeros((2, s, h // 2, 2 * d))
    for b in range(2):
        for head in range(h // 2):
            pair = head // (h // hkv)
            maps = []
            for m in (0, 1):
                sc = q[b, :, head, m] @ k[b, :, pair, m].T / math.sqrt(d)
                sc = onp.where(live, sc, -onp.inf)
                p = onp.exp(sc - sc.max(-1, keepdims=True))
                maps.append(p / p.sum(-1, keepdims=True) @ v[b, :, pair])
            o = maps[0] - lamv * maps[1]
            o = o / onp.sqrt((o ** 2).mean(-1, keepdims=True) + 1e-5)
            want[b, :, head] = o * lam["subln_gamma"] * (1 - lam0)
    want = want.reshape(2, s, h * d) @ wo.T + bo
    assert _rel(out.asnumpy(), want) < 1e-4


@pytest.mark.parametrize("window", [None, 256], ids=["causal", "window"])
def test_flash_kernels_take_a_value_head_twice_the_query_head(window):
    """The flash kernels (interpreted) with v's heads 2 d wide, grouped 2
    to a key/value head, against the oracle's dense attention: the
    forward and the VJP of q, k and v, float32. Both the rectangle
    (causal) and a mask spec's visits (the window)."""
    from mxnet_tpu.kernels import flash_attention as fa

    b, h, hkv, s, d = 1, 4, 2, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, 2 * d))
    w = jax.random.normal(ks[3], (b, h, s, 2 * d))
    mask = None if window is None else fa.SlidingWindowMask(s, window)

    def loss(use, q, k, v):
        o = fa.flash_attention(q, k, v, causal=mask is None, mask=mask,
                               use_pallas=use)
        return jnp.sum(o * w), o

    (_, got), dgot = jax.value_and_grad(loss, (1, 2, 3), has_aux=True)(
        True, q, k, v)
    (_, want), dwant = jax.value_and_grad(loss, (1, 2, 3), has_aux=True)(
        False, q, k, v)
    assert got.shape == (b, h, s, 2 * d)
    assert _rel(got, want) < 1e-5
    for x, y in zip(dgot, dwant):
        assert x.shape == y.shape and _rel(x, y) < 1e-4
