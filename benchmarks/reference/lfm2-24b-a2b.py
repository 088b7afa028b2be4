"""LFM2-24B-A2B's decoder in plain jax.numpy: the reference of
configuration ``lfm2-24b-a2b``.

Widths and the layer pattern from LiquidAI/LFM2-24B-A2B ``config.json``
(``model_type`` ``lfm2_moe``); the equations of the conv, attention and
MLP layers as the dense family's ``transformers`` code has them
(``models/lfm2/modeling_lfm2.py``: ``Lfm2ShortConv.slow_forward``,
``Lfm2Attention``, ``Lfm2MLP``, ``Lfm2DecoderLayer``); the router as
DeepSeek-V3's auxiliary-loss-free design, whose keys the config carries
(arXiv:2412.19437 section 2.1.2). Layer ``l`` is attention where
``layer_types[l]`` is ``"full_attention"``, else a conv layer; the first
``num_dense_layers`` layers have a dense MLP, the rest experts. For
layer ``l`` with input ``x`` (S, E):

    n = RMSNorm(x; g1)

    conv layer (taps K = conv_L_cache):
      [B|C|x'] = n W_in;  u = B * x'
      z_t = sum_j w_j u_(t - K + 1 + j)       (causal, depthwise, no bias)
      y = (C * z) W_out
    attention layer (Hq query heads over Hkv key/value heads of D):
      [q|k|v] = n W_qkv;  q, k = RMSNorm over D (g_q, g_k);  RoPE
      (rotate-half, theta) on q and k
      y = softmax(q k^T / sqrt(D) where j <= i) v W_o
    h = x + y
    m = RMSNorm(h; g2)
    dense layer:   out = h + (silu(m W_1) * (m W_3)) W_2
    expert layer:  s = sigmoid(m W_r) (float32);  idx = top4(s + rate n);
                   w = s[idx] / sum(s[idx]) * routed_scaling_factor
                   out = h + sum over e in idx of
                         w_e * (silu(m W_gate,e) * (m W_up,e)) W_down,e
                   load_e = assignments of routed expert e over the
                   batch / their mean;  n <- n + sign(1 - load_e): the
                   bias b = rate n, kept in whole steps n

then a final RMSNorm and the head tied to the embedding; the loss is the
mean next-token cross-entropy over the vocabulary slice.

Departures and assumptions, each also a key under ``assumed`` in the
configuration's file:
- the score is the sigmoid and the bias moves at ``expert_bias_rate``
  (1e-3, DeepSeek-V3's): the config says ``use_expert_bias`` and gives
  no score function and no rate.
- the head is tied to the embedding (the family's default in
  ``configuration_lfm2.py``; the config row has no key).
- the column order of the fused projections: [B|C|x] as the source's
  ``chunk(3)``, [q|k|v], and an expert's [gate|up].
- this chip's share: ``num_experts`` experts from ``experts_first`` on
  are held and what the absent ones would add is left out; the router is
  ``router_experts`` wide and its bias and load are over all of them;
  ids, logits and the loss are over the vocabulary slice; only the first
  ``num_hidden_layers`` layers are built.

No kernel and no routing buffer. Weights are (in, out); an expert's and
a dense MLP's gate and up projections lie side by side in ``w13``; the
convolution's weight is (channels, K), tap K-1 the position's own.
Nothing of the program is imported.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from refcommon import Prec, softmax_xent

Q_ROWS = 256      # query rows a block of attention takes


def is_attention(cfg, i):
    return cfg["layer_types"][i] == "full_attention"


def is_dense(cfg, i):
    return i < cfg["num_dense_layers"]


def leaf_shapes(cfg):
    """Ordered {leaf: (shape, kind)} in the order the network is built."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    routed = cfg["router_experts"]
    out = {"embed.w": ((cfg["vocab_size"], e), "embed")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}"
        out[p + ".ln1.gamma"] = ((e,), "gamma")
        if is_attention(cfg, i):
            out[p + ".attn.q_norm"] = ((d,), "gamma")
            out[p + ".attn.k_norm"] = ((d,), "gamma")
            out[p + ".attn.qkv.w"] = ((e, (hq + 2 * hkv) * d), "dense")
            out[p + ".attn.out.w"] = ((hq * d, e), "dense")
        else:
            out[p + ".attn.conv.w"] = ((e, cfg["conv_L_cache"]), "dense")
            out[p + ".attn.in.w"] = ((e, 3 * e), "dense")
            out[p + ".attn.out.w"] = ((e, e), "dense")
        out[p + ".ln2.gamma"] = ((e,), "gamma")
        if is_dense(cfg, i):
            width = cfg["intermediate_size"]
            out[p + ".mlp.w13"] = ((e, 2 * width), "dense")
            out[p + ".mlp.w2"] = ((width, e), "dense")
        else:
            out[p + ".moe.router.w"] = ((e, routed), "dense")
            out[p + ".moe.w13"] = ((held, e, 2 * f), "dense")
            out[p + ".moe.w2"] = ((held, f, e), "dense")
            out[p + ".moe.rows"] = ((held,), "state")
            out[p + ".moe.bias"] = ((routed,), "state")
            out[p + ".moe.load"] = ((routed,), "state")
    out["lnf.gamma"] = ((e,), "gamma")
    return out


def init(cfg, key):
    """(params, aux): normal(0, init_std) matrices, embeddings and
    convolution taps; unit gammas; aux holds each expert layer's rows per
    held expert, selection bias and load (zero)."""
    std = cfg["init_std"]
    params, aux = {}, {}
    for i, (name, (shape, kind)) in enumerate(leaf_shapes(cfg).items()):
        at = jax.random.fold_in(key, i)
        if kind in ("embed", "dense"):
            params[name] = std * jax.random.normal(at, shape, jnp.float32)
        elif kind == "gamma":
            params[name] = jnp.ones(shape, jnp.float32)
        else:
            aux[name] = jnp.zeros(shape, jnp.float32)
    return params, aux


def _rms(x, g, eps, prec):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return prec.store(xf * lax.rsqrt(ms + eps) * g)


def rope(x, pos, theta, prec):
    """(B, H, S, D) at positions ``pos`` (S,): rotate-half over the
    whole head."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    xf = x.astype(jnp.float32)
    half = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
    return prec.store(xf * cos + half * sin)


def _attention(q, k, v, prec):
    """softmax(q k^T / sqrt(d) where j <= i) v for q (B, Hkv, G, S, D)
    and k, v (B, Hkv, S, D), in blocks of Q_ROWS query rows, each
    recomputed in the backward pass."""
    b, hkv, g, s, d = q.shape
    rows = Q_ROWS if s % Q_ROWS == 0 else s
    ko, vo = prec.operand(k), prec.operand(v)

    @jax.checkpoint
    def block(qb, first):
        live = jnp.arange(s)[None, :] <= (first + jnp.arange(rows))[:, None]
        sc = prec.product(jnp.einsum(
            "bhgqd,bhkd->bhgqk", prec.operand(qb), ko, precision=prec.lax,
            preferred_element_type=jnp.float32)) / (d ** 0.5)
        pr = jax.nn.softmax(jnp.where(live, sc, -jnp.inf), axis=-1)
        return prec.store(prec.product(jnp.einsum(
            "bhgqk,bhkd->bhgqd", prec.operand(pr), vo, precision=prec.lax,
            preferred_element_type=jnp.float32)))

    qs = q.reshape(b, hkv, g, s // rows, rows, d).transpose(3, 0, 1, 2, 4, 5)
    out = lax.map(lambda a: block(*a), (qs, jnp.arange(s // rows) * rows))
    return out.transpose(1, 2, 3, 0, 4, 5).reshape(b, hkv, g, s, d)


def conv_layer(n, params, p, cfg, prec):
    """The gated short convolution on the normed input ``n`` (B, S, E)."""
    b, s, e = n.shape
    taps = cfg["conv_L_cache"]
    bcx = prec.matmul(n, params[p + ".attn.in.w"]).astype(jnp.float32)
    u = bcx[..., :e] * bcx[..., 2 * e:]
    w = prec.store(params[p + ".attn.conv.w"]).astype(jnp.float32)
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    z = sum(padded[:, j:j + s] * w[:, j] for j in range(taps))
    y = prec.store(bcx[..., e:2 * e] * z)
    return prec.matmul(y, params[p + ".attn.out.w"])


def attention_layer(n, params, p, cfg, prec):
    """Causal grouped-query attention on the normed input ``n``."""
    b, s, _ = n.shape
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["norm_eps"]
    qkv = prec.matmul(n, params[p + ".attn.qkv.w"])
    q = qkv[..., :hq * d].reshape(b, s, hq, d)
    k = qkv[..., hq * d:(hq + hkv) * d].reshape(b, s, hkv, d)
    v = qkv[..., (hq + hkv) * d:].reshape(b, s, hkv, d)
    q = _rms(q, params[p + ".attn.q_norm"], eps, prec).transpose(0, 2, 1, 3)
    k = _rms(k, params[p + ".attn.k_norm"], eps, prec).transpose(0, 2, 1, 3)
    pos, theta = jnp.arange(s), cfg["rope_parameters"]["rope_theta"]
    q, k = rope(q, pos, theta, prec), rope(k, pos, theta, prec)
    att = _attention(q.reshape(b, hkv, hq // hkv, s, d), k,
                     v.transpose(0, 2, 1, 3), prec)
    att = att.reshape(b, hq, s, d).transpose(0, 2, 1, 3).reshape(b, s, hq * d)
    return prec.matmul(att, params[p + ".attn.out.w"])


def _gated_ffn(m, w13, w2, prec):
    """(silu(m W_gate) * (m W_up)) W_down, float32."""
    f = w2.shape[0]
    h = prec.matmul(m, w13)
    act = prec.store(jax.nn.silu(h[..., :f].astype(jnp.float32))
                     * h[..., f:].astype(jnp.float32))
    return prec.matmul(act, w2).astype(jnp.float32)


def _experts(m, idx, gates, w13, w2, first, prec):
    """sum over the held experts of gate_e * expert_e(m): a plain loop,
    every row through every held expert with the weight 0 where it was
    not routed there."""
    @jax.checkpoint
    def one(y, ew):
        e, a13, a2 = ew
        ge = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        return y + ge[..., None] * _gated_ffn(m, a13, a2, prec), None

    held = first + jnp.arange(w13.shape[0])
    y, _ = lax.scan(one, jnp.zeros(m.shape, jnp.float32), (held, w13, w2))
    return y


def route(m, router_w, bias, cfg, prec):
    """(idx, gates) of the layer's router on the normed input of its
    experts: sigmoid scores over all the routed experts in float32, the
    ``num_experts_per_tok`` largest of score plus ``bias`` (in the
    scores' units) chosen, their scores renormalised and scaled."""
    logits = prec.matmul(m, router_w)
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = lax.top_k(lax.stop_gradient(scores + bias),
                       cfg["num_experts_per_tok"])
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return idx, gates * cfg["routed_scaling_factor"]


def moe_layer(m, params, aux, p, cfg, prec, first=None, held=None):
    """(the experts' part of the layer on its normed input ``m``, its new
    state): the experts ``first`` .. ``first + held`` (the
    configuration's by default), the rows each got, the load of every
    routed expert and the selection bias after its update."""
    first = cfg["experts_first"] if first is None else first
    held = cfg["num_experts"] if held is None else held
    steps = aux[p + ".moe.bias"]
    idx, gates = route(m, params[p + ".moe.router.w"],
                       cfg["expert_bias_rate"] * steps, cfg, prec)
    y = _experts(m, idx, gates, params[p + ".moe.w13"],
                 params[p + ".moe.w2"], first, prec)
    rows = jnp.sum((idx[..., None] == first + jnp.arange(held)),
                   axis=(0, 1, 2)).astype(jnp.float32)
    count = jnp.sum(idx[..., None] == jnp.arange(cfg["router_experts"]),
                    axis=(0, 1, 2)).astype(jnp.float32)
    load = count / jnp.mean(count)
    return prec.store(y), {p + ".moe.rows": rows,
                           p + ".moe.bias": steps + jnp.sign(1.0 - load),
                           p + ".moe.load": load}


def _layer(x, i, cfg, params, aux, prec):
    p = f"l{i}"
    eps = cfg["norm_eps"]
    n = _rms(x, params[p + ".ln1.gamma"], eps, prec)
    mixer = attention_layer if is_attention(cfg, i) else conv_layer
    h = prec.store(x + mixer(n, params, p, cfg, prec))
    m = _rms(h, params[p + ".ln2.gamma"], eps, prec)
    if is_dense(cfg, i):
        y = prec.store(_gated_ffn(m, params[p + ".mlp.w13"],
                                  params[p + ".mlp.w2"], prec))
        return prec.store(h + y), {}
    y, state = moe_layer(m, params, aux, p, cfg, prec)
    return prec.store(h + y), state


def forward(cfg, params, aux, tokens, train, precision="float32"):
    """(logits (B, S, V) float32, new aux) for (B, S) token ids. Each
    layer is rematerialised in the backward pass."""
    prec = Prec(precision)
    x = prec.store(params["embed.w"][tokens])
    new_aux = {}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}"
        sub = {k: v for k, v in params.items() if k.startswith(p + ".")}
        state = {k: v for k, v in aux.items() if k.startswith(p + ".")}
        x, got = jax.checkpoint(
            lambda x, sub, state, i=i: _layer(x, i, cfg, sub, state, prec))(
            x, sub, state)
        new_aux.update(got)
    x = _rms(x, params["lnf.gamma"], cfg["norm_eps"], prec)
    logits = prec.matmul(x, params["embed.w"].T)
    return logits.astype(jnp.float32), new_aux


def loss(cfg, params, aux, batch, precision="float32"):
    """(mean next-token cross-entropy, aux) of one batch ``(tokens,
    tokens)``: position t predicts token t+1."""
    tokens = jnp.asarray(batch[0], jnp.int32)
    logits, new_aux = forward(cfg, params, aux, tokens, True, precision)
    return softmax_xent(logits[:, :-1], tokens[:, 1:]), new_aux
