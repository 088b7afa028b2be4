"""Qwen3-Next-80B-A3B-Instruct's decoder in plain jax.numpy: the reference
of configuration ``qwen3-next-80b-a3b``.

Widths and the layer pattern from Qwen/Qwen3-Next-80B-A3B-Instruct
``config.json`` (``model_type`` ``qwen3_next``; the linear layer is Gated
DeltaNet, Yang et al., arXiv:2412.06464). Layer ``l`` is full attention
when ``(l + 1) % full_attention_interval == 0``, else linear. For layer
``l`` with input ``x`` (S, E):

    n = RMSNorm(x; g1)

    linear layer (Hk key heads, Hv value heads, dk, dv, convolution K):
      [q|k|v|z] = n W_qkvz                [b|a] = n W_ba
      [q|k|v]   = silu(causal depthwise conv1d, kernel K, no bias)
      beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)   (float32)
      q, k = q / ||q||_2, k / ||k||_2 over dk (eps 1e-6);  q *= dk^-0.5
      per value head (key head j serves value heads j*Hv/Hk onwards),
      state M (dk x dv), M_0 = 0, for t = 1..S:
          M   = exp(g_t) * M
          d_t = beta_t * (v_t - M^T k_t)
          M   = M + k_t d_t^T
          o_t = M^T q_t
      y = (RMSNorm(o over dv; w_o) * silu(z)) W_out
    full layer (Hq query heads over Hkv key/value heads of D):
      [q|gate] = n W_q (a head's 2D columns: its query, its gate)
      q, k = RMSNorm over D (g_q, g_k); RoPE (rotate-half) on the first
      partial_rotary_factor * D dims only
      y = (softmax(q k^T / sqrt(D) where j <= i) v * sigmoid(gate)) W_o
    h = x + y
    m = RMSNorm(h; g2)
    r = softmax_f32(m W_r);  idx = top10(r);  w = r[idx] / sum(r[idx])
    out = h + sum over e in idx of w_e * (silu(m W_gate,e) * (m W_up,e)) W_down,e
            + sigmoid(m w_sg) * (silu(m W_gate,s) * (m W_up,s)) W_down,s

then a final RMSNorm and an untied head; the loss is the mean next-token
cross-entropy over the vocabulary slice.

The linear layer is the recurrence position by position, as written: no
chunk, no triangular inverse, nothing of the program's algebra. It runs
under ``lax.scan`` in blocks of ``T_ROWS`` positions, each block
recomputed in the backward pass, so that 8,192 positions and their
gradients fit.

Departures and assumptions, each also a key under ``assumed`` in the
configuration's file:
- every RMSNorm but the gated one is zero-centred in the source
  (``x^ * (1 + w)``); this file and the program hold ``g = 1 + w``: the
  same function and the same Adam update.
- the column order of the two fused projections of a linear layer,
  [q|k|v|z] and [b|a], and of the full layer's [q,gate a head|k|v]:
  with weights from a seed any order is the same model.
- ``A_log`` = log of uniform(0, 16), ``dt_bias`` = 1 a value head, the
  gated norm's weight 1: the family's initialiser (the config has no key).
- no multi-token-prediction module, no auxiliary loss, no bias anywhere.
- this chip's share: ``num_experts`` experts from ``experts_first`` on
  are held and what the absent ones would add is left out; the shared
  expert is whole; ids, logits and the loss are over the vocabulary
  slice; only the first ``num_hidden_layers`` layers are built.

No kernel and no routing buffer. Weights are (in, out); an expert's gate
and up projections lie side by side in ``w13``; the convolution's weight
is (channels, K), tap K-1 the position's own. Nothing of the program is
imported.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from refcommon import Prec, softmax_xent

Q_ROWS = 256      # query rows a block of attention takes
T_ROWS = 64       # positions a block of the recurrence takes


def is_full(cfg, i):
    return (i + 1) % cfg["full_attention_interval"] == 0


def _linear_sizes(cfg):
    return (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])


def leaf_shapes(cfg):
    """Ordered {leaf: (shape, kind)} in the order the network is built."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hk, hv, dk, dv = _linear_sizes(cfg)
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    fs = cfg["shared_expert_intermediate_size"]
    out = {"embed.w": ((cfg["vocab_size"], e), "embed")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}"
        out[p + ".ln1.gamma"] = ((e,), "gamma")
        if is_full(cfg, i):
            out[p + ".attn.q_norm"] = ((d,), "gamma")
            out[p + ".attn.k_norm"] = ((d,), "gamma")
            out[p + ".attn.qkv.w"] = ((e, (2 * hq + 2 * hkv) * d), "dense")
            out[p + ".attn.out.w"] = ((hq * d, e), "dense")
        else:
            mixed = 2 * hk * dk + hv * dv
            out[p + ".attn.conv.w"] = (
                (mixed, cfg["linear_conv_kernel_dim"]), "dense")
            out[p + ".attn.a_log"] = ((hv,), "a_log")
            out[p + ".attn.dt_bias"] = ((hv,), "gamma")
            out[p + ".attn.norm.gamma"] = ((dv,), "gamma")
            out[p + ".attn.qkvz.w"] = ((e, mixed + hv * dv), "dense")
            out[p + ".attn.ba.w"] = ((e, 2 * hv), "dense")
            out[p + ".attn.out.w"] = ((hv * dv, e), "dense")
        out[p + ".ln2.gamma"] = ((e,), "gamma")
        out[p + ".moe.router.w"] = ((e, cfg["router_experts"]), "dense")
        out[p + ".moe.w13"] = ((held, e, 2 * f), "dense")
        out[p + ".moe.w2"] = ((held, f, e), "dense")
        out[p + ".moe.rows"] = ((held,), "state")
        out[p + ".moe.shared.gate.w"] = ((e, 1), "dense")
        out[p + ".moe.shared.w13"] = ((e, 2 * fs), "dense")
        out[p + ".moe.shared.w2"] = ((fs, e), "dense")
    out["lnf.gamma"] = ((e,), "gamma")
    out["head.w"] = ((e, cfg["vocab_size"]), "dense")
    return out


def init(cfg, key):
    """(params, aux): normal(0, init_std) matrices, embeddings and
    convolution taps; unit gammas (``dt_bias`` and the q and k norms'
    among them); ``A_log`` the log of uniform(0, 16); aux holds each
    layer's rows per held expert (zero)."""
    std = cfg["init_std"]
    params, aux = {}, {}
    for i, (name, (shape, kind)) in enumerate(leaf_shapes(cfg).items()):
        at = jax.random.fold_in(key, i)
        if kind in ("embed", "dense"):
            params[name] = std * jax.random.normal(at, shape, jnp.float32)
        elif kind == "gamma":
            params[name] = jnp.ones(shape, jnp.float32)
        elif kind == "a_log":
            # uniform(0, 16) is 0 once in 2**23 draws: its log would be
            # no number for Adam to move
            params[name] = jnp.log(jnp.maximum(jax.random.uniform(
                at, shape, jnp.float32, 0.0, 16.0), 1e-6))
        else:
            aux[name] = jnp.zeros(shape, jnp.float32)
    return params, aux


def _rms(x, g, eps, prec):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return prec.store(xf * lax.rsqrt(ms + eps) * g)


def rope(x, pos, theta, rotary, prec):
    """(B, H, S, D) at positions ``pos`` (S,): rotate-half on the first
    ``rotary`` of the D dimensions, the rest as they are."""
    inv = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    xf = x[..., :rotary].astype(jnp.float32)
    half = jnp.concatenate([-xf[..., rotary // 2:], xf[..., :rotary // 2]],
                           -1)
    return jnp.concatenate([prec.store(xf * cos + half * sin),
                            prec.store(x[..., rotary:])], -1)


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


def delta_rule(q, k, v, g, beta, prec):
    """The recurrence, position by position: q, k (B, H, S, dk), v
    (B, H, S, dv), g, beta (B, H, S) -> o (B, H, S, dv) float32. The
    state is float32; its three products (M^T k, the rank-one update,
    M^T q) take their operands as ``prec`` gives them."""
    b, h, s, dk = k.shape
    rows = T_ROWS if s % T_ROWS == 0 else s

    def dot(spec, x, y):
        return prec.product(jnp.einsum(
            spec, prec.operand(x), prec.operand(y), precision=prec.lax,
            preferred_element_type=jnp.float32))

    def one(m, x):
        qt, kt, vt, gt, bt = x
        m = jnp.exp(gt)[..., None, None] * m
        d = bt[..., None] * (vt - dot("bhkv,bhk->bhv", m, kt))
        m = m + dot("bhk,bhv->bhkv", kt, d)
        return m, dot("bhkv,bhk->bhv", m, qt)

    @jax.checkpoint
    def block(m, xs):
        return lax.scan(one, m, xs)

    def by_blocks(a):       # (B, H, S, ...) -> (blocks, rows, B, H, ...)
        a = jnp.moveaxis(a.astype(jnp.float32), 2, 0)
        return a.reshape((s // rows, rows) + a.shape[1:])

    _, o = lax.scan(block, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
                    tuple(by_blocks(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 2)


def linear_layer(n, params, p, cfg, prec):
    """The Gated DeltaNet mixer on the normed input ``n`` (B, S, E)."""
    hk, hv, dk, dv = _linear_sizes(cfg)
    b, s, _ = n.shape
    kd, taps = hk * dk, cfg["linear_conv_kernel_dim"]
    qkvz = prec.matmul(n, params[p + ".attn.qkvz.w"])
    ba = prec.matmul(n, params[p + ".attn.ba.w"]).astype(jnp.float32)
    mixed, z = qkvz[..., :2 * kd + hv * dv], qkvz[..., 2 * kd + hv * dv:]
    w = prec.store(params[p + ".attn.conv.w"]).astype(jnp.float32)
    padded = jnp.pad(mixed.astype(jnp.float32),
                     ((0, 0), (taps - 1, 0), (0, 0)))
    conv = jax.nn.silu(sum(padded[:, j:j + s] * w[:, j]
                           for j in range(taps)))

    def unit(a):
        return a * lax.rsqrt(jnp.sum(jnp.square(a), -1, keepdims=True)
                             + 1e-6)

    rep = hv // hk
    heads = lambda a, h, d: prec.store(  # noqa: E731
        a.reshape(b, s, h, d)).transpose(0, 2, 1, 3)
    q = heads(unit(conv[..., :kd].reshape(b, s, hk, dk)) * dk ** -0.5,
              hk, dk)
    k = heads(unit(conv[..., kd:2 * kd].reshape(b, s, hk, dk)), hk, dk)
    v = heads(conv[..., 2 * kd:], hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv]).transpose(0, 2, 1)
    a_log = prec.store(params[p + ".attn.a_log"]).astype(jnp.float32)
    dt_bias = prec.store(params[p + ".attn.dt_bias"]).astype(jnp.float32)
    g = (-jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)) \
        .transpose(0, 2, 1)
    o = delta_rule(jnp.repeat(q, rep, 1), jnp.repeat(k, rep, 1), v, g, beta,
                   prec)
    o = _rms(prec.store(o.transpose(0, 2, 1, 3)),
             params[p + ".attn.norm.gamma"], cfg["rms_norm_eps"], prec)
    o = prec.store(o.astype(jnp.float32) * jax.nn.silu(
        z.reshape(b, s, hv, dv).astype(jnp.float32)))
    return prec.matmul(o.reshape(b, s, hv * dv), params[p + ".attn.out.w"])


def full_layer(n, params, p, cfg, prec):
    """Gated softmax attention on the normed input ``n`` (B, S, E)."""
    b, s, _ = n.shape
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    qkv = prec.matmul(n, params[p + ".attn.qkv.w"])
    qg = qkv[..., :2 * hq * d].reshape(b, s, hq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(b, s, hq * d)
    k = qkv[..., 2 * hq * d:(2 * hq + hkv) * d].reshape(b, s, hkv, d)
    v = qkv[..., (2 * hq + hkv) * d:].reshape(b, s, hkv, d)
    q = _rms(q, params[p + ".attn.q_norm"], eps, prec).transpose(0, 2, 1, 3)
    k = _rms(k, params[p + ".attn.k_norm"], eps, prec).transpose(0, 2, 1, 3)
    rotary = int(round(cfg["partial_rotary_factor"] * d))
    pos = jnp.arange(s)
    q = rope(q, pos, cfg["rope_theta"], rotary, prec)
    k = rope(k, pos, cfg["rope_theta"], rotary, prec)
    att = _attention(q.reshape(b, hkv, hq // hkv, s, d), k,
                     v.transpose(0, 2, 1, 3), prec)
    att = att.reshape(b, hq, s, d).transpose(0, 2, 1, 3).reshape(b, s, hq * d)
    att = prec.store(att.astype(jnp.float32)
                     * jax.nn.sigmoid(gate.astype(jnp.float32)))
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


def route(m, router_w, cfg, prec):
    """(idx, gates) of the layer's router on the normed input of its
    experts: softmax over all the routed experts in float32, the largest
    ``num_experts_per_tok`` renormalised."""
    logits = prec.matmul(m, router_w)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, idx = lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return idx, gates


def moe_layer(m, params, p, cfg, prec, first=None, held=None, shared=True):
    """(the experts' part of the layer on its normed input ``m``, rows a
    held expert): the experts ``first`` .. ``first + held`` (the
    configuration's by default) and, with ``shared``, the shared one."""
    first = cfg["experts_first"] if first is None else first
    held = cfg["num_experts"] if held is None else held
    idx, gates = route(m, params[p + ".moe.router.w"], cfg, prec)
    y = _experts(m, idx, gates, params[p + ".moe.w13"],
                 params[p + ".moe.w2"], first, prec)
    if shared:
        gate = jax.nn.sigmoid(prec.matmul(
            m, params[p + ".moe.shared.gate.w"]).astype(jnp.float32))
        y = y + gate * _gated_ffn(m, params[p + ".moe.shared.w13"],
                                  params[p + ".moe.shared.w2"], prec)
    rows = jnp.sum((idx[..., None] == first + jnp.arange(held)),
                   axis=(0, 1, 2)).astype(jnp.float32)
    return prec.store(y), rows


def _layer(x, i, cfg, params, prec):
    p = f"l{i}"
    n = _rms(x, params[p + ".ln1.gamma"], cfg["rms_norm_eps"], prec)
    mixer = full_layer if is_full(cfg, i) else linear_layer
    h = prec.store(x + mixer(n, params, p, cfg, prec))
    m = _rms(h, params[p + ".ln2.gamma"], cfg["rms_norm_eps"], prec)
    moe, rows = moe_layer(m, params, p, cfg, prec)
    return prec.store(h + moe), rows


def forward(cfg, params, aux, tokens, train, precision="float32"):
    """(logits (B, S, V) float32, new aux) for (B, S) token ids. Each
    layer is rematerialised in the backward pass."""
    prec = Prec(precision)
    x = prec.store(params["embed.w"][tokens])
    new_aux = {}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}"
        sub = {k: v for k, v in params.items() if k.startswith(p + ".")}
        x, new_aux[p + ".moe.rows"] = jax.checkpoint(
            lambda x, sub, i=i: _layer(x, i, cfg, sub, prec))(x, sub)
    x = _rms(x, params["lnf.gamma"], cfg["rms_norm_eps"], prec)
    logits = prec.matmul(x, params["head.w"])
    return logits.astype(jnp.float32), new_aux


def loss(cfg, params, aux, batch, precision="float32"):
    """(mean next-token cross-entropy, aux) of one batch ``(tokens,
    tokens)``: position t predicts token t+1."""
    tokens = jnp.asarray(batch[0], jnp.int32)
    logits, new_aux = forward(cfg, params, aux, tokens, True, precision)
    return softmax_xent(logits[:, :-1], tokens[:, 1:]), new_aux
