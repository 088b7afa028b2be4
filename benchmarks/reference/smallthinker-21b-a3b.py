"""SmallThinker-21BA3B-Instruct's decoder in plain jax.numpy: the
reference of configuration ``smallthinker-21b-a3b``.

Widths and the layer pattern from PowerInfer/SmallThinker-21BA3B-Instruct
``config.json`` (paper: SmallThinker, arXiv:2507.20984). For layer ``l``
with input ``x`` (S, E):

    n      = RMSNorm(x; g1)
    r      = softmax_f32(n W_r)                 (moe_primary_router_apply_softmax)
    idx    = top6(r);  gate = r[idx] / sum(r[idx])        (norm_topk_prob)
    q,k,v  = n W_q (28 x 128), n W_k (4 x 128), n W_v (4 x 128)
    if rope_layout[l] == 1: q, k = RoPE(q, k; theta 1.5e6, position i)
    live(i, j) = j <= i and (sliding_window_layout[l] == 0 or i - j < 4096)
    h      = x + softmax(q k^T / sqrt(128) where live) v W_o
    m      = RMSNorm(h; g2)
    y[t]   = sum over e in idx[t] of gate[t,e] * (relu(m W_gate,e) * (m W_up,e)) W_down,e
    out    = h + y

then a final RMSNorm and an untied head; the loss is the mean next-token
cross-entropy over the vocabulary slice. Query head h reads key/value
head h // 7. No q/k norm, no bias, no shared expert, no secondary
experts: the config has keys for none of them.

Assumed, each also a key under ``assumed`` in the configuration's file:
the router reads the *normed* layer input (the array attention's
projections read), before attention; the window holds the query's own
position and the 4,095 before it (``i - j < 4096``); rotate-half RoPE,
and a layer whose ``rope_layout`` is 0 carries no positions at all; ReGLU
experts; no auxiliary loss; weights normal(0, ``init_std``), gammas 1.

Departures, as the configuration states them: this chip's share.
``moe_num_primary_experts`` experts from ``experts_first`` on are held
and what the absent experts would add is left out (the partial sum goes
on to the next layer), exactly as the program is told to; ids, logits
and the loss are over the vocabulary slice; only the first
``num_hidden_layers`` entries of the two layouts are built.

No kernel and no routing buffer: attention is softmax(QK^T + mask)V in
blocks of query rows (so that 16,384 positions fit beside the state),
each block's mask made from its own row numbers; the experts are a plain
loop over the held ones with a 0 / gate weight per row. Weights are
(in, out); an expert's gate and up projections lie side by side in
``w13``. Nothing of the program is imported.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from refcommon import Prec, softmax_xent

Q_ROWS = 256      # query rows a block of attention takes


def leaf_shapes(cfg):
    """Ordered {leaf: (shape, kind)} in the order the network is built."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, held = cfg["moe_ffn_hidden_size"], cfg["moe_num_primary_experts"]
    out = {"embed.w": ((cfg["vocab_size"], e), "embed")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}"
        out[p + ".ln1.gamma"] = ((e,), "gamma")
        out[p + ".attn.qkv.w"] = ((e, (hq + 2 * hkv) * d), "dense")
        out[p + ".attn.out.w"] = ((hq * d, e), "dense")
        out[p + ".ln2.gamma"] = ((e,), "gamma")
        out[p + ".moe.router.w"] = ((e, cfg["router_experts"]), "dense")
        out[p + ".moe.w13"] = ((held, e, 2 * f), "dense")
        out[p + ".moe.w2"] = ((held, f, e), "dense")
        out[p + ".moe.rows"] = ((held,), "state")
    out["lnf.gamma"] = ((e,), "gamma")
    out["head.w"] = ((e, cfg["vocab_size"]), "dense")
    return out


def init(cfg, key):
    """(params, aux): normal(0, init_std) matrices and embeddings, unit
    gammas; the q and k columns of each fused projection times
    ``qk_init_scale`` (1 where the configuration has no such key); aux
    holds each layer's rows per held expert (zero)."""
    std = cfg["init_std"]
    qk = (cfg["num_attention_heads"] + cfg["num_key_value_heads"]) \
        * cfg["head_dim"]
    params, aux = {}, {}
    for i, (name, (shape, kind)) in enumerate(leaf_shapes(cfg).items()):
        if kind in ("embed", "dense"):
            params[name] = std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
            if name.endswith(".attn.qkv.w"):
                params[name] = params[name].at[:, :qk].multiply(
                    cfg.get("qk_init_scale", 1.0))
        elif kind == "gamma":
            params[name] = jnp.ones(shape, jnp.float32)
        else:
            aux[name] = jnp.zeros(shape, jnp.float32)
    return params, aux


def _rms(x, g, eps, prec):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return prec.store(xf * lax.rsqrt(ms + eps) * g)


def _rope(x, pos, theta, prec):
    """(B, H, S, D) at positions ``pos`` (S,), rotate-half."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    xf = x.astype(jnp.float32)
    half = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
    return prec.store(xf * cos + half * sin)


def live_mask(seq, window=None, rows=None):
    """(rows, seq) bool: may query i see key j? ``j <= i``, and
    ``i - j < window`` where the layer has one; ``rows`` are the queries'
    positions (all of them by default)."""
    i = (jnp.arange(seq) if rows is None else rows)[:, None]
    j = jnp.arange(seq)[None, :]
    live = j <= i
    if window is not None:
        live &= i - j < window
    return live


def _attention(q, k, v, window, prec):
    """softmax(q k^T / sqrt(d) where live) v for q (B, Hkv, G, S, D) and
    k, v (B, Hkv, S, D), in blocks of Q_ROWS query rows, each recomputed
    in the backward pass."""
    b, hkv, g, s, d = q.shape
    rows = Q_ROWS if s % Q_ROWS == 0 else s
    ko, vo = prec.operand(k), prec.operand(v)

    @jax.checkpoint
    def block(qb, first):
        mb = live_mask(s, window, first + jnp.arange(rows))
        sc = prec.product(jnp.einsum(
            "bhgqd,bhkd->bhgqk", prec.operand(qb), ko, precision=prec.lax,
            preferred_element_type=jnp.float32)) / (d ** 0.5)
        pr = jax.nn.softmax(jnp.where(mb, sc, -jnp.inf), axis=-1)
        return prec.store(prec.product(jnp.einsum(
            "bhgqk,bhkd->bhgqd", prec.operand(pr), vo, precision=prec.lax,
            preferred_element_type=jnp.float32)))

    qs = q.reshape(b, hkv, g, s // rows, rows, d).transpose(3, 0, 1, 2, 4, 5)
    out = lax.map(lambda a: block(*a), (qs, jnp.arange(s // rows) * rows))
    return out.transpose(1, 2, 3, 0, 4, 5).reshape(b, hkv, g, s, d)


def _experts(m, idx, gates, w13, w2, first, prec):
    """sum over the held experts of gate_e * (relu(m Wgate_e) * (m Wup_e))
    Wdown_e: a plain loop, every row through every held expert with the
    weight 0 where it was not routed there."""
    f = w2.shape[1]

    @jax.checkpoint
    def one(y, ew):
        e, a13, a2 = ew
        ge = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        h = prec.matmul(m, a13)
        act = prec.store(jax.nn.relu(h[..., :f].astype(jnp.float32))
                         * h[..., f:].astype(jnp.float32))
        return y + ge[..., None] * prec.matmul(act, a2).astype(
            jnp.float32), None

    held = first + jnp.arange(w13.shape[0])
    y, _ = lax.scan(one, jnp.zeros(m.shape, jnp.float32), (held, w13, w2))
    return prec.store(y)


def route(n, router_w, cfg, prec):
    """(idx, gates) of the layer's router on its normed input ``n``:
    softmax over all the routed experts in float32, the largest
    ``moe_num_active_primary_experts`` renormalised."""
    logits = prec.matmul(n, router_w)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, idx = lax.top_k(probs, cfg["moe_num_active_primary_experts"])
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return idx, gates


def _layer(x, i, cfg, params, prec):
    p = f"l{i}"
    b, s, e = x.shape
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    n = _rms(x, params[p + ".ln1.gamma"], eps, prec)
    idx, gates = route(n, params[p + ".moe.router.w"], cfg, prec)
    qkv = prec.matmul(n, params[p + ".attn.qkv.w"])
    q = qkv[..., :hq * d].reshape(b, s, hq, d).transpose(0, 2, 1, 3)
    k = qkv[..., hq * d:(hq + hkv) * d].reshape(b, s, hkv, d) \
        .transpose(0, 2, 1, 3)
    v = qkv[..., (hq + hkv) * d:].reshape(b, s, hkv, d)
    if cfg["rope_layout"][i]:
        pos = jnp.arange(s)
        q = _rope(q, pos, cfg["rope_theta"], prec)
        k = _rope(k, pos, cfg["rope_theta"], prec)
    window = cfg["sliding_window_size"] \
        if cfg["sliding_window_layout"][i] else None
    att = _attention(q.reshape(b, hkv, hq // hkv, s, d), k,
                     v.transpose(0, 2, 1, 3), window, prec)
    att = att.reshape(b, hq, s, d).transpose(0, 2, 1, 3).reshape(b, s, hq * d)
    h = prec.store(x + prec.matmul(att, params[p + ".attn.out.w"]))
    m = _rms(h, params[p + ".ln2.gamma"], eps, prec)
    first, held = cfg["experts_first"], cfg["moe_num_primary_experts"]
    moe = _experts(m, idx, gates, params[p + ".moe.w13"],
                   params[p + ".moe.w2"], first, prec)
    rows = jnp.sum((idx[..., None] == first + jnp.arange(held)),
                   axis=(0, 1, 2)).astype(jnp.float32)
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
