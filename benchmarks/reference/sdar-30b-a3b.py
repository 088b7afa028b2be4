"""SDAR-30B-A3B's decoder under block-diffusion training in plain
jax.numpy: the reference of configuration ``sdar-30b-a3b``.

Widths from JetLM/SDAR-30B-A3B-Chat ``config.json`` (``model_type``
``sdar_moe``): pre-RMSNorm blocks, 32 query heads reading 4 key/value
heads of 128 (query head h reads head h // 8), a per-head RMSNorm on q
and k, rotary positions (rotate-half, theta 1e6), no bias, and for every
layer's MLP a mixture of SiLU-gated experts: the router's softmax over
all ``router_experts`` in float32, the ``num_experts_per_tok`` largest
renormalised, no shared expert, no auxiliary loss; a final RMSNorm and
an untied head. This chip's share is stated by the configuration:
``num_experts`` experts from ``experts_first`` on are held, and what the
absent experts would add is left out (the partial sum goes on to the
next layer), exactly as the program is told to.

Training is block diffusion (BD3-LM, Arriola et al. 2025,
arXiv:2503.09573): the input is ``[xt ; x0]``, L noised then L clean
tokens, copy i of either half at position i mod L, under a dense boolean
mask built from the three rules; the head and the loss see the noised
half only, each masked position's own logits against its clean token,
times 1/t of its block, summed and divided by B * L.

No kernel and no routing buffer: attention is softmax(QK^T + mask)V in
blocks of query rows (so that 16,384 positions fit beside the state),
the experts are a plain loop over the held ones with a 0 / gate weight
per row. Weights are (in, out); an expert's gate and up projections lie
side by side in ``w13``. Nothing of the program is imported.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from refcommon import Prec

Q_ROWS = 256      # query rows a block of attention takes


def leaf_shapes(cfg):
    """Ordered {leaf: (shape, kind)} in the order the network is built."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    out = {"embed.w": ((cfg["vocab_size"], e), "embed")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}"
        out[p + ".ln1.gamma"] = ((e,), "gamma")
        out[p + ".attn.q_norm"] = ((d,), "qk_gamma")
        out[p + ".attn.k_norm"] = ((d,), "qk_gamma")
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
    gammas but for the q and k norms' (``qk_norm_init``, 1 where the
    configuration has no such key); aux holds each layer's rows per held
    expert (zero)."""
    std = cfg["init_std"]
    params, aux = {}, {}
    for i, (name, (shape, kind)) in enumerate(leaf_shapes(cfg).items()):
        if kind in ("embed", "dense"):
            params[name] = std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        elif kind == "gamma":
            params[name] = jnp.ones(shape, jnp.float32)
        elif kind == "qk_gamma":
            params[name] = jnp.full(shape, cfg.get("qk_norm_init", 1.0),
                                    jnp.float32)
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


def live_mask(seq, block):
    """(2L, 2L) bool: may query i see key j? The three rules."""
    i = jnp.arange(2 * seq)
    noisy, blk = i < seq, (i % seq) // block
    qn, kn = noisy[:, None], noisy[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return (qn & kn & (qb == kb)) | (qn & ~kn & (kb < qb)) \
        | (~qn & ~kn & (kb <= qb))


def _attention(q, k, v, mask, prec):
    """softmax(q k^T / sqrt(d) + mask) v for q (B, Hkv, G, S, D) and k, v
    (B, Hkv, S, D), in blocks of Q_ROWS query rows, each recomputed in
    the backward pass."""
    b, hkv, g, s, d = q.shape
    rows = Q_ROWS if s % Q_ROWS == 0 else s
    ko, vo = prec.operand(k), prec.operand(v)

    @jax.checkpoint
    def block(qb, mb):
        sc = prec.product(jnp.einsum(
            "bhgqd,bhkd->bhgqk", prec.operand(qb), ko, precision=prec.lax,
            preferred_element_type=jnp.float32)) / (d ** 0.5)
        pr = jax.nn.softmax(jnp.where(mb, sc, -jnp.inf), axis=-1)
        return prec.store(prec.product(jnp.einsum(
            "bhgqk,bhkd->bhgqd", prec.operand(pr), vo, precision=prec.lax,
            preferred_element_type=jnp.float32)))

    qs = q.reshape(b, hkv, g, s // rows, rows, d).transpose(3, 0, 1, 2, 4, 5)
    out = lax.map(lambda a: block(*a), (qs, mask.reshape(s // rows, rows, s)))
    return out.transpose(1, 2, 3, 0, 4, 5).reshape(b, hkv, g, s, d)


def _experts(w, idx, gates, w13, w2, first, prec):
    """sum over the held experts of gate_e * (silu(w Wgate_e) * (w Wup_e))
    Wdown_e: a plain loop, every row through every held expert with the
    weight 0 where it was not routed there."""
    f = w2.shape[1]

    @jax.checkpoint
    def one(y, ew):
        e, a13, a2 = ew
        ge = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        h = prec.matmul(w, a13)
        act = prec.store(jax.nn.silu(h[..., :f].astype(jnp.float32))
                         * h[..., f:].astype(jnp.float32))
        return y + ge[..., None] * prec.matmul(act, a2).astype(
            jnp.float32), None

    held = first + jnp.arange(w13.shape[0])
    y, _ = lax.scan(one, jnp.zeros(w.shape, jnp.float32), (held, w13, w2))
    return prec.store(y)


def _layer(x, p, cfg, params, pos, mask, prec):
    b, s, e = x.shape
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    qkv = prec.matmul(_rms(x, params[p + ".ln1.gamma"], eps, prec),
                      params[p + ".attn.qkv.w"])
    q = qkv[..., :hq * d].reshape(b, s, hq, d)
    k = qkv[..., hq * d:(hq + hkv) * d].reshape(b, s, hkv, d)
    v = qkv[..., (hq + hkv) * d:].reshape(b, s, hkv, d)
    q = _rope(_rms(q, params[p + ".attn.q_norm"], eps, prec)
              .transpose(0, 2, 1, 3), pos, cfg["rope_theta"], prec)
    k = _rope(_rms(k, params[p + ".attn.k_norm"], eps, prec)
              .transpose(0, 2, 1, 3), pos, cfg["rope_theta"], prec)
    att = _attention(q.reshape(b, hkv, hq // hkv, s, d), k,
                     v.transpose(0, 2, 1, 3), mask, prec)
    att = att.reshape(b, hq, s, d).transpose(0, 2, 1, 3).reshape(b, s, hq * d)
    h1 = prec.store(x + prec.matmul(att, params[p + ".attn.out.w"]))
    w = _rms(h1, params[p + ".ln2.gamma"], eps, prec)
    logits = prec.matmul(w, params[p + ".moe.router.w"])
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, idx = lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    first, held = cfg["experts_first"], cfg["num_experts"]
    moe = _experts(w, idx, gates, params[p + ".moe.w13"],
                   params[p + ".moe.w2"], first, prec)
    rows = jnp.sum((idx[..., None] == first + jnp.arange(held)),
                   axis=(0, 1, 2)).astype(jnp.float32)
    return prec.store(h1 + moe), rows


def forward(cfg, params, aux, tokens, train, precision="float32"):
    """(logits (B, L, V) float32 of the noised half, new aux) for
    (B, 2L) token ids ``[xt ; x0]``. Each layer is rematerialised in the
    backward pass."""
    prec = Prec(precision)
    seq = tokens.shape[1] // 2
    pos = jnp.arange(2 * seq) % seq
    mask = live_mask(seq, cfg["block_length"])
    x = prec.store(params["embed.w"][tokens])
    new_aux = {}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}"
        sub = {k: v for k, v in params.items() if k.startswith(p + ".")}
        x, new_aux[p + ".moe.rows"] = jax.checkpoint(
            lambda x, sub, p=p: _layer(x, p, cfg, sub, pos, mask, prec))(
            x, sub)
    x = _rms(x[:, :seq], params["lnf.gamma"], cfg["rms_norm_eps"], prec)
    logits = prec.matmul(x, params["head.w"])
    return logits.astype(jnp.float32), new_aux


def loss(cfg, params, aux, batch, precision="float32"):
    """(weighted cross-entropy over the masked positions / (B * L), aux)
    of one batch ``(x (B, 2L) ids, y (B, 2, L))``: ``y[:, 0]`` the clean
    tokens, ``y[:, 1]`` each position's weight, 1/t where it was masked
    and 0 elsewhere. No shift: position i predicts its own token."""
    x, y = batch
    logits, new_aux = forward(cfg, params, aux, jnp.asarray(x, jnp.int32),
                              True, precision)
    y = jnp.asarray(y, jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, y[:, 0].astype(jnp.int32)[..., None], axis=-1)[..., 0]
    return -jnp.sum(picked * y[:, 1]) / picked.size, new_aux
