"""OPT's decoder in plain jax.numpy: the reference of configuration
``opt-1.3b``.

Zhang et al. 2022 (arXiv:2205.01068); widths from facebook/opt-1.3b
``config.json``. Learned positions, pre-LayerNorm blocks, multi-head
causal attention, ReLU MLP, output head tied to the embedding. The three
departures of the repo's block from OPT that the configuration lists
under ``assumed`` are followed here, because the reference states what
the configuration states: no bias on q/k/v/out, embeddings scaled by
sqrt(E), positions start at 0. Attention is the textbook softmax(QK^T)V
with a mask: no kernel, no blocking. Weights are (in, out). Nothing of
the program is imported.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from refcommon import Prec, softmax_xent

LN_EPS = 1e-5


def leaf_shapes(cfg):
    """Ordered {leaf: (shape, kind)} in the order the network is built."""
    e, f = cfg["hidden_size"], cfg["ffn_dim"]
    out = {"embed.w": ((cfg["vocab_size"], e), "embed"),
           "pos.w": ((cfg["max_position_embeddings"], e), "embed")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}"
        out[p + ".ln1.gamma"] = ((e,), "gamma")
        out[p + ".ln1.beta"] = ((e,), "beta")
        out[p + ".qkv.w"] = ((e, 3 * e), "dense")
        out[p + ".out.w"] = ((e, e), "dense")
        out[p + ".ln2.gamma"] = ((e,), "gamma")
        out[p + ".ln2.beta"] = ((e,), "beta")
        out[p + ".ffn1.w"] = ((e, f), "dense")
        out[p + ".ffn1.b"] = ((f,), "bias")
        out[p + ".ffn2.w"] = ((f, e), "dense")
        out[p + ".ffn2.b"] = ((e,), "bias")
    out["lnf.gamma"] = ((e,), "gamma")
    out["lnf.beta"] = ((e,), "beta")
    return out


def init(cfg, key):
    """(params, aux): normal(0, init_std) matrices and embeddings, unit
    gammas, zero betas and biases. No state besides the parameters."""
    std = cfg["init_std"]
    params = {}
    for i, (name, (shape, kind)) in enumerate(leaf_shapes(cfg).items()):
        if kind in ("embed", "dense"):
            params[name] = std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        elif kind == "gamma":
            params[name] = jnp.ones(shape, jnp.float32)
        else:
            params[name] = jnp.zeros(shape, jnp.float32)
    return params, {}


def _ln(x, g, b, prec):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return prec.store((xf - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b)


def _layer(x, p, cfg, params, prec):
    b, s, e = x.shape
    h = cfg["num_attention_heads"]
    d = e // h
    qkv = prec.matmul(_ln(x, params[p + ".ln1.gamma"],
                          params[p + ".ln1.beta"], prec), params[p + ".qkv.w"])
    qkv = qkv.reshape(b, s, 3, h, d).transpose(2, 0, 3, 1, 4)
    q, k, v = qkv[0], qkv[1], qkv[2]
    scores = prec.product(jnp.einsum(
        "bhqd,bhkd->bhqk", prec.operand(q), prec.operand(k),
        precision=prec.lax, preferred_element_type=jnp.float32)) / (d ** 0.5)
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    att = prec.product(jnp.einsum(
        "bhqk,bhkd->bhqd", prec.operand(probs), prec.operand(v),
        precision=prec.lax, preferred_element_type=jnp.float32))
    att = prec.store(att).transpose(0, 2, 1, 3).reshape(b, s, e)
    x = prec.store(x + prec.matmul(att, params[p + ".out.w"]))
    y = _ln(x, params[p + ".ln2.gamma"], params[p + ".ln2.beta"], prec)
    y = jax.nn.relu(prec.store(prec.matmul(y, params[p + ".ffn1.w"])
                               + params[p + ".ffn1.b"].astype(prec.act)))
    y = prec.matmul(y, params[p + ".ffn2.w"]) \
        + params[p + ".ffn2.b"].astype(prec.act)
    return prec.store(x + y)


def forward(cfg, params, aux, tokens, train, precision="float32"):
    """Logits (float32) for (batch, sequence) token ids. Each layer is
    rematerialised in the backward pass so that float32 at the timed
    size fits one chip."""
    prec = Prec(precision)
    _, s = tokens.shape
    e = cfg["hidden_size"]
    x = params["embed.w"][tokens] * (e ** 0.5) + params["pos.w"][:s][None]
    x = prec.store(x)
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}"
        sub = {k: v for k, v in params.items() if k.startswith(p + ".")}
        x = jax.checkpoint(
            lambda x, sub, p=p: _layer(x, p, cfg, sub, prec))(x, sub)
    x = _ln(x, params["lnf.gamma"], params["lnf.beta"], prec)
    logits = prec.matmul(x, params["embed.w"].T)
    return logits.astype(jnp.float32), dict(aux)


def loss(cfg, params, aux, batch, precision="float32"):
    """(mean next-token cross-entropy, aux) of one batch ``(tokens,
    tokens)``: position t predicts token t+1."""
    tokens = batch[0].astype(jnp.int32)
    logits, new_aux = forward(cfg, params, aux, tokens, True, precision)
    return softmax_xent(logits[:, :-1], tokens[:, 1:]), new_aux
