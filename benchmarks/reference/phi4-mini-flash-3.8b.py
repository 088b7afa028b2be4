"""Phi-4-mini-flash-reasoning's decoder (SambaY) in plain jax.numpy: the
reference of configuration ``phi4-mini-flash-3.8b``.

Widths from microsoft/Phi-4-mini-flash-reasoning ``config.json``
(``model_type`` ``phi4flash``; Ren et al., arXiv:2507.06607). The layers
are the configuration's ``layers``, each a pre-norm block with LayerNorm
(weight and bias, eps ``layer_norm_eps``):

    n = LN(x; g1, b1);  h = x + mixer(n);  out = h + W2(up * silu(gate)),
    [gate | up] = W1 LN(h; g2, b2)

    mamba (d_inner Di = expand * E, state N, conv K, dt rank R):
      [u | z] = n W_in
      u = silu(causal depthwise conv_K(u) + b_conv)   (tap K-1 the own)
      [r | B | C] = u W_x;  delta = softplus(r W_dt + b_dt);  A = -exp(A_log)
      per channel c and state s, h_0 = 0, for t = 1..S:
          h_t[c, s] = exp(delta_t[c] A[c, s]) h_(t-1)[c, s]
                      + delta_t[c] B_t[s] u_t[c]
          y_t[c]    = sum_s C_t[s] h_t[c, s] + D[c] u_t[c]
      g = y * silu(z)  (the memory, for the GMUs after it);  mixer = g W_out
    gmu:    mixer = (memory * silu(n W_i)) W_o
    window, causal (differential attention; H query heads, Hkv key/value
    heads of d):
      [q | k | v] = n W_qkv + b_qkv (the shared set, for the cross layers)
      q: H/2 differential heads [q1 | q2]; k: Hkv/2 pairs [k1 | k2];
      v: Hkv/2 heads of 2d; differential head j reads pair j // (H/Hkv)
      A_i = softmax(q_i k_i^T / sqrt(d) where j <= i [and i - j < w])
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l)
      o = (1 - lambda_init) RMSNorm_2d(A_1 v - lambda A_2 v; gamma)
      mixer = concat(o) W_o + b_o
    cross:  the same with q = n W_q + b_q and k, v of the shared set, causal

then LN(x; gf, bf) and the head tied to the embedding; the loss is the
mean next-token cross-entropy over the vocabulary slice.

The scan is the recurrence position by position, as written, under
``lax.scan`` in blocks of ``T_ROWS`` positions, each block recomputed in
the backward pass; attention is dense in blocks of ``Q_ROWS`` query rows,
each recomputed; the loss in blocks of ``L_ROWS`` positions: so that
8,192 positions and their gradients fit beside the float32 weights and
Adam's state. ``lambda_init`` takes the index of the published layer each
layer stands for (``published_layer_index``). Every assumption is a key
under ``assumed`` in the configuration's file.

No kernel. Weights are (in, out); the convolution's weight is (channels,
K). Nothing of the program is imported.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from refcommon import Prec

Q_ROWS = 256      # query rows a block of attention takes
T_ROWS = 64       # positions a block of the scan takes
L_ROWS = 1024     # positions a block of the loss takes


def sizes(cfg):
    """(E, Di, N, K, R, H, Hkv, d, F)."""
    e = cfg["hidden_size"]
    return (e, cfg["mamba_expand"] * e, cfg["mamba_d_state"],
            cfg["mamba_d_conv"], cfg["mamba_dt_rank"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"])


def layers(cfg):
    return cfg["layers"][:cfg["num_hidden_layers"]]


def leaf_shapes(cfg):
    """Ordered {leaf: (shape, kind)} in the order the network is built."""
    e, di, n, k, r, h, hkv, d, f = sizes(cfg)
    out = {"embed.w": ((cfg["vocab_size"], e), "dense")}
    for i, kind in enumerate(layers(cfg)):
        p = f"l{i}"
        out[p + ".ln1.gamma"] = ((e,), "gamma")
        out[p + ".ln1.beta"] = ((e,), "zero")
        a = p + ".attn"
        if kind == "mamba":
            out[a + ".conv.w"] = ((di, k), "conv")
            out[a + ".conv.b"] = ((di,), "conv")
            out[a + ".a_log"] = ((di, n), "a_log")
            out[a + ".d"] = ((di,), "gamma")
            out[a + ".in.w"] = ((e, 2 * di), "dense")
            out[a + ".x.w"] = ((di, r + 2 * n), "dense")
            out[a + ".dt.w"] = ((r, di), "dense")
            out[a + ".dt.b"] = ((di,), "dt_bias")
            out[a + ".out.w"] = ((di, e), "dense")
        elif kind == "gmu":
            out[a + ".in.w"] = ((e, di), "dense")
            out[a + ".out.w"] = ((di, e), "dense")
        else:
            for lam in ("lq1", "lk1", "lq2", "lk2"):
                out[a + "." + lam] = ((d,), "lambda")
            out[a + ".subln"] = ((2 * d,), "gamma")
            width = (h + (0 if kind == "cross" else 2 * hkv)) * d
            out[a + ".qkv.w"] = ((e, width), "dense")
            out[a + ".qkv.b"] = ((width,), "zero")
            out[a + ".out.w"] = ((h * d, e), "dense")
            out[a + ".out.b"] = ((e,), "zero")
        out[p + ".ln2.gamma"] = ((e,), "gamma")
        out[p + ".ln2.beta"] = ((e,), "zero")
        out[p + ".mlp.fc1.w"] = ((e, 2 * f), "dense")
        out[p + ".mlp.fc2.w"] = ((f, e), "dense")
    out["lnf.gamma"] = ((e,), "gamma")
    out["lnf.beta"] = ((e,), "zero")
    return out


def init(cfg, key):
    """(params, aux): normal(0, init_std) matrices and embedding; unit
    gammas and D; zero biases; the Mamba initialiser's A_log = log(1..N)
    and dt bias (the inverse softplus of delta log-uniform in [1e-3,
    0.1]); the convolution uniform(-1/sqrt(K), 1/sqrt(K)); lambda vectors
    normal(0, lambda_init_std). No state leaves."""
    std = cfg["init_std"]
    params = {}
    for i, (name, (shape, kind)) in enumerate(leaf_shapes(cfg).items()):
        at = jax.random.fold_in(key, i)
        if kind == "dense":
            params[name] = std * jax.random.normal(at, shape, jnp.float32)
        elif kind == "gamma":
            params[name] = jnp.ones(shape, jnp.float32)
        elif kind == "zero":
            params[name] = jnp.zeros(shape, jnp.float32)
        elif kind == "lambda":
            params[name] = cfg["lambda_init_std"] * jax.random.normal(
                at, shape, jnp.float32)
        elif kind == "conv":
            bound = 1.0 / math.sqrt(cfg["mamba_d_conv"])
            params[name] = jax.random.uniform(at, shape, jnp.float32,
                                              -bound, bound)
        elif kind == "a_log":
            params[name] = jnp.log(jnp.broadcast_to(
                jnp.arange(1, shape[1] + 1, dtype=jnp.float32), shape))
        elif kind == "dt_bias":
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                at, shape, jnp.float32, math.log(1e-3), math.log(0.1))),
                1e-4)
            params[name] = dt + jnp.log(-jnp.expm1(-dt))
        else:
            raise ValueError(kind)
    return params, {}


def _ln(x, g, b, eps, prec):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return prec.store((xf - mu) * lax.rsqrt(var + eps) * g + b)


def _linear(x, params, name, prec):
    y = prec.matmul(x, params[name + ".w"])
    if name + ".b" in params:
        y = prec.store(y.astype(jnp.float32) + params[name + ".b"])
    return y


def selective_scan(u, delta, a, b, c):
    """y (B, S, Di) float32 of the recurrence, position by position: u,
    delta (B, S, Di), A (Di, N), B, C (B, S, N); the state float32."""
    bsz, s, di = u.shape
    rows = T_ROWS if s % T_ROWS == 0 else s

    def one(h, xs):
        ut, dt, bt, ct = xs
        h = jnp.exp(dt[..., None] * a) * h \
            + (dt * ut)[..., None] * bt[:, None, :]
        return h, jnp.sum(h * ct[:, None, :], axis=-1)

    @jax.checkpoint
    def block(h, xs):
        return lax.scan(one, h, xs)

    def by_blocks(x):       # (B, S, ...) -> (blocks, rows, B, ...)
        x = jnp.moveaxis(x.astype(jnp.float32), 1, 0)
        return x.reshape((s // rows, rows) + x.shape[1:])

    _, y = lax.scan(block, jnp.zeros((bsz, di, a.shape[1]), jnp.float32),
                    tuple(by_blocks(x) for x in (u, delta, b, c)))
    return jnp.moveaxis(y.reshape((s,) + y.shape[2:]), 0, 1)


def mamba(n, params, a, cfg, prec):
    """(the mixer's output, the gated scan output g) on the normed input
    ``n`` (B, S, E)."""
    e, di, nst, k, r = sizes(cfg)[:5]
    s = n.shape[1]
    xz = prec.matmul(n, params[a + ".in.w"])
    u, z = xz[..., :di], xz[..., di:]
    w = params[a + ".conv.w"]
    padded = jnp.pad(u.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    u = prec.store(jax.nn.silu(sum(padded[:, j:j + s] * w[:, j]
                                   for j in range(k))
                               + params[a + ".conv.b"]))
    xdbl = prec.matmul(u, params[a + ".x.w"])
    dt = _linear(xdbl[..., :r], params, a + ".dt", prec)
    delta = jax.nn.softplus(dt.astype(jnp.float32))
    y = selective_scan(u, delta, -jnp.exp(params[a + ".a_log"]),
                       xdbl[..., r:r + nst], xdbl[..., r + nst:])
    y = y + params[a + ".d"] * u.astype(jnp.float32)
    g = prec.store(y * jax.nn.silu(z.astype(jnp.float32)))
    return prec.matmul(g, params[a + ".out.w"]), g


def _maps(q1, q2, k1, k2, v, window, prec):
    """A_1 v and A_2 v for q (B, Hkv/2, G, S, d), k (B, Hkv/2, S, d), v
    (B, Hkv/2, S, 2d), causal (and i - j < window), in blocks of Q_ROWS
    query rows, each recomputed in the backward pass."""
    b, hp, g, s, d = q1.shape
    rows = Q_ROWS if s % Q_ROWS == 0 else s
    ko1, ko2, vo = prec.operand(k1), prec.operand(k2), prec.operand(v)

    def one_map(qb, ko, live):
        sc = prec.product(jnp.einsum(
            "bhgqd,bhkd->bhgqk", prec.operand(qb), ko, precision=prec.lax,
            preferred_element_type=jnp.float32)) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(live, sc, -jnp.inf), axis=-1)
        return prec.product(jnp.einsum(
            "bhgqk,bhkd->bhgqd", prec.operand(pr), vo, precision=prec.lax,
            preferred_element_type=jnp.float32))

    @jax.checkpoint
    def block(qb1, qb2, first):
        i = (first + jnp.arange(rows))[:, None]
        j = jnp.arange(s)[None, :]
        live = j <= i
        if window is not None:
            live = live & (i - j < window)
        return one_map(qb1, ko1, live), one_map(qb2, ko2, live)

    def split(q):
        return q.reshape(b, hp, g, s // rows, rows, d) \
            .transpose(3, 0, 1, 2, 4, 5)

    o1, o2 = lax.map(lambda x: block(*x), (split(q1), split(q2),
                                           jnp.arange(s // rows) * rows))
    back = lambda o: o.transpose(1, 2, 3, 0, 4, 5).reshape(  # noqa: E731
        b, hp, g, s, 2 * d)
    return back(o1), back(o2)


def diff_attention(n, qkv_src, params, a, cfg, prec, window, depth,
                   cross=False):
    """(the mixer's output, its q|k|v projection) on the normed input
    ``n``; ``qkv_src`` the shared set a cross layer reads."""
    e, h, hkv, d = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    b, s, _ = n.shape
    qkv = _linear(n, params, a + ".qkv", prec)
    src = qkv_src if cross else qkv
    hp, g = hkv // 2, h // hkv
    q = qkv[..., :h * d].reshape(b, s, hp, g, 2, d)
    q1, q2 = (q[..., i, :].transpose(0, 2, 3, 1, 4) for i in (0, 1))
    k = src[..., h * d:(h + hkv) * d].reshape(b, s, hp, 2, d)
    k1, k2 = (k[..., i, :].transpose(0, 2, 1, 3) for i in (0, 1))
    v = src[..., (h + hkv) * d:].reshape(b, s, hp, 2 * d).transpose(0, 2, 1, 3)
    o1, o2 = _maps(q1, q2, k1, k2, v, window, prec)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = jnp.exp(jnp.sum(params[a + ".lq1"] * params[a + ".lk1"])) \
        - jnp.exp(jnp.sum(params[a + ".lq2"] * params[a + ".lk2"])) + lam0
    o = o1.astype(jnp.float32) - lam * o2.astype(jnp.float32)
    ms = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    o = o * lax.rsqrt(ms + 1e-5) * params[a + ".subln"] * (1.0 - lam0)
    o = prec.store(o).reshape(b, h // 2, s, 2 * d).transpose(0, 2, 1, 3) \
        .reshape(b, s, h * d)
    return _linear(o, params, a + ".out", prec), qkv


def _layer(x, memory, kv, i, cfg, params, prec):
    p, kind = f"l{i}", layers(cfg)[i]
    eps, a = cfg["layer_norm_eps"], f"l{i}.attn"
    n = _ln(x, params[p + ".ln1.gamma"], params[p + ".ln1.beta"], eps, prec)
    if kind == "mamba":
        y, memory = mamba(n, params, a, cfg, prec)
    elif kind == "gmu":
        gate = jax.nn.silu(prec.matmul(n, params[a + ".in.w"])
                           .astype(jnp.float32))
        y = prec.matmul(prec.store(memory.astype(jnp.float32) * gate),
                        params[a + ".out.w"])
    else:
        window = cfg["sliding_window"] if kind == "window" else None
        depth = cfg["published_layer_index"][i]
        y, qkv = diff_attention(n, kv, params, a, cfg, prec, window, depth,
                                cross=kind == "cross")
        if kind != "cross":
            kv = qkv
    h = prec.store(x.astype(jnp.float32) + y.astype(jnp.float32))
    m = _ln(h, params[p + ".ln2.gamma"], params[p + ".ln2.beta"], eps, prec)
    f = cfg["intermediate_size"]
    up = prec.matmul(m, params[p + ".mlp.fc1.w"]).astype(jnp.float32)
    act = prec.store(jax.nn.silu(up[..., :f]) * up[..., f:])
    out = prec.matmul(act, params[p + ".mlp.fc2.w"])
    return prec.store(h.astype(jnp.float32) + out.astype(jnp.float32)), \
        memory, kv


def hidden(cfg, params, tokens, precision="float32"):
    """The final LayerNorm's output (B, S, E) for (B, S) token ids. Each
    layer is rematerialised in the backward pass."""
    prec = Prec(precision)
    x = prec.store(params["embed.w"][tokens])
    b, s = tokens.shape
    e, di = cfg["hidden_size"], sizes(cfg)[1]
    memory = jnp.zeros((b, s, di), prec.act)
    kv = jnp.zeros((b, s, (cfg["num_attention_heads"]
                           + 2 * cfg["num_key_value_heads"])
                    * cfg["head_dim"]), prec.act)
    for i in range(len(layers(cfg))):
        p = f"l{i}"
        sub = {k: v for k, v in params.items() if k.startswith(p + ".")}
        x, memory, kv = jax.checkpoint(
            lambda x, m, kv, sub, i=i: _layer(x, m, kv, i, cfg, sub, prec))(
            x, memory, kv, sub)
    return _ln(x, params["lnf.gamma"], params["lnf.beta"],
               cfg["layer_norm_eps"], prec), prec


def forward(cfg, params, aux, tokens, train, precision="float32"):
    """(logits (B, S, V) float32, aux) for (B, S) token ids."""
    x, prec = hidden(cfg, params, tokens, precision)
    return prec.matmul(x, params["embed.w"].T).astype(jnp.float32), aux


def loss(cfg, params, aux, batch, precision="float32"):
    """(mean next-token cross-entropy, aux) of one batch ``(tokens,
    tokens)``: position t predicts token t+1; the tied head and the
    log-softmax in blocks of ``L_ROWS`` positions, each recomputed in the
    backward pass."""
    tokens = jnp.asarray(batch[0], jnp.int32)
    x, prec = hidden(cfg, params, tokens, precision)
    b, s, e = x.shape
    rows = L_ROWS if s % L_ROWS == 0 else s
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    valid = (jnp.arange(s) < s - 1).astype(jnp.float32)

    @jax.checkpoint
    def block(xb, lb, vb):
        logits = prec.matmul(xb, params["embed.w"].T).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(lp, lb[..., None], axis=-1)[..., 0]
        return -jnp.sum(picked * vb)

    split = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape((b, s // rows, rows) + a.shape[2:]), 1, 0)
    sums = lax.map(lambda t: block(*t), (split(x), split(labels),
                                         split(jnp.broadcast_to(valid,
                                                                (b, s)))))
    return jnp.sum(sums) / (b * (s - 1)), aux
