"""Operations and bytes of configuration ``phi4-mini-flash-3.8b``, counted
from its shapes.

A multiply-accumulate is two operations. Per position: a Mamba layer's
in_proj, x_proj, dt_proj and out_proj, its convolution (one
multiply-accumulate a tap and channel) and the selective scan (below); a
GMU's two projections; a self-attention layer's q|k|v projection and a
cross layer's q projection, each with the output projection, and the two
maps of every differential head over its live pairs: two score products
at the head size d and two value products at 2d, 12 d a pair (causal,
``S(S+1)/2`` pairs a head; the window ``w(w+1)/2 + (S - w) w``); every
layer's SwiGLU MLP; the tied head over the vocabulary slice. Embedding
lookups, norms, softmax, lambda, gates and the optimizer are not counted.
Training is three times forward; recomputation is not counted.

The scan is counted by its own work, whatever implements it: per position,
channel and state the decay's product and exponential, the decayed
state, ``B (delta u)`` and the sum, and the read-out's multiply-add: 7
forward, and backward twice that and the forward again. Its bytes are u,
delta (float32), z, B and C read once and g written once a position,
and backward the same with g's cotangent in and the five cotangents of
u, delta, z, B, C out. No chunk, stored state or recomputed state enters
either count.
"""
from __future__ import annotations

_ITEM = {"bfloat16": 2, "float32": 4}
_SCAN_OPS = 7


def _sizes(cfg):
    e = cfg["hidden_size"]
    return (e, cfg["mamba_expand"] * e, cfg["mamba_d_state"],
            cfg["mamba_d_conv"], cfg["mamba_dt_rank"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"])


def kinds(cfg):
    return cfg["layers"][:cfg["num_hidden_layers"]]


def live_pairs(kind, seq, window):
    """Live (query, key) pairs of one head of a layer of ``kind``."""
    if kind == "window" and window < seq:
        return window * (window + 1) // 2 + (seq - window) * window
    return seq * (seq + 1) // 2


def scan_flops_per_position(cfg):
    _, di, n = _sizes(cfg)[:3]
    return _SCAN_OPS * di * n


def forward_flops_per_item(cfg, seq):
    """Model FLOPs of one token in a sequence of ``seq``."""
    e, di, n, k, r, h, hkv, d, f = _sizes(cfg)
    mlp = 2 * e * 2 * f + 2 * f * e
    per_kind = {
        "mamba": 2 * e * 2 * di + 2 * k * di + 2 * di * (r + 2 * n)
        + 2 * r * di + scan_flops_per_position(cfg) + 2 * di * e,
        "gmu": 2 * 2 * e * di,
    }
    total = 2 * e * cfg["vocab_size"]
    for kind in kinds(cfg):
        if kind in per_kind:
            total += per_kind[kind]
        else:
            width = h + (0 if kind == "cross" else 2 * hkv)
            pairs = live_pairs(kind, seq, cfg["sliding_window"])
            total += 2 * e * width * d + 2 * h * d * e \
                + h // 2 * pairs / seq * 12 * d
        total += mlp
    return total


def train_flops_per_item(cfg, traffic):
    return 3 * forward_flops_per_item(cfg, traffic["seq"])


def _scan(cfg, traffic, passes, gradients):
    """(flops, bytes) of a STEP's Mamba layers' scans: ``passes`` times
    the forward's operations; u, z, B, C in the compute dtype and delta
    float32 in, g out, and with ``gradients`` g's cotangent in and the
    five cotangents out."""
    _, di, n = _sizes(cfg)[:3]
    positions = traffic["seq"] * traffic["batch_per_chip"]
    item = _ITEM[cfg["train"]["compute_dtype"]]
    ins = di * (2 * item + 4) + 2 * n * item
    nbytes = ins + di * item + (ins if gradients else 0)
    layers = sum(1 for kind in kinds(cfg) if kind == "mamba")
    return (layers * positions * passes * scan_flops_per_position(cfg),
            layers * positions * nbytes)


def ssm_scan_fwd(cfg, traffic):
    """The scan forward: u, delta, z, B, C in, g out."""
    return _scan(cfg, traffic, 1, False)


def ssm_scan_bwd(cfg, traffic):
    """Its backward: twice the forward's operations and the forward
    again; the forward's inputs and g's cotangent in, five cotangents
    out."""
    return _scan(cfg, traffic, 3, True)


def _attention(cfg, traffic, products, arrays):
    """(flops, bytes) a STEP's calls of one flash kernel need, the three
    differential layers together, two calls a layer (one a map): the live
    pairs only, of h/2 query heads over h_kv/2 key/value heads, q and k at
    the head size d and v at 2d. ``products`` are (at d, at 2d) a pair;
    ``arrays`` (at d, at 2d) the q-sized arrays, once per query head, and
    the k- or v-sized ones, once per key/value head (the group reads them
    in place)."""
    d, h, hkv = cfg["head_dim"], cfg["num_attention_heads"] // 2, \
        cfg["num_key_value_heads"] // 2
    seq, batch = traffic["seq"], traffic["batch_per_chip"]
    item = _ITEM[cfg["train"]["compute_dtype"]]
    flops = nbytes = 0
    for kind in kinds(cfg):
        if kind in ("mamba", "gmu"):
            continue
        pairs = live_pairs(kind, seq, cfg["sliding_window"])
        calls = 2 * batch
        flops += calls * h * pairs * 2 * (products[0] * d
                                          + products[1] * 2 * d)
        nbytes += calls * seq * item * (
            h * (arrays[0][0] * d + arrays[0][1] * 2 * d)
            + hkv * (arrays[1][0] * d + arrays[1][1] * 2 * d))
    return flops, nbytes


def win_attn_fwd(cfg, traffic):
    """QK^T at d and PV at 2d over the live pairs; q in and o out, k and
    v in."""
    return _attention(cfg, traffic, (1, 1), ((1, 1), (1, 1)))


def win_attn_bwd(cfg, traffic):
    """The fused backward's five products over the live pairs: QK^T, dS K
    and dS^T Q at d, dO V^T and P^T dO at 2d; q, dq and do, k, dk and v,
    dv."""
    return _attention(cfg, traffic, (3, 2), ((2, 1), (2, 2)))


def flash_fwd_shape(cfg, traffic):
    """(batch*heads, positions, head_dim) of the flash kernels' q-sized
    arrays, for a reader that tells a scan backward's ``while`` from
    others: 20 differential heads a map, each call at the head size."""
    return (traffic["batch_per_chip"] * cfg["num_attention_heads"] // 2,
            traffic["seq"], cfg["head_dim"])
