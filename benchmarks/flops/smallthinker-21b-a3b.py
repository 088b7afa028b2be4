"""Operations and bytes of configuration ``smallthinker-21b-a3b``,
counted from its shapes.

A multiply-accumulate is two operations. Per position and layer: the
fused q|k|v projection and the output projection, the router, and the
held experts at the share that lands here (experts a token x held /
routed assignments a position, three products of E x F each). Attention
counts the live scores only, two products forward: ``S(S+1)/2`` pairs a
head of a global layer, ``w(w+1)/2 + (S-w)w`` of a window layer (a query
sees its own position and the w-1 before it). The head runs over the
vocabulary slice. Embedding lookups, norms, rotary positions, softmax,
routing and the optimizer are not counted. Training is three times
forward; recomputation is not counted.
"""
from __future__ import annotations

_ITEM = {"bfloat16": 2, "float32": 4}


def _sizes(cfg):
    return (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_ffn_hidden_size"])


def held_per_position(cfg):
    """Expected assignments a position sends to the experts held here."""
    return cfg["moe_num_active_primary_experts"] \
        * cfg["moe_num_primary_experts"] / cfg["router_experts"]


def live_pairs(seq, window=None):
    """Live (query, key) pairs of one sequence and head: causal, within
    ``window`` positions where one is given."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_pairs(cfg, seq):
    """Live pairs a head, layer by layer."""
    n = cfg["num_hidden_layers"]
    return [live_pairs(seq, cfg["sliding_window_size"] if w else None)
            for w in cfg["sliding_window_layout"][:n]]


def forward_flops_per_item(cfg, seq):
    """Model FLOPs of one token in a sequence of ``seq``."""
    e, d, hq, hkv, f = _sizes(cfg)
    position = 2 * e * (hq + 2 * hkv) * d + 2 * hq * d * e \
        + 2 * e * cfg["router_experts"] \
        + held_per_position(cfg) * 3 * 2 * e * f
    attention = sum(layer_pairs(cfg, seq)) / seq * hq * 2 * 2 * d
    return cfg["num_hidden_layers"] * position + attention \
        + 2 * e * cfg["vocab_size"]


def train_flops_per_item(cfg, traffic):
    return 3 * forward_flops_per_item(cfg, traffic["seq"])


def _attention(cfg, traffic, products, arrays):
    """(flops, bytes) a STEP's calls of one flash kernel need, all layers
    together: the live pairs only; q-sized arrays once per query head,
    k and v once per key/value head (the group reads them in place)."""
    e, d, hq, hkv, f = _sizes(cfg)
    seq, batch = traffic["seq"], traffic["batch_per_chip"]
    flops = batch * hq * sum(layer_pairs(cfg, seq)) * products * 2 * d
    item = _ITEM[cfg["train"]["compute_dtype"]]
    q_sized, kv_sized = seq * hq * d * item, seq * hkv * d * item
    calls = cfg["num_hidden_layers"] * batch
    return flops, calls * (arrays[0] * q_sized + arrays[1] * kv_sized)


def win_attn_fwd(cfg, traffic):
    """QK^T and PV over the live pairs; q in, o out, k and v in."""
    return _attention(cfg, traffic, 2, (2, 2))


def win_attn_bwd(cfg, traffic):
    """The fused backward's five products over the live pairs; q, do in
    and dq out, k, v in and dk, dv out."""
    return _attention(cfg, traffic, 5, (3, 4))


def flash_fwd_shape(cfg, traffic):
    """(batch*heads, positions, head_dim) of the flash kernels' q-sized
    arrays: ``attn_bwd_ms.tokens`` asks for it to tell a scan backward's
    ``while`` from others (this configuration's backward is the kernel,
    found by its name)."""
    return (traffic["batch_per_chip"] * cfg["num_attention_heads"],
            traffic["seq"], cfg["head_dim"])


def moe_gmm(cfg, traffic):
    """(flops, bytes) of a STEP's grouped products, whatever implements
    them: the expected rows (positions x experts a token x held /
    routed) through gate+up and down, forward and the two backward
    products of each; every product reads its two operands and writes
    its result once."""
    e, d, hq, hkv, f = _sizes(cfg)
    held = cfg["moe_num_primary_experts"]
    rows = traffic["seq"] * traffic["batch_per_chip"] \
        * held_per_position(cfg)
    item = _ITEM[cfg["train"]["compute_dtype"]]
    flops = nbytes = 0
    for k, n in ((e, 2 * f), (f, e)):
        flops += 3 * 2 * rows * k * n
        nbytes += 3 * item * (rows * k + held * k * n + rows * n)
    return cfg["num_hidden_layers"] * flops, cfg["num_hidden_layers"] * nbytes
