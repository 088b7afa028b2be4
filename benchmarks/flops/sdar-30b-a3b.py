"""Operations and bytes of configuration ``sdar-30b-a3b``, counted from
its shapes.

A multiply-accumulate is two operations. A clean token passes every
layer twice (its noised and its clean copy). Per position and layer: the
fused q|k|v projection and the output projection, the router, and the
held experts at the share that lands here (``num_experts_per_tok`` x
held / routed assignments a position, three products of E x F each).
Attention counts the live scores only: ``L^2 + L*b`` pairs a sequence of
the ``4 L^2``, two products forward. The head runs once per clean token,
over the vocabulary slice. Embedding lookups, norms, rotary positions,
softmax, routing and the optimizer are not counted. Training is three
times forward; recomputation is not counted.
"""
from __future__ import annotations

_ITEM = {"bfloat16": 2, "float32": 4}


def _sizes(cfg):
    return (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_intermediate_size"])


def held_per_position(cfg):
    """Expected assignments a position sends to the experts held here."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]


def live_pairs(seq, block):
    """Live (query, key) pairs of one sequence under the mask."""
    return seq * seq + seq * block


def forward_flops_per_item(cfg, seq):
    """Model FLOPs of one clean token in a sequence of ``seq``."""
    e, d, hq, hkv, f = _sizes(cfg)
    position = 2 * e * (hq + 2 * hkv) * d + 2 * hq * d * e \
        + 2 * e * cfg["router_experts"] \
        + held_per_position(cfg) * 3 * 2 * e * f
    attention = live_pairs(seq, cfg["block_length"]) / seq * hq * 2 * 2 * d
    return cfg["num_hidden_layers"] * (2 * position + attention) \
        + 2 * e * cfg["vocab_size"]


def train_flops_per_item(cfg, traffic):
    return 3 * forward_flops_per_item(cfg, traffic["seq"])


def _attention(cfg, traffic, products, out_arrays):
    """(flops, bytes) a STEP's calls of one flash kernel need: the live
    pairs only; q-sized arrays once per query head, k and v once per
    key/value head (the group reads them in place)."""
    e, d, hq, hkv, f = _sizes(cfg)
    seq, batch = traffic["seq"], traffic["batch_per_chip"]
    calls = cfg["num_hidden_layers"] * batch
    flops = calls * hq * live_pairs(seq, cfg["block_length"]) \
        * products * 2 * d
    item = _ITEM[cfg["train"]["compute_dtype"]]
    q_sized, kv_sized = 2 * seq * hq * d * item, 2 * seq * hkv * d * item
    return flops, calls * (out_arrays[0] * q_sized + out_arrays[1] * kv_sized)


def bd_attn_fwd(cfg, traffic):
    """QK^T and PV over the live pairs; q in, o out, k and v in."""
    return _attention(cfg, traffic, 2, (2, 2))


def bd_attn_bwd(cfg, traffic):
    """The fused backward's five products over the live pairs; q, do in
    and dq out, k, v in and dk, dv out."""
    return _attention(cfg, traffic, 5, (3, 4))


def flash_fwd_shape(cfg, traffic):
    """(batch*heads, positions, head_dim) of the flash kernels' q-sized
    arrays: ``attn_bwd_ms.tokens`` asks for it to tell a scan backward's
    ``while`` from others (this configuration's backward is the kernel,
    found by its name)."""
    return (traffic["batch_per_chip"] * cfg["num_attention_heads"],
            2 * traffic["seq"], cfg["head_dim"])


def moe_gmm(cfg, traffic):
    """(flops, bytes) of a STEP's grouped products, whatever implements
    them: the expected rows (positions x experts per token x held /
    routed) through gate+up and down, forward and the two backward
    products of each; every product reads its two operands and writes
    its result once."""
    e, d, hq, hkv, f = _sizes(cfg)
    held = cfg["num_experts"]
    rows = 2 * traffic["seq"] * traffic["batch_per_chip"] \
        * held_per_position(cfg)
    item = _ITEM[cfg["train"]["compute_dtype"]]
    flops = nbytes = 0
    for k, n in ((e, 2 * f), (f, e)):
        flops += 3 * 2 * rows * k * n
        nbytes += 3 * item * (rows * k + held * k * n + rows * n)
    return cfg["num_hidden_layers"] * flops, cfg["num_hidden_layers"] * nbytes
