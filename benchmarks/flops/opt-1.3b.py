"""Operations of the OPT decoder, counted from its shapes.

A multiply-accumulate is two operations. Per token and layer the four
projections are 4*E*E and the MLP 2*E*F multiply-accumulates; causal
attention needs, on average over a sequence of S positions, S/2 keys for
QK^T and as many for PV. The tied head is E*V. Embedding lookups,
LayerNorm, softmax and the optimizer are not counted. Training is three
times forward; recomputation (the flash backward recomputes the scores)
is not counted.
"""
from __future__ import annotations


def forward_flops_per_item(cfg, seq):
    """Model FLOPs of one token in a sequence of ``seq`` positions."""
    e, f = cfg["hidden_size"], cfg["ffn_dim"]
    layer = 2 * (4 * e * e + 2 * e * f) + 2 * 2 * (seq / 2) * e
    return cfg["num_hidden_layers"] * layer + 2 * e * cfg["vocab_size"]


def train_flops_per_item(cfg, traffic):
    return 3 * forward_flops_per_item(cfg, traffic["seq"])


def flash_fwd(cfg, traffic):
    """(flops, bytes) that ONE call of the flash forward kernel needs for
    this cell's (batch*heads, seq, head_dim), causal: the useful half of
    the S x S scores for QK^T and PV, and q, k, v read and o written once
    in the compute type."""
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    d, s = e // h, traffic["seq"]
    bh = traffic["batch_per_chip"] * h
    flops = bh * 2 * 2 * (s * s / 2) * d
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["train"]["compute_dtype"]]
    return flops, bh * 4 * s * d * itemsize


def flash_fwd_shape(cfg, traffic):
    """(batch*heads, seq, head_dim): the shape of the kernel's result, by
    which its op is found in the trace."""
    h = cfg["num_attention_heads"]
    return (traffic["batch_per_chip"] * h, traffic["seq"],
            cfg["hidden_size"] // h)
