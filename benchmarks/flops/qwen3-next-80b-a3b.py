"""Operations and bytes of configuration ``qwen3-next-80b-a3b``, counted
from its shapes.

A multiply-accumulate is two operations. Per position: a linear layer's
two fused projections and its output projection, its convolution (one
multiply-accumulate a tap and channel) and the delta rule's recurrence
(below); a full layer's fused q,gate|k|v projection and output
projection and its live scores, two products forward over ``S(S+1)/2``
pairs a head; in every layer the router, the shared expert (its three
products and its gate) and the held experts at the share that lands here
(experts a token x held / routed assignments a position, three products
of E x F each); the head over the vocabulary slice. Embedding lookups,
norms, rotary positions, softmax, gates, routing and the optimizer are
not counted. Training is three times forward; recomputation is not
counted.

The recurrence is counted by its own work, whatever implements it: per
position and value head the decay of the (dk x dv) state (dk dv), ``M^T
k``, the rank-one update and ``M^T q`` (2 dk dv each): 7 dk dv forward,
and backward twice that and the forward again. No chunk size, saved
state or preparation enters either count.
"""
from __future__ import annotations

_ITEM = {"bfloat16": 2, "float32": 4}


def _linear_sizes(cfg):
    return (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])


def layer_kinds(cfg):
    """(linear layers, full layers) among the layers built."""
    n, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    full = sum(1 for i in range(n) if (i + 1) % every == 0)
    return n - full, full


def held_per_position(cfg):
    """Expected assignments a position sends to the experts held here."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]


def rule_flops_per_position(cfg):
    """Forward operations of one linear layer's recurrence a position."""
    hk, hv, dk, dv = _linear_sizes(cfg)
    return hv * 7 * dk * dv


def forward_flops_per_item(cfg, seq):
    """Model FLOPs of one token in a sequence of ``seq``."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hk, hv, dk, dv = _linear_sizes(cfg)
    mixed = 2 * hk * dk + hv * dv
    linear = 2 * e * (mixed + hv * dv) + 2 * e * 2 * hv + 2 * hv * dv * e \
        + 2 * cfg["linear_conv_kernel_dim"] * mixed \
        + rule_flops_per_position(cfg)
    full = 2 * e * (2 * hq + 2 * hkv) * d + 2 * hq * d * e \
        + (seq + 1) / 2 * hq * 2 * 2 * d
    f, fs = cfg["moe_intermediate_size"], \
        cfg["shared_expert_intermediate_size"]
    experts = 2 * e * cfg["router_experts"] + 3 * 2 * e * fs + 2 * e \
        + held_per_position(cfg) * 3 * 2 * e * f
    n_linear, n_full = layer_kinds(cfg)
    return n_linear * linear + n_full * full \
        + cfg["num_hidden_layers"] * experts + 2 * e * cfg["vocab_size"]


def train_flops_per_item(cfg, traffic):
    return 3 * forward_flops_per_item(cfg, traffic["seq"])


def _rule(cfg, traffic, passes, gradients):
    """(flops, bytes) of a STEP's linear layers' recurrence: ``passes``
    times the forward's operations; q and k once a key head, v and o (or
    do) once a value head in the compute dtype, g and beta float32, and
    with ``gradients`` all five gradients out."""
    hk, hv, dk, dv = _linear_sizes(cfg)
    positions = traffic["seq"] * traffic["batch_per_chip"]
    item = _ITEM[cfg["train"]["compute_dtype"]]
    qkvgb = item * (2 * hk * dk + hv * dv) + 2 * 4 * hv
    nbytes = qkvgb + item * hv * dv + (qkvgb if gradients else 0)
    layers = layer_kinds(cfg)[0]
    return (layers * positions * passes * rule_flops_per_position(cfg),
            layers * positions * nbytes)


def gdn_fwd(cfg, traffic):
    """The recurrence forward: q, k, v, g, beta in, o out."""
    return _rule(cfg, traffic, 1, False)


def gdn_bwd(cfg, traffic):
    """Its backward: twice the forward's operations and the forward
    again; q, k, v, g, beta and do in, the five gradients out."""
    return _rule(cfg, traffic, 3, True)


def _full_attention(cfg, traffic, products, arrays):
    """(flops, bytes) a STEP's calls of one flash kernel need, the full
    layers together: the ``S(S+1)/2`` live pairs a query head;
    q-sized arrays once per query head, k and v once per key/value head
    (the group reads them in place)."""
    d, hq = cfg["head_dim"], cfg["num_attention_heads"]
    seq, batch = traffic["seq"], traffic["batch_per_chip"]
    calls = layer_kinds(cfg)[1] * batch
    item = _ITEM[cfg["train"]["compute_dtype"]]
    q_sized = seq * hq * d * item
    kv_sized = seq * cfg["num_key_value_heads"] * d * item
    return (calls * hq * (seq * (seq + 1) // 2) * products * 2 * d,
            calls * (arrays[0] * q_sized + arrays[1] * kv_sized))


def full_attn_fwd(cfg, traffic):
    """QK^T and PV over the live pairs; q in, o out, k and v in."""
    return _full_attention(cfg, traffic, 2, (2, 2))


def full_attn_bwd(cfg, traffic):
    """The fused backward's five products over the live pairs; q, do in
    and dq out, k, v in and dk, dv out."""
    return _full_attention(cfg, traffic, 5, (3, 4))


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
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["num_experts"]
    rows = traffic["seq"] * traffic["batch_per_chip"] \
        * held_per_position(cfg)
    item = _ITEM[cfg["train"]["compute_dtype"]]
    flops = nbytes = 0
    for k, n in ((e, 2 * f), (f, e)):
        flops += 3 * 2 * rows * k * n
        nbytes += 3 * item * (rows * k + held * k * n + rows * n)
    return cfg["num_hidden_layers"] * flops, cfg["num_hidden_layers"] * nbytes
