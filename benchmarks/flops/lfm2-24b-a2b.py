"""Operations and bytes of configuration ``lfm2-24b-a2b``, counted from
its shapes.

A multiply-accumulate is two operations. Per position: a conv layer's
fused B|C|x projection (E x 3E), its gates and depthwise convolution
(two products and one multiply-accumulate a tap, a channel) and its
output projection (E x E); an attention layer's fused q|k|v projection,
its output projection and its live scores, two products forward over
``S(S+1)/2`` pairs a head; a dense layer's SwiGLU (three products of E x
F); an expert layer's router and its held experts at the share that
lands here (experts a token x held / routed assignments a position,
three products of E x F each); the tied head over the vocabulary slice.
Embedding lookups, norms, rotary positions, softmax, gates, routing and
the optimizer are not counted. Training is three times forward;
recomputation is not counted.
"""
from __future__ import annotations

_ITEM = {"bfloat16": 2, "float32": 4}


def layer_kinds(cfg):
    """(conv layers, attention layers, dense layers, expert layers) among
    the layers built."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    attention = sum(1 for k in kinds if k == "full_attention")
    dense = min(cfg["num_dense_layers"], cfg["num_hidden_layers"])
    return (len(kinds) - attention, attention, dense,
            cfg["num_hidden_layers"] - dense)


def held_per_position(cfg):
    """Expected assignments a position sends to the experts held here."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]


def forward_flops_per_item(cfg, seq):
    """Model FLOPs of one token in a sequence of ``seq``."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    conv = 2 * e * 3 * e + 2 * e * e + (2 + 2 * cfg["conv_L_cache"]) * e
    attention = 2 * e * (hq + 2 * hkv) * d + 2 * hq * d * e \
        + (seq + 1) / 2 * hq * 2 * 2 * d
    dense = 3 * 2 * e * cfg["intermediate_size"]
    experts = 2 * e * cfg["router_experts"] \
        + held_per_position(cfg) * 3 * 2 * e * cfg["moe_intermediate_size"]
    n_conv, n_attention, n_dense, n_experts = layer_kinds(cfg)
    return n_conv * conv + n_attention * attention + n_dense * dense \
        + n_experts * experts + 2 * e * cfg["vocab_size"]


def train_flops_per_item(cfg, traffic):
    return 3 * forward_flops_per_item(cfg, traffic["seq"])


def _short_conv(cfg, traffic, passes, arrays):
    """(flops, bytes) of a STEP's calls of one short-convolution kernel,
    the conv layers together: ``passes`` times the forward's element-wise
    work (two gates and a multiply-accumulate a tap, a channel), and
    ``arrays`` arrays of E channels a position read or written once in
    the compute dtype (the halos and the weight's partial sums left out)."""
    e, taps = cfg["hidden_size"], cfg["conv_L_cache"]
    positions = traffic["seq"] * traffic["batch_per_chip"]
    item = _ITEM[cfg["train"]["compute_dtype"]]
    layers = layer_kinds(cfg)[0]
    return (layers * positions * passes * (2 + 2 * taps) * e,
            layers * positions * arrays * e * item)


def short_conv_fwd(cfg, traffic):
    """The forward: B|C|x in (3E), y out (E)."""
    return _short_conv(cfg, traffic, 1, 4)


def short_conv_bwd(cfg, traffic):
    """The backward: twice the forward's work and the forward again; dy
    (E) and B|C|x (3E) in, d(B|C|x) (3E) out."""
    return _short_conv(cfg, traffic, 3, 7)


def _full_attention(cfg, traffic, products, arrays):
    """(flops, bytes) a STEP's calls of one flash kernel need, the
    attention layers together: the ``S(S+1)/2`` live pairs a query head;
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
    its result once; the expert layers only."""
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["num_experts"]
    rows = traffic["seq"] * traffic["batch_per_chip"] \
        * held_per_position(cfg)
    item = _ITEM[cfg["train"]["compute_dtype"]]
    flops = nbytes = 0
    for k, n in ((e, 2 * f), (f, e)):
        flops += 3 * 2 * rows * k * n
        nbytes += 3 * item * (rows * k + held * k * n + rows * n)
    layers = layer_kinds(cfg)[3]
    return layers * flops, layers * nbytes
