"""Builds configuration ``lfm2-24b-a2b`` through the program's public API
(``models.MoEDecoderLM`` under its layer pattern: gated short-convolution
mixers and, where ``layer_types`` says so, grouped-query attention with
q/k norms and RoPE; dense SwiGLU MLPs in the leading layers, then top-k
experts behind a sigmoid router with an auxiliary-loss-free selection
bias; the head tied to the embedding), supplies the next-token loss
block, makes the batches, and ties its parameters to the reference's
leaves."""
from __future__ import annotations

import numpy as onp


def build_net(cfg):
    from mxnet_tpu import models

    n = cfg["num_hidden_layers"]
    # the router's weights are taken as renormalised, unscaled
    assert cfg["routed_scaling_factor"] == 1, cfg["routed_scaling_factor"]
    conv = {"short_conv": {"taps": cfg["conv_L_cache"]}}
    dense = {"dense": cfg["intermediate_size"]}
    return models.MoEDecoderLM(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
        num_layers=n, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["router_experts"],
        expert_dim=cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"],
        experts_held=(cfg["experts_first"], cfg["num_experts"]),
        norm_topk_prob=cfg["norm_topk_prob"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        epsilon=cfg["norm_eps"],
        attention=["causal" if kind == "full_attention" else conv
                   for kind in cfg["layer_types"][:n]],
        mlp=[dense if i < cfg["num_dense_layers"] else "moe"
             for i in range(n)],
        score="sigmoid",
        expert_bias=cfg["expert_bias_rate"] if cfg["use_expert_bias"]
        else None,
        tie_embeddings=cfg["tie_word_embeddings"])


def loss_block(cfg):
    from mxnet_tpu.gluon.loss import Loss

    class NextTokenLoss(Loss):
        """Cross-entropy of position t's logits against token t+1."""

        def __init__(self, **kw):
            super().__init__(None, 0, **kw)

        def hybrid_forward(self, F, pred, label):
            logp = F.log_softmax(pred[:, :-1], axis=-1)
            return -F.pick(logp, label[:, 1:], axis=-1, keepdims=True)

    return NextTokenLoss()


def example_input(cfg, traffic):
    return onp.zeros((1, traffic["seq"]), "int32")


def items_per_batch(cfg, traffic, batch):
    return batch * traffic["seq"]


def make_batch(cfg, traffic, batch, rng):
    """(tokens, tokens): ids drawn uniformly from the vocabulary slice;
    the loss block shifts the labels."""
    tok = rng.integers(0, cfg["vocab_size"], (batch, traffic["seq"]),
                       dtype=onp.int32)
    return tok, tok


def to_program(leaf, value):
    """Dense weights (in, out) -> (out, in) and the convolution's
    (channels, taps) -> (taps, channels); the embedding, the router
    (in, experts), the experts' 3-D weights and the vectors as they
    are."""
    if leaf.endswith((".conv.w", ".in.w", ".out.w", ".qkv.w")) \
            or ".mlp." in leaf:
        return value.T
    return value
