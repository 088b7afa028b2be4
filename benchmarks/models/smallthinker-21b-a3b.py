"""Builds configuration ``smallthinker-21b-a3b`` through the program's
public API (``models.MoEDecoderLM`` under its layer pattern: a global
layer without positions, then window layers with RoPE; no q/k norm; ReGLU
experts routed from the layer's input), supplies the next-token loss
block, makes the batches, and ties its parameters to the reference's
leaves."""
from __future__ import annotations

import numpy as onp


def build_net(cfg):
    from mxnet_tpu import models

    n = cfg["num_hidden_layers"]
    window = {"window": cfg["sliding_window_size"]}
    return models.MoEDecoderLM(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
        num_layers=n, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["router_experts"],
        expert_dim=cfg["moe_ffn_hidden_size"],
        top_k=cfg["moe_num_active_primary_experts"],
        experts_held=(cfg["experts_first"], cfg["moe_num_primary_experts"]),
        norm_topk_prob=cfg["norm_topk_prob"], rope_theta=cfg["rope_theta"],
        epsilon=cfg["rms_norm_eps"],
        attention=[window if w else "causal"
                   for w in cfg["sliding_window_layout"][:n]],
        rope=[bool(r) for r in cfg["rope_layout"][:n]],
        qk_norm=False, router_input="layer", activation="relu")


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
    """Dense weights (in, out) -> (out, in); the embedding, the router
    (in, experts) and the experts' 3-D weights as they are."""
    if leaf.endswith((".qkv.w", ".out.w")) or leaf == "head.w":
        return value.T
    return value
