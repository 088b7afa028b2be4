"""Builds configuration ``qwen3-next-80b-a3b`` through the program's
public API (``models.MoEDecoderLM`` under its layer pattern: Gated
DeltaNet layers and, every ``full_attention_interval``-th, a gated full
attention layer with q/k norm and partial RoPE; top-k experts from the
MLP's input beside a shared expert), supplies the next-token loss block,
makes the batches, and ties its parameters to the reference's leaves."""
from __future__ import annotations

import numpy as onp


def build_net(cfg):
    from mxnet_tpu import models

    n, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    linear = {"gated_delta": dict(
        num_k_heads=cfg["linear_num_key_heads"],
        num_v_heads=cfg["linear_num_value_heads"],
        head_k_dim=cfg["linear_key_head_dim"],
        head_v_dim=cfg["linear_value_head_dim"],
        conv_kernel=cfg["linear_conv_kernel_dim"])}
    return models.MoEDecoderLM(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
        num_layers=n, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["router_experts"],
        expert_dim=cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"],
        experts_held=(cfg["experts_first"], cfg["num_experts"]),
        norm_topk_prob=cfg["norm_topk_prob"], rope_theta=cfg["rope_theta"],
        epsilon=cfg["rms_norm_eps"],
        attention=["causal" if (i + 1) % every == 0 else linear
                   for i in range(n)],
        rotary_dim=int(round(cfg["partial_rotary_factor"]
                             * cfg["head_dim"])),
        output_gate=True,
        shared_expert=cfg["shared_expert_intermediate_size"])


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
    (in, experts), the experts' 3-D weights and the shared expert's
    (in, out) matrices as they are."""
    if leaf.endswith((".qkv.w", ".qkvz.w", ".ba.w", ".out.w", ".conv.w")) \
            or leaf == "head.w":
        return value.T
    return value
