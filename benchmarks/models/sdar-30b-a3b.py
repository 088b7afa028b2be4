"""Builds configuration ``sdar-30b-a3b`` through the program's public API
(``models.MoEDecoderLM`` under block-diffusion attention), supplies the
weighted cross-entropy of block-diffusion training as its loss block,
makes the noised batches, and ties its parameters to the reference's
leaves."""
from __future__ import annotations

import numpy as onp


def build_net(cfg):
    from mxnet_tpu import models

    return models.MoEDecoderLM(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["router_experts"],
        expert_dim=cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"],
        experts_held=(cfg["experts_first"], cfg["num_experts"]),
        norm_topk_prob=cfg["norm_topk_prob"], rope_theta=cfg["rope_theta"],
        epsilon=cfg["rms_norm_eps"],
        attention={"block_length": cfg["block_length"]})


def loss_block(cfg):
    from mxnet_tpu.gluon.loss import Loss

    class BlockDiffusionLoss(Loss):
        """Per sequence: sum over its L positions of weight * cross-entropy
        of the position's own logits against its clean token, over L.
        ``label`` (B, 2, L) carries the targets and the weights."""

        def __init__(self, **kw):
            super().__init__(None, 0, **kw)

        def hybrid_forward(self, F, pred, label):
            logp = F.log_softmax(pred, axis=-1)
            picked = F.pick(logp, label[:, 0], axis=-1)
            return -F.mean(picked * label[:, 1], axis=1)

    return BlockDiffusionLoss()


def example_input(cfg, traffic):
    return onp.zeros((1, 2 * traffic["seq"]), "int32")


def items_per_batch(cfg, traffic, batch):
    """Clean tokens a step: each passes the layers twice."""
    return batch * traffic["seq"]


def make_batch(cfg, traffic, batch, rng):
    """(x (B, 2L) int32, y (B, 2, L) float32). Clean ids uniform over the
    vocabulary slice less the mask id (its last row); per block a rate t
    uniform on ``t_range``; each token of the block replaced by the mask
    id with probability t; x = [xt ; x0]; y[:, 0] the clean ids and
    y[:, 1] the weight 1/t at masked positions, 0 elsewhere."""
    seq, blk = traffic["seq"], traffic["block_length"]
    if blk != cfg["block_length"] or seq % blk:
        raise ValueError(f"block_length {blk} of the traffic, "
                         f"{cfg['block_length']} of the configuration, "
                         f"seq {seq}")
    mask_id = cfg["vocab_size"] - 1
    x0 = rng.integers(0, mask_id, (batch, seq), dtype=onp.int32)
    lo, hi = traffic["t_range"]
    t = onp.repeat(rng.uniform(lo, hi, (batch, seq // blk)), blk, axis=1)
    masked = rng.random((batch, seq)) < t
    xt = onp.where(masked, mask_id, x0).astype(onp.int32)
    y = onp.stack([x0.astype(onp.float32),
                   onp.where(masked, 1.0 / t, 0.0).astype(onp.float32)], 1)
    return onp.concatenate([xt, x0], 1), y


def to_program(leaf, value):
    """Dense weights (in, out) -> (out, in); the embedding, the router
    (in, experts) and the experts' 3-D weights as they are."""
    if leaf.endswith((".qkv.w", ".out.w")) or leaf == "head.w":
        return value.T
    return value
