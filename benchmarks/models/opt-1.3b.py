"""Builds configuration ``opt-1.3b`` through the program's public API
(``models.TransformerLM``), supplies the next-token loss block it lacks,
and ties its parameters to the reference's leaves."""
from __future__ import annotations

import numpy as onp


def build_net(cfg):
    from mxnet_tpu import models

    return models.TransformerLM(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], ffn_dim=cfg["ffn_dim"],
        max_len=cfg["max_position_embeddings"], dropout=cfg["dropout"],
        tie_weights=cfg["tie_word_embeddings"])


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
    """(tokens, tokens): ids drawn uniformly from the vocabulary; the
    loss block shifts the labels."""
    tok = rng.integers(0, cfg["vocab_size"], (batch, traffic["seq"]),
                       dtype=onp.int32)
    return tok, tok


def to_program(leaf, value):
    """Dense weights (in, out) -> (out, in); embeddings as they are."""
    if value.ndim == 2 and leaf not in ("embed.w", "pos.w"):
        return value.T
    return value

