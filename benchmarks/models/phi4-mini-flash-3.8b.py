"""Builds configuration ``phi4-mini-flash-3.8b`` through the program's
public API (``models.SambaYLM`` under its layer list: Mamba, sliding-
window differential attention, Mamba whose output is the memory, full
differential attention whose q|k|v is the shared set, a Gated Memory
Unit, cross-attention; LayerNorm, SwiGLU MLPs, the head tied to the
embedding), supplies the next-token loss block, makes the batches, and
ties its parameters to the reference's leaves."""
from __future__ import annotations

import numpy as onp


def layer_list(cfg):
    """The configuration's layers as ``SambaYLM`` takes them."""
    window = {"window": cfg["sliding_window"]}
    return [window if kind == "window" else kind
            for kind in cfg["layers"][:cfg["num_hidden_layers"]]]


def build_net(cfg):
    from mxnet_tpu import models

    e = cfg["hidden_size"]
    n = cfg["num_hidden_layers"]
    return models.SambaYLM(
        vocab_size=cfg["vocab_size"], embed_dim=e, layers=layer_list(cfg),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ffn_dim=cfg["intermediate_size"], d_inner=cfg["mamba_expand"] * e,
        d_state=cfg["mamba_d_state"], d_conv=cfg["mamba_d_conv"],
        dt_rank=cfg["mamba_dt_rank"],
        depths=cfg["published_layer_index"][:n],
        epsilon=cfg["layer_norm_eps"])


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
    (channels, taps) -> (taps, channels); the embedding, A_log (channels,
    states) and the vectors as they are."""
    if leaf.endswith((".w",)) and leaf != "embed.w":
        return value.T
    return value
