"""Transformer language model — the long-context flagship.

NEW model family beyond the reference's zoo (the reference's sequence
flagship is the fused-RNN word LM, example/rnn/word_lm/; SURVEY Appx C).
Decoder-only pre-norm transformer built from Gluon blocks whose attention
is the Pallas flash kernel (mxnet_tpu/ops/flash_attention.py); with a
dp×sp mesh the sequence axis shards across devices and attention runs as
the ring variant (mxnet_tpu/parallel/ring_attention.py).
"""
from __future__ import annotations

import math

from ..gluon.block import HybridBlock
from ..gluon import nn

__all__ = ["TransformerLM", "TransformerBlock", "MultiHeadSelfAttention"]


class MultiHeadSelfAttention(HybridBlock):
    """Causal self-attention over (B, S, E) via flash attention."""

    def __init__(self, embed_dim, num_heads, ring_axis=None,
                 ring_batch_axis=None, sp_mode="ring", **kwargs):
        super().__init__(**kwargs)
        assert embed_dim % num_heads == 0
        self._e = embed_dim
        self._h = num_heads
        self._ring_axis = ring_axis
        self._ring_batch_axis = ring_batch_axis
        # "ring" (ppermute pipeline, any head count) or "ulysses"
        # (all-to-all head scatter, needs heads % sp == 0, fewer
        # collectives when heads are plentiful) — parallel/ulysses.py
        self._sp_mode = sp_mode
        with self.name_scope():
            self.qkv = nn.Dense(3 * embed_dim, use_bias=False,
                                flatten=False)
            self.out = nn.Dense(embed_dim, use_bias=False, flatten=False)

    def hybrid_forward(self, F, x):
        from .. import nd

        B, S, E = x.shape
        h, d = self._h, self._e // self._h
        qkv = self.qkv(x).reshape(B, S, 3, h, d)
        qkv = qkv.transpose((2, 0, 3, 1, 4))  # (3, B, h, S, d)
        q, k, v = qkv[0], qkv[1], qkv[2]
        if self._ring_axis is not None:
            from .. import parallel

            sp_attn = (parallel.ulysses_attention
                       if self._sp_mode == "ulysses"
                       else parallel.ring_attention)
            attn = sp_attn(
                q, k, v, causal=True, axis_name=self._ring_axis,
                batch_axis=self._ring_batch_axis)
        else:
            attn = nd.flash_attention(q, k, v, causal=True)
        attn = attn.transpose((0, 2, 1, 3)).reshape(B, S, E)
        return self.out(attn)


class TransformerBlock(HybridBlock):
    def __init__(self, embed_dim, num_heads, ffn_dim, dropout=0.0,
                 ring_axis=None, ring_batch_axis=None, sp_mode="ring",
                 **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ln1 = nn.LayerNorm()
            self.attn = MultiHeadSelfAttention(
                embed_dim, num_heads, ring_axis=ring_axis,
                ring_batch_axis=ring_batch_axis, sp_mode=sp_mode)
            self.ln2 = nn.LayerNorm()
            self.ffn1 = nn.Dense(ffn_dim, flatten=False, activation="relu")
            self.ffn2 = nn.Dense(embed_dim, flatten=False)
            self.drop = nn.Dropout(dropout)

    def hybrid_forward(self, F, x):
        x = x + self.drop(self.attn(self.ln1(x)))
        return x + self.drop(self.ffn2(self.ffn1(self.ln2(x))))


class TransformerLM(HybridBlock):
    """Decoder-only LM: embed → N blocks → LayerNorm → tied-ish head.

    (The reference word LM ties embedding and decoder weights,
    example/rnn/word_lm/model.py:21-50; here `tie_weights` mirrors that.)
    """

    def __init__(self, vocab_size, embed_dim=256, num_layers=2, num_heads=4,
                 ffn_dim=None, max_len=1024, dropout=0.0, tie_weights=False,
                 ring_axis=None, ring_batch_axis=None, sp_mode="ring",
                 **kwargs):
        super().__init__(**kwargs)
        ffn_dim = ffn_dim or 4 * embed_dim
        self._scale = math.sqrt(embed_dim)
        self._max_len = max_len
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, embed_dim)
            self.pos_embed = nn.Embedding(max_len, embed_dim)
            self.blocks = nn.HybridSequential(prefix="blocks_")
            for _ in range(num_layers):
                self.blocks.add(TransformerBlock(
                    embed_dim, num_heads, ffn_dim, dropout,
                    ring_axis=ring_axis, ring_batch_axis=ring_batch_axis,
                    sp_mode=sp_mode))
            self.ln_f = nn.LayerNorm()
            self._tie = tie_weights
            if not tie_weights:
                self.head = nn.Dense(vocab_size, flatten=False,
                                     use_bias=False)

    def hybrid_forward(self, F, tokens):
        from .. import nd

        B, S = tokens.shape
        if S > self._max_len:
            raise ValueError(f"sequence length {S} exceeds max_len "
                             f"{self._max_len} (positional table size)")
        pos = nd.arange(S).reshape(1, S)
        x = self.embed(tokens) * self._scale + self.pos_embed(pos)
        x = self.blocks(x)
        x = self.ln_f(x)
        if self._tie:
            # tied decoder = embedding matrix reused as the output proj
            # (reference word LM ties weights, word_lm/model.py:41-50)
            import jax

            w = self.embed.weight.data()
            E = w.shape[1]
            with jax.named_scope("head"):   # no child block to open it
                return nd.dot(x.reshape(-1, E),
                              nd.transpose(w)).reshape(B, S, -1)
        return self.head(x)  # (B, S, vocab)
