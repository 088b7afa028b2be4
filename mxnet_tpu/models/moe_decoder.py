"""Mixture-of-experts decoder LM of the kind trained today, and its
block-diffusion training input.

A pre-norm decoder block with RMSNorm, grouped key/value heads with a
per-head RMSNorm on q and k and rotary positions (rotate-half), no bias,
no learned positions, a drop-free top-k mixture of SiLU-gated experts
for every layer's MLP (``gluon.contrib.nn.TopKMoE``: told which experts
it holds), a final RMSNorm and an untied head. Attention is the flash
kernels (``kernels/flash_attention.py``).

``attention="causal"`` is the autoregressive model on (B, S) token ids.
``attention={"block_length": b}`` is block-diffusion training (BD3-LM,
Arriola et al. 2025, arXiv:2503.09573): the input is (B, 2L) ids, the L
noised tokens followed by the L clean ones, copy i of either half at
position i mod L, under ``BlockDiffusionMask(L, b)``; the head runs on
the L noised positions only, so the logits are (B, L, vocab).
"""
from __future__ import annotations

from ..gluon.block import HybridBlock
from ..gluon import nn
from ..gluon.contrib.nn import TopKMoE

__all__ = ["MoEDecoderLM", "MoEDecoderBlock", "GroupedQueryAttention"]


def _block_length(attention):
    if attention == "causal":
        return None
    if isinstance(attention, dict) and set(attention) == {"block_length"}:
        return int(attention["block_length"])
    raise ValueError('attention is "causal" or {"block_length": b}, got '
                     f"{attention!r}")


def rope(x, positions, theta):
    """Rotary positions on (B, H, S, D), rotate-half convention, in
    float32: ``x * cos + rotate_half(x) * sin`` with the D/2 frequencies
    ``theta ** (-2i / D)`` laid out twice along D."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    xf = x.astype(jnp.float32)
    half = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
    return (xf * cos + half * sin).astype(x.dtype)


class GroupedQueryAttention(HybridBlock):
    """Self-attention over (B, S, E): ``num_heads`` query heads read
    ``num_kv_heads`` key/value heads of ``head_dim`` (query head h reads
    head h // group), q and k each pass an RMSNorm over their head and
    then RoPE, no bias. One fused q|k|v projection."""

    def __init__(self, embed_dim, num_heads, num_kv_heads, head_dim,
                 rope_theta=1e6, epsilon=1e-6, attention="causal", **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads over {num_kv_heads}")
        self._h, self._hkv, self._d = num_heads, num_kv_heads, head_dim
        self._theta, self._eps = float(rope_theta), float(epsilon)
        self._block = _block_length(attention)
        with self.name_scope():
            self.qkv = nn.Dense((num_heads + 2 * num_kv_heads) * head_dim,
                                use_bias=False, flatten=False)
            self.q_norm = self.params.get("q_norm_gamma", shape=(head_dim,),
                                          init="ones")
            self.k_norm = self.params.get("k_norm_gamma", shape=(head_dim,),
                                          init="ones")
            self.out = nn.Dense(embed_dim, use_bias=False, flatten=False)

    def hybrid_forward(self, F, x, q_norm, k_norm):
        from ..ndarray.registry import apply_pure

        h, hkv, d, eps, theta = self._h, self._hkv, self._d, self._eps, \
            self._theta
        block = self._block

        def pure(qkv, gq, gk):
            import jax.numpy as jnp

            from ..gluon.nn.basic_layers import rms_norm
            from ..kernels.flash_attention import (BlockDiffusionMask,
                                                   flash_attention)

            b, s, _ = qkv.shape
            q = qkv[..., :h * d].reshape(b, s, h, d)
            k = qkv[..., h * d:(h + hkv) * d].reshape(b, s, hkv, d)
            v = qkv[..., (h + hkv) * d:].reshape(b, s, hkv, d)

            pos = jnp.arange(s)
            mask = None
            if block is not None:
                if s % 2:
                    raise ValueError("block-diffusion input is (B, 2L), got "
                                     f"{s} positions")
                pos = pos % (s // 2)
                mask = BlockDiffusionMask(s // 2, block)
            q = rope(rms_norm(q, gq, eps).transpose(0, 2, 1, 3), pos, theta)
            k = rope(rms_norm(k, gk, eps).transpose(0, 2, 1, 3), pos, theta)
            o = flash_attention(q, k, v.transpose(0, 2, 1, 3),
                                causal=mask is None, mask=mask)
            return o.transpose(0, 2, 1, 3).reshape(b, s, h * d)

        return self.out(apply_pure(pure, [self.qkv(x), q_norm, k_norm]))


class MoEDecoderBlock(HybridBlock):
    def __init__(self, embed_dim, num_heads, num_kv_heads, head_dim,
                 num_experts, expert_dim, top_k, experts_held=None,
                 norm_topk_prob=True, rope_theta=1e6, epsilon=1e-6,
                 attention="causal", **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ln1 = nn.RMSNorm(epsilon)
            self.attn = GroupedQueryAttention(
                embed_dim, num_heads, num_kv_heads, head_dim, rope_theta,
                epsilon, attention)
            self.ln2 = nn.RMSNorm(epsilon)
            self.moe = TopKMoE(num_experts, expert_dim, top_k,
                               experts_held=experts_held,
                               norm_topk_prob=norm_topk_prob)

    def hybrid_forward(self, F, x):
        x = x + self.attn(self.ln1(x))
        return x + self.moe(self.ln2(x))


class MoEDecoderLM(HybridBlock):
    """embed -> N x MoEDecoderBlock -> RMSNorm -> untied head."""

    def __init__(self, vocab_size, embed_dim, num_layers, num_heads,
                 num_kv_heads, head_dim, num_experts, expert_dim, top_k,
                 experts_held=None, norm_topk_prob=True, rope_theta=1e6,
                 epsilon=1e-6, attention="causal", **kwargs):
        super().__init__(**kwargs)
        self._block = _block_length(attention)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, embed_dim)
            self.blocks = nn.HybridSequential(prefix="blocks_")
            for _ in range(num_layers):
                self.blocks.add(MoEDecoderBlock(
                    embed_dim, num_heads, num_kv_heads, head_dim,
                    num_experts, expert_dim, top_k, experts_held,
                    norm_topk_prob, rope_theta, epsilon, attention))
            self.ln_f = nn.RMSNorm(epsilon)
            self.head = nn.Dense(vocab_size, flatten=False, use_bias=False)

    def hybrid_forward(self, F, tokens):
        x = self.blocks(self.embed(tokens))
        if self._block is not None:     # the head sees the noised half
            x = x[:, :tokens.shape[1] // 2]
        return self.head(self.ln_f(x))
