"""Mixture-of-experts decoder LM of the kind trained today, and its
block-diffusion training input.

A pre-norm decoder block with RMSNorm, grouped key/value heads, no bias,
no learned positions, a drop-free top-k mixture of gated experts for
every layer's MLP (``gluon.contrib.nn.TopKMoE``: told which experts it
holds), a final RMSNorm and an untied head. Attention is the flash
kernels (``kernels/flash_attention.py``). The layer pattern is part of
the architecture, given layer by layer:

- ``attention``: one kind for every layer, or a list of one kind a
  layer. ``"causal"`` is autoregressive attention over (B, S) token ids;
  ``{"window": w}`` is causal attention over a query's own position and
  the ``w - 1`` before it (``SlidingWindowMask``);
  ``{"block_length": b}`` is block-diffusion training (BD3-LM, Arriola
  et al. 2025, arXiv:2503.09573), for every layer or none: the input is
  (B, 2L) ids, the L noised tokens followed by the L clean ones, copy i
  of either half at position i mod L, under ``BlockDiffusionMask(L, b)``;
  the head runs on the L noised positions only, so the logits are
  (B, L, vocab).
- ``rope``: whether q and k carry rotary positions (rotate-half), for
  every layer or layer by layer (a layer without them has no positions
  at all: NoPE).
- ``qk_norm``: a per-head RMSNorm on q and k before the positions.
- ``router_input``: ``"mlp"`` routes each token by the normed input of
  its MLP, ``"layer"`` by the normed input of the layer, before
  attention, which the experts' rows do not come from.
- ``activation``: the experts' gate, ``"silu"`` or ``"relu"`` (ReGLU).
"""
from __future__ import annotations

from ..gluon.block import HybridBlock
from ..gluon import nn
from ..gluon.contrib.nn import TopKMoE

__all__ = ["MoEDecoderLM", "MoEDecoderBlock", "GroupedQueryAttention"]


_SIZED_KINDS = {"window": "window", "block_length": "block"}


def _attention_kind(attention):
    """``(kind, size)`` of one layer's attention: ``("causal", None)``,
    ``("window", w)`` or ``("block", b)``."""
    if attention == "causal":
        return "causal", None
    if isinstance(attention, dict) and len(attention) == 1:
        (key, size), = attention.items()
        if key in _SIZED_KINDS and int(size) > 0:
            return _SIZED_KINDS[key], int(size)
    raise ValueError('attention is "causal", {"window": w} or '
                     f'{{"block_length": b}}, got {attention!r}')


def _per_layer(value, num_layers, what):
    """``value`` for each of ``num_layers`` layers: a list of that many,
    or the one value every layer gets."""
    if isinstance(value, (list, tuple)):
        if len(value) != num_layers:
            raise ValueError(f"{what}: {len(value)} entries for "
                             f"{num_layers} layers")
        return list(value)
    return [value] * num_layers


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
    head h // group), q and k each pass an RMSNorm over their head
    (``qk_norm``) and then RoPE (``rope``), no bias. One fused q|k|v
    projection."""

    def __init__(self, embed_dim, num_heads, num_kv_heads, head_dim,
                 rope_theta=1e6, epsilon=1e-6, attention="causal",
                 rope=True, qk_norm=True, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads over {num_kv_heads}")
        self._h, self._hkv, self._d = num_heads, num_kv_heads, head_dim
        self._theta, self._eps = float(rope_theta), float(epsilon)
        self._kind, self._size = _attention_kind(attention)
        self._rope, self._qk_norm = bool(rope), bool(qk_norm)
        with self.name_scope():
            self.qkv = nn.Dense((num_heads + 2 * num_kv_heads) * head_dim,
                                use_bias=False, flatten=False)
            if self._qk_norm:
                self.q_norm = self.params.get(
                    "q_norm_gamma", shape=(head_dim,), init="ones")
                self.k_norm = self.params.get(
                    "k_norm_gamma", shape=(head_dim,), init="ones")
            self.out = nn.Dense(embed_dim, use_bias=False, flatten=False)

    def hybrid_forward(self, F, x, q_norm=None, k_norm=None):
        from ..ndarray.registry import apply_pure

        h, hkv, d, eps, theta = self._h, self._hkv, self._d, self._eps, \
            self._theta
        kind, size, with_rope = self._kind, self._size, self._rope

        def pure(qkv, gq=None, gk=None):
            import jax.numpy as jnp

            from ..gluon.nn.basic_layers import rms_norm
            from ..kernels.flash_attention import (
                BlockDiffusionMask, SlidingWindowMask, flash_attention)

            b, s, _ = qkv.shape
            q = qkv[..., :h * d].reshape(b, s, h, d)
            k = qkv[..., h * d:(h + hkv) * d].reshape(b, s, hkv, d)
            v = qkv[..., (h + hkv) * d:].reshape(b, s, hkv, d)

            pos = jnp.arange(s)
            mask = None
            if kind == "block":
                if s % 2:
                    raise ValueError("block-diffusion input is (B, 2L), got "
                                     f"{s} positions")
                pos = pos % (s // 2)
                mask = BlockDiffusionMask(s // 2, size)
            elif kind == "window" and size < s:     # else causal alone
                mask = SlidingWindowMask(s, size)
            if gq is not None:
                q, k = rms_norm(q, gq, eps), rms_norm(k, gk, eps)
            q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
            if with_rope:
                q, k = rope(q, pos, theta), rope(k, pos, theta)
            o = flash_attention(q, k, v.transpose(0, 2, 1, 3),
                                causal=mask is None, mask=mask)
            return o.transpose(0, 2, 1, 3).reshape(b, s, h * d)

        gammas = [q_norm, k_norm] if self._qk_norm else []
        return self.out(apply_pure(pure, [self.qkv(x)] + gammas))


class MoEDecoderBlock(HybridBlock):
    def __init__(self, embed_dim, num_heads, num_kv_heads, head_dim,
                 num_experts, expert_dim, top_k, experts_held=None,
                 norm_topk_prob=True, rope_theta=1e6, epsilon=1e-6,
                 attention="causal", rope=True, qk_norm=True,
                 router_input="mlp", activation="silu", **kwargs):
        super().__init__(**kwargs)
        if router_input not in ("mlp", "layer"):
            raise ValueError(f'router_input is "mlp" or "layer", got '
                             f"{router_input!r}")
        self._route_by_layer_input = router_input == "layer"
        with self.name_scope():
            self.ln1 = nn.RMSNorm(epsilon)
            self.attn = GroupedQueryAttention(
                embed_dim, num_heads, num_kv_heads, head_dim, rope_theta,
                epsilon, attention, rope, qk_norm)
            self.ln2 = nn.RMSNorm(epsilon)
            self.moe = TopKMoE(num_experts, expert_dim, top_k,
                               experts_held=experts_held,
                               norm_topk_prob=norm_topk_prob,
                               activation=activation)

    def hybrid_forward(self, F, x):
        n = self.ln1(x)
        h = x + self.attn(n)
        if self._route_by_layer_input:
            return h + self.moe(self.ln2(h), n)
        return h + self.moe(self.ln2(h))


class MoEDecoderLM(HybridBlock):
    """embed -> N x MoEDecoderBlock -> RMSNorm -> untied head."""

    def __init__(self, vocab_size, embed_dim, num_layers, num_heads,
                 num_kv_heads, head_dim, num_experts, expert_dim, top_k,
                 experts_held=None, norm_topk_prob=True, rope_theta=1e6,
                 epsilon=1e-6, attention="causal", rope=True, qk_norm=True,
                 router_input="mlp", activation="silu", **kwargs):
        super().__init__(**kwargs)
        kinds = _per_layer(attention, num_layers, "attention")
        ropes = _per_layer(rope, num_layers, "rope")
        parsed = {_attention_kind(a) for a in kinds}
        self._block = any(kind == "block" for kind, _ in parsed)
        if self._block and len(parsed) > 1:
            raise ValueError("block-diffusion attention is every layer's, "
                             f"at one block length, or none's: {kinds!r}")
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, embed_dim)
            self.blocks = nn.HybridSequential(prefix="blocks_")
            for kind, with_rope in zip(kinds, ropes):
                self.blocks.add(MoEDecoderBlock(
                    embed_dim, num_heads, num_kv_heads, head_dim,
                    num_experts, expert_dim, top_k, experts_held,
                    norm_topk_prob, rope_theta, epsilon, kind, with_rope,
                    qk_norm, router_input, activation))
            self.ln_f = nn.RMSNorm(epsilon)
            self.head = nn.Dense(vocab_size, flatten=False, use_bias=False)

    def hybrid_forward(self, F, tokens):
        x = self.blocks(self.embed(tokens))
        if self._block:     # the head sees the noised half
            x = x[:, :tokens.shape[1] // 2]
        return self.head(self.ln_f(x))
