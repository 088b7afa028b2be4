"""Mixture-of-experts decoder LM of the kind trained today, and its
block-diffusion training input.

A pre-norm decoder block with RMSNorm, grouped key/value heads, no bias,
no learned positions, a drop-free top-k mixture of gated experts for
each layer's MLP (``gluon.contrib.nn.TopKMoE``: told which experts it
holds) or a dense one, a final RMSNorm and a head, untied by default.
Attention is the flash kernels (``kernels/flash_attention.py``). The
layer pattern is part of the architecture, given layer by layer:

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
  ``{"gated_delta": {...}}`` is no attention at all: the layer's mixer
  is a ``GatedDeltaNet`` (linear attention by the gated delta rule,
  ``kernels/gated_delta.py``) of ``num_k_heads`` key heads of
  ``head_k_dim`` serving ``num_v_heads`` value heads of ``head_v_dim``
  behind a causal depthwise convolution of ``conv_kernel`` positions.
  ``{"short_conv": {"taps": n}}`` is none either: the mixer is a
  ``GatedShortConv`` (LFM2's gated short convolution,
  ``kernels/short_conv.py``) over ``n`` positions.
- ``mlp``: each layer's MLP, for every layer or layer by layer:
  ``"moe"``, the mixture of experts, or ``{"dense": width}``, a dense
  SwiGLU of that width (``SwiGLU``), as a model's leading dense layers
  have it.
- ``score``, ``expert_bias``: the router's score (``"softmax"`` or
  ``"sigmoid"``) and the rate of its auxiliary-loss-free selection bias
  (none by default; ``TopKMoE``).
- ``tie_embeddings``: the head reads the embedding's matrix and has no
  weight of its own.
- ``rope``: whether q and k carry rotary positions (rotate-half), for
  every layer or layer by layer (a layer without them has no positions
  at all: NoPE); ``rotary_dim``: on the leading ``rotary_dim`` of a
  head's dimensions only (all of them by default).
- ``output_gate``: the q projection is twice as wide, a head's columns
  its query then its gate, and ``sigmoid(gate)`` multiplies the
  attention's result before the output projection.
- ``shared_expert``: the width of a dense gated expert every token
  passes beside its routed ones (``TopKMoE(shared_expert=)``).
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

__all__ = ["MoEDecoderLM", "MoEDecoderBlock", "GroupedQueryAttention",
           "GatedDeltaNet", "GatedShortConv", "SwiGLU"]


_SIZED_KINDS = {"window": "window", "block_length": "block"}


#: mixers that are no attention, by the key that names them
_MIXERS = ("gated_delta", "short_conv")


def _attention_kind(attention):
    """``(kind, size)`` of one layer's mixer: ``("causal", None)``,
    ``("window", w)``, ``("block", b)``, ``("gated_delta", {sizes})`` or
    ``("short_conv", {sizes})``."""
    if attention == "causal":
        return "causal", None
    if isinstance(attention, dict) and len(attention) == 1:
        (key, size), = attention.items()
        if key in _MIXERS and isinstance(size, dict):
            return key, dict(size)
        if key in _SIZED_KINDS and int(size) > 0:
            return _SIZED_KINDS[key], int(size)
    raise ValueError('attention is "causal", {"window": w}, '
                     '{"block_length": b}, {"gated_delta": {...}} or '
                     f'{{"short_conv": {{...}}}}, got {attention!r}')


def _mlp_kind(mlp):
    """One layer's MLP: ``None`` for the experts, else the dense width."""
    if mlp == "moe":
        return None
    if isinstance(mlp, dict) and list(mlp) == ["dense"] \
            and int(mlp["dense"]) > 0:
        return int(mlp["dense"])
    raise ValueError(f'mlp is "moe" or {{"dense": width}}, got {mlp!r}')


def _per_layer(value, num_layers, what):
    """``value`` for each of ``num_layers`` layers: a list of that many,
    or the one value every layer gets."""
    if isinstance(value, (list, tuple)):
        if len(value) != num_layers:
            raise ValueError(f"{what}: {len(value)} entries for "
                             f"{num_layers} layers")
        return list(value)
    return [value] * num_layers


class GroupedQueryAttention(HybridBlock):
    """Self-attention over (B, S, E): ``num_heads`` query heads read
    ``num_kv_heads`` key/value heads of ``head_dim`` (query head h reads
    head h // group), q and k each pass an RMSNorm over their head
    (``qk_norm``) and then RoPE (``rope``, on the leading ``rotary_dim``
    of the head), no bias. One fused q|k|v projection; with
    ``output_gate`` its q part is twice as wide, each head's query then
    its gate, and ``sigmoid(gate)`` multiplies the heads' results before
    the output projection."""

    def __init__(self, embed_dim, num_heads, num_kv_heads, head_dim,
                 rope_theta=1e6, epsilon=1e-6, attention="causal",
                 rope=True, qk_norm=True, rotary_dim=None,
                 output_gate=False, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads over {num_kv_heads}")
        self._h, self._hkv, self._d = num_heads, num_kv_heads, head_dim
        self._theta, self._eps = float(rope_theta), float(epsilon)
        self._kind, self._size = _attention_kind(attention)
        if self._kind in _MIXERS:
            raise ValueError(f"a {self._kind} layer is no attention")
        self._rope, self._qk_norm = bool(rope), bool(qk_norm)
        self._rot = head_dim if rotary_dim is None else int(rotary_dim)
        if not 0 < self._rot <= head_dim or self._rot % 2:
            raise ValueError(f"rotary_dim {rotary_dim} of {head_dim}")
        self._gate = bool(output_gate)
        q_heads = num_heads * (2 if self._gate else 1)
        with self.name_scope():
            self.qkv = nn.Dense((q_heads + 2 * num_kv_heads) * head_dim,
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
        rot, gated = self._rot, self._gate

        def pure(qkv, gq=None, gk=None):
            import jax
            import jax.numpy as jnp

            from ..kernels.flash_attention import (
                BlockDiffusionMask, SlidingWindowMask, flash_attention)
            from ..kernels.qk_prologue import qk_prologue

            b, s, _ = qkv.shape
            period, mask = s, None
            if kind == "block":
                if s % 2:
                    raise ValueError("block-diffusion input is (B, 2L), got "
                                     f"{s} positions")
                period = s // 2
                mask = BlockDiffusionMask(s // 2, size)
            elif kind == "window" and size < s:     # else causal alone
                mask = SlidingWindowMask(s, size)
            # the norms, the positions and (B, H, S, D): one kernel pair on
            # the chip (kernels/qk_prologue.py)
            q, k, v, gate = qk_prologue(
                qkv, gq, gk, h, hkv, d, epsilon=eps, rope=with_rope,
                rotary_dim=rot, rope_theta=theta, period=period,
                output_gate=gated)
            o = flash_attention(q, k, v, causal=mask is None, mask=mask)
            o = o.transpose(0, 2, 1, 3).reshape(b, s, h * d)
            if gate is not None:
                with jax.named_scope("gate"):
                    o = (o.astype(jnp.float32) * jax.nn.sigmoid(
                        gate.astype(jnp.float32))).astype(o.dtype)
            return o

        gammas = [q_norm, k_norm] if self._qk_norm else []
        return self.out(apply_pure(pure, [self.qkv(x)] + gammas))


class GatedDeltaNet(HybridBlock):
    """Linear attention by the gated delta rule over (B, S, E) (Gated
    DeltaNet, Yang et al., arXiv:2412.06464), no bias: one fused
    projection to q | k | v | z and one to b | a (a value head each);
    q | k | v pass a causal depthwise convolution over ``conv_kernel``
    positions and SiLU; q and k are l2-normalised a head, q scaled by
    ``head_k_dim ** -0.5`` (``kernels.delta_prologue``); ``beta =
    sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)`` in
    float32; the rule (``kernels.gated_delta.gated_delta_rule``, key
    head j serving the value heads from ``j * num_v_heads /
    num_k_heads`` on); an RMSNorm over each head's result times
    ``silu(z)``; the output projection."""

    def __init__(self, embed_dim, num_k_heads, num_v_heads, head_k_dim,
                 head_v_dim, conv_kernel=4, epsilon=1e-6, chunk=64,
                 **kwargs):
        super().__init__(**kwargs)
        if num_v_heads % num_k_heads:
            raise ValueError(f"{num_v_heads} value heads over "
                             f"{num_k_heads} key heads")
        self._hk, self._hv = int(num_k_heads), int(num_v_heads)
        self._dk, self._dv = int(head_k_dim), int(head_v_dim)
        self._taps, self._eps = int(conv_kernel), float(epsilon)
        self._chunk = int(chunk)
        kd, vd = self._hk * self._dk, self._hv * self._dv
        with self.name_scope():
            self.conv_weight = self.params.get(
                "conv_weight", shape=(self._taps, 2 * kd + vd))
            self.a_log = self.params.get("a_log", shape=(self._hv,),
                                         init="zeros")
            self.dt_bias = self.params.get("dt_bias", shape=(self._hv,),
                                           init="ones")
            self.norm_gamma = self.params.get(
                "norm_gamma", shape=(self._dv,), init="ones")
            self.qkvz = nn.Dense(2 * kd + 2 * vd, use_bias=False,
                                 flatten=False)
            self.ba = nn.Dense(2 * self._hv, use_bias=False, flatten=False)
            self.out = nn.Dense(embed_dim, use_bias=False, flatten=False)

    def hybrid_forward(self, F, x, conv_weight, a_log, dt_bias, norm_gamma):
        from ..ndarray.registry import apply_pure

        hk, hv, dk, dv = self._hk, self._hv, self._dk, self._dv
        eps, chunk = self._eps, self._chunk

        def pure(qkvz, ba, conv_w, a_log, dt_bias, gamma):
            import jax
            import jax.numpy as jnp

            from ..gluon.nn.basic_layers import rms_norm
            from ..kernels import delta_prologue as dp
            from ..kernels.gated_delta import gated_delta_rule

            b, s, _ = qkvz.shape
            kd, f32 = hk * dk, jnp.float32

            # the gated norm: element-wise work in float32, recomputed in
            # the backward pass from the projection's result, which is
            # kept anyway
            @jax.checkpoint
            def after(o, qkvz, gamma):
                with jax.named_scope("gate_norm"):
                    z = qkvz[..., 2 * kd + hv * dv:].reshape(b, s, hv, dv)
                    o = rms_norm(o.transpose(0, 2, 1, 3), gamma, eps)
                    o = o.astype(f32) * jax.nn.silu(z.astype(f32))
                    return o.astype(qkvz.dtype).reshape(b, s, hv * dv)

            # the convolution, SiLU, the l2 norms and (B, H, S, d): one
            # kernel pair on the chip (kernels/delta_prologue.py)
            q, k, v = dp.delta_prologue(qkvz, conv_w, hk, hv, dk, dv)
            beta = jax.nn.sigmoid(ba[..., :hv].astype(f32))
            g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
                ba[..., hv:].astype(f32) + dt_bias.astype(f32))
            o = gated_delta_rule(q, k, v, g.transpose(0, 2, 1),
                                 beta.transpose(0, 2, 1), chunk=chunk)
            return after(o, qkvz, gamma)

        return self.out(apply_pure(pure, [
            self.qkvz(x), self.ba(x), conv_weight, a_log, dt_bias,
            norm_gamma]))


class GatedShortConv(HybridBlock):
    """LFM2's gated short convolution over (B, S, E), no bias anywhere
    (Liquid AI's LFM2, ``transformers``' ``Lfm2ShortConv``): one fused
    projection to B | C | x, ``u = B * x``, a causal depthwise
    convolution over ``taps`` positions (the last tap the position's
    own), ``y = C * conv(u)``, the output projection. The part between
    the projections is one kernel pair on the chip
    (``kernels.short_conv``)."""

    def __init__(self, embed_dim, taps=3, **kwargs):
        super().__init__(**kwargs)
        self._taps = int(taps)
        with self.name_scope():
            self.conv_weight = self.params.get(
                "conv_weight", shape=(self._taps, embed_dim))
            self.in_proj = nn.Dense(3 * embed_dim, use_bias=False,
                                    flatten=False)
            self.out_proj = nn.Dense(embed_dim, use_bias=False,
                                     flatten=False)

    def hybrid_forward(self, F, x, conv_weight):
        from ..kernels.short_conv import short_conv
        from ..ndarray.registry import apply_pure

        return self.out_proj(apply_pure(short_conv,
                                        [self.in_proj(x), conv_weight]))


class SwiGLU(HybridBlock):
    """A dense gated MLP of ``width``, no bias: ``w2(silu(w1 x) * w3 x)``,
    w1 and w3 one fused projection ``w13`` (gate, then up), the gate in
    float32."""

    def __init__(self, embed_dim, width, **kwargs):
        super().__init__(**kwargs)
        self._width = int(width)
        with self.name_scope():
            self.w13 = nn.Dense(2 * self._width, use_bias=False,
                                flatten=False)
            self.w2 = nn.Dense(embed_dim, use_bias=False, flatten=False)

    def hybrid_forward(self, F, x):
        from ..ndarray.registry import apply_pure

        f = self._width

        def gate(h):
            import jax
            import jax.numpy as jnp

            return (jax.nn.silu(h[..., :f].astype(jnp.float32))
                    * h[..., f:].astype(jnp.float32)).astype(h.dtype)

        return self.w2(apply_pure(gate, [self.w13(x)]))


class MoEDecoderBlock(HybridBlock):
    def __init__(self, embed_dim, num_heads, num_kv_heads, head_dim,
                 num_experts, expert_dim, top_k, experts_held=None,
                 norm_topk_prob=True, rope_theta=1e6, epsilon=1e-6,
                 attention="causal", rope=True, qk_norm=True,
                 router_input="mlp", activation="silu", rotary_dim=None,
                 output_gate=False, shared_expert=None, mlp="moe",
                 score="softmax", expert_bias=None, **kwargs):
        super().__init__(**kwargs)
        if router_input not in ("mlp", "layer"):
            raise ValueError(f'router_input is "mlp" or "layer", got '
                             f"{router_input!r}")
        self._route_by_layer_input = router_input == "layer"
        kind, size = _attention_kind(attention)
        dense = _mlp_kind(mlp)
        with self.name_scope():
            self.ln1 = nn.RMSNorm(epsilon)
            if kind == "gated_delta":
                self.attn = GatedDeltaNet(embed_dim, epsilon=epsilon, **size)
            elif kind == "short_conv":
                self.attn = GatedShortConv(embed_dim, **size)
            else:
                self.attn = GroupedQueryAttention(
                    embed_dim, num_heads, num_kv_heads, head_dim, rope_theta,
                    epsilon, attention, rope, qk_norm, rotary_dim,
                    output_gate)
            self.ln2 = nn.RMSNorm(epsilon)
            if dense is not None:
                self.mlp = SwiGLU(embed_dim, dense)
            else:
                self.moe = TopKMoE(num_experts, expert_dim, top_k,
                                   experts_held=experts_held,
                                   norm_topk_prob=norm_topk_prob,
                                   activation=activation,
                                   shared_expert=shared_expert, score=score,
                                   expert_bias=expert_bias)
        self._dense = dense is not None

    def hybrid_forward(self, F, x):
        n = self.ln1(x)
        h = x + self.attn(n)
        if self._dense:
            return h + self.mlp(self.ln2(h))
        if self._route_by_layer_input:
            return h + self.moe(self.ln2(h), n)
        return h + self.moe(self.ln2(h))


class MoEDecoderLM(HybridBlock):
    """embed -> N x MoEDecoderBlock -> RMSNorm -> head (untied, or the
    embedding's matrix with ``tie_embeddings``)."""

    def __init__(self, vocab_size, embed_dim, num_layers, num_heads,
                 num_kv_heads, head_dim, num_experts, expert_dim, top_k,
                 experts_held=None, norm_topk_prob=True, rope_theta=1e6,
                 epsilon=1e-6, attention="causal", rope=True, qk_norm=True,
                 router_input="mlp", activation="silu", rotary_dim=None,
                 output_gate=False, shared_expert=None, mlp="moe",
                 score="softmax", expert_bias=None, tie_embeddings=False,
                 **kwargs):
        super().__init__(**kwargs)
        kinds = _per_layer(attention, num_layers, "attention")
        ropes = _per_layer(rope, num_layers, "rope")
        mlps = _per_layer(mlp, num_layers, "mlp")
        parsed = [_attention_kind(a) for a in kinds]
        self._block = any(kind == "block" for kind, _ in parsed)
        if self._block and any(p != parsed[0] for p in parsed):
            raise ValueError("block-diffusion attention is every layer's, "
                             f"at one block length, or none's: {kinds!r}")
        self._tied = bool(tie_embeddings)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, embed_dim)
            self.blocks = nn.HybridSequential(prefix="blocks_")
            for kind, with_rope, layer_mlp in zip(kinds, ropes, mlps):
                self.blocks.add(MoEDecoderBlock(
                    embed_dim, num_heads, num_kv_heads, head_dim,
                    num_experts, expert_dim, top_k, experts_held,
                    norm_topk_prob, rope_theta, epsilon, kind, with_rope,
                    qk_norm, router_input, activation, rotary_dim,
                    output_gate, shared_expert, layer_mlp, score,
                    expert_bias))
            self.ln_f = nn.RMSNorm(epsilon)
            if not self._tied:
                self.head = nn.Dense(vocab_size, flatten=False,
                                     use_bias=False)

    def hybrid_forward(self, F, tokens):
        x = self.blocks(self.embed(tokens))
        if self._block:     # the head sees the noised half
            x = x[:, :tokens.shape[1] // 2]
        x = self.ln_f(x)
        if not self._tied:
            return self.head(x)
        import jax

        from .. import nd

        b, s = x.shape[:2]
        w = self.embed.weight.data()
        with jax.named_scope("head"):   # no child block to open it
            return nd.dot(x.reshape(-1, w.shape[1]),
                          nd.transpose(w)).reshape(b, s, -1)
