"""SambaY: a decoder-hybrid-decoder language model (Ren et al., Decoder-
Hybrid-Decoder Architecture for Efficient Reasoning with Long Generation,
arXiv:2507.06607), the architecture of Phi-4-mini-flash-reasoning.

Every layer is a pre-norm block with LayerNorm (weight and bias), a token
mixer and a SwiGLU MLP without bias:

    h   = x + mixer(LN1(x))
    out = h + W2(up * silu(gate)),   [gate | up] = W1 LN2(h)

then LayerNorm and a head tied to the embedding. No positions anywhere:
the Mamba layers carry them. The layer pattern is part of the
architecture, given layer by layer (``layers``):

- ``"mamba"``: a selective state-space layer (``MambaMixer``,
  ``kernels/selective_scan.py``); its gated output is the *memory* the
  Gated Memory Units after it read (the last Mamba layer before them).
- ``{"window": w}`` / ``"causal"``: differential attention
  (``DifferentialAttention``) over a query's own position and the
  ``w - 1`` before it, or causal; its key/value projection is the *shared
  key/value set* the cross-attention layers after it read (the last
  self-attention layer before them).
- ``"gmu"``: a Gated Memory Unit, ``W_o(memory * silu(W_i x))``.
- ``"cross"``: differential attention with its own query projection over
  the shared keys and values, causal.

The memory and the key/value set pass from the layer that makes them to
the layers that read them inside one traced forward, so autodiff sums
their gradients from every reader. A mixer is its block's child ``attn``
(so the step's scopes read ``blocks/<i>/attn/``), with the scopes
``conv`` and ``ssm_scan`` inside a Mamba layer, ``gmu`` inside a GMU,
``diff_attn`` around the two maps' combination and ``cross_kv`` where a
cross layer takes the shared set apart.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..gluon.block import HybridBlock
from ..gluon import nn

__all__ = ["SambaYLM", "SambaYBlock", "MambaMixer", "DifferentialAttention",
           "GatedMemoryUnit"]


def _layer_kind(layer):
    """``(kind, window)`` of one entry of ``layers``."""
    if layer in ("mamba", "causal", "gmu", "cross"):
        return layer, None
    if isinstance(layer, dict) and set(layer) == {"window"} \
            and int(layer["window"]) > 0:
        return "window", int(layer["window"])
    raise ValueError('a layer is "mamba", "causal", {"window": w}, "gmu" or '
                     f'"cross", got {layer!r}')


def lambda_init(depth):
    """Differential attention's fixed part of lambda at layer ``depth``
    (0-based): 0.8 - 0.6 exp(-0.3 depth)."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


class MambaMixer(HybridBlock):
    """Mamba over (B, S, E), no bias but the convolution's and
    ``dt_proj``'s: ``[u | z] = W_in x``; ``u = silu(causal depthwise
    conv(u) + b)`` over ``d_conv`` positions; ``[r | B | C] = W_x u``;
    ``A = -exp(A_log)`` in float32; the selective scan with ``delta =
    softplus(W_dt r + b_dt)`` taken inside it in float32, ``D`` and the
    gate ``silu(z)`` (``kernels.selective_scan.selective_scan``);
    ``W_out``. Returns the
    layer's output and the gated scan output g, the memory a GMU reads."""

    def __init__(self, embed_dim, d_inner, d_state=16, d_conv=4,
                 dt_rank=None, **kwargs):
        super().__init__(**kwargs)
        self._di, self._n, self._taps = int(d_inner), int(d_state), int(d_conv)
        self._r = int(dt_rank or math.ceil(embed_dim / 16))
        with self.name_scope():
            self.conv_weight = self.params.get(
                "conv_weight", shape=(self._taps, self._di))
            self.conv_bias = self.params.get("conv_bias", shape=(self._di,),
                                             init="zeros")
            self.a_log = self.params.get("a_log", shape=(self._di, self._n),
                                         init="zeros")
            self.d_skip = self.params.get("d_skip", shape=(self._di,),
                                          init="ones")
            self.in_proj = nn.Dense(2 * self._di, use_bias=False,
                                    flatten=False, in_units=embed_dim)
            self.x_proj = nn.Dense(self._r + 2 * self._n, use_bias=False,
                                   flatten=False, in_units=self._di)
            self.dt_proj = nn.Dense(self._di, flatten=False,
                                    in_units=self._r)
            self.out_proj = nn.Dense(embed_dim, use_bias=False,
                                     flatten=False, in_units=self._di)

    def hybrid_forward(self, F, x, conv_weight, conv_bias, a_log, d_skip):
        from ..ndarray.registry import apply_pure

        di, n, taps, r = self._di, self._n, self._taps, self._r

        # recomputed in the backward pass from the projection's result,
        # which is kept anyway
        @jax.checkpoint
        def conv(xz, w, bias):
            with jax.named_scope("conv"):
                s, f32 = xz.shape[1], jnp.float32
                padded = jnp.pad(xz[..., :di], ((0, 0), (taps - 1, 0), (0, 0)))
                # y_t = sum_j w_j u_(t - (taps - 1) + j): the last tap is
                # the position's own
                y = sum(padded[:, j:j + s].astype(f32) * w[j].astype(f32)
                        for j in range(taps))
                return jax.nn.silu(y + bias.astype(f32)).astype(xz.dtype)

        def scan(u, xdbl, w_dt, b_dt, xz, a_log, d_skip):
            from ..kernels.selective_scan import selective_scan

            f32 = jnp.float32
            with jax.named_scope("dt_proj"):
                dt = jnp.matmul(xdbl[..., :r], w_dt.T)
            # softplus(dt + b_dt) in float32 inside the scan, z read in
            # place from the projection's [u | z]
            return selective_scan(
                u, dt, -jnp.exp(a_log.astype(f32)), xdbl[..., r:r + n],
                xdbl[..., r + n:], d_skip.astype(f32), xz, b_dt.astype(f32),
                z_col=di)

        xz = self.in_proj(x)
        u = apply_pure(conv, [xz, conv_weight, conv_bias])
        xdbl = self.x_proj(u)
        g = apply_pure(scan, [u, xdbl, self.dt_proj.weight.data(),
                              self.dt_proj.bias.data(), xz, a_log, d_skip])
        return self.out_proj(g), g


class GatedMemoryUnit(HybridBlock):
    """``W_o(memory * silu(W_i x))``, no bias: an element-wise gate of the
    memory a Mamba layer before it handed over."""

    def __init__(self, embed_dim, d_inner, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.in_proj = nn.Dense(d_inner, use_bias=False, flatten=False,
                                    in_units=embed_dim)
            self.out_proj = nn.Dense(embed_dim, use_bias=False,
                                     flatten=False, in_units=d_inner)

    def hybrid_forward(self, F, x, memory):
        from ..ndarray.registry import apply_pure

        def gate(m, a):
            with jax.named_scope("gmu"):
                return (m.astype(jnp.float32) * jax.nn.silu(
                    a.astype(jnp.float32))).astype(a.dtype)

        return self.out_proj(apply_pure(gate, [memory, self.in_proj(x)]))


class DifferentialAttention(HybridBlock):
    """Differential attention (Ye et al., arXiv:2410.05258) over (B, S,
    E): ``num_heads`` query heads of ``head_dim`` read as ``num_heads /
    2`` differential heads ``[q1 | q2]``, ``num_kv_heads`` key heads as
    pairs ``[k1 | k2]`` and value heads as ``num_kv_heads / 2`` heads of
    twice the width; differential head j reads pair ``j // group``. Per
    head ``o = (1 - lambda_init) RMSNorm(A1 v - lambda A2 v; gamma)`` with
    ``A_i = softmax(q_i k_i^T / sqrt(head_dim) + mask)`` and ``lambda =
    exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(depth)``. q|k|v and the
    output projection have bias.

    ``attention``: ``"causal"``, ``{"window": w}`` or ``"cross"``: causal
    over the keys and values of another layer's q|k|v projection, handed
    to the forward, with a q projection of its own. Self-attention
    returns its q|k|v projection beside its output, the set a cross layer
    reads. Each map is one flash call, q and k at ``head_dim`` and v at
    twice that: no (S, S) array reaches HBM."""

    def __init__(self, embed_dim, num_heads, num_kv_heads, head_dim, depth,
                 attention="causal", epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        if num_heads % 2 or num_kv_heads % 2 or num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads over {num_kv_heads}: "
                             "both in pairs, the first a multiple")
        self._h, self._hkv, self._d = num_heads, num_kv_heads, head_dim
        self._eps, self._lam0 = float(epsilon), lambda_init(depth)
        self._cross = attention == "cross"
        kind, self._window = _layer_kind("causal" if self._cross
                                         else attention)
        if kind not in ("causal", "window"):
            raise ValueError(f"differential attention is causal, windowed "
                             f"or cross, got {attention!r}")
        with self.name_scope():
            for name in ("lambda_q1", "lambda_k1", "lambda_q2",
                         "lambda_k2"):
                setattr(self, name, self.params.get(
                    name, shape=(head_dim,), init="zeros"))
            self.subln_gamma = self.params.get(
                "subln_gamma", shape=(2 * head_dim,), init="ones")
            width = (num_heads + (0 if self._cross else 2 * num_kv_heads)) \
                * head_dim
            self.qkv = nn.Dense(width, flatten=False, in_units=embed_dim)
            self.out = nn.Dense(embed_dim, flatten=False,
                                in_units=num_heads * head_dim)

    def hybrid_forward(self, F, x, kv=None, lambda_q1=None, lambda_k1=None,
                       lambda_q2=None, lambda_k2=None, subln_gamma=None):
        from ..ndarray.registry import apply_pure

        h, hkv, d, eps, lam0 = self._h, self._hkv, self._d, self._eps, \
            self._lam0
        window, cross = self._window, self._cross

        def pure(q_src, kv_src, lq1, lk1, lq2, lk2, gamma):
            from ..gluon.nn.basic_layers import rms_norm
            from ..kernels.flash_attention import (SlidingWindowMask,
                                                   flash_attention)

            b, s, _ = q_src.shape
            f32 = jnp.float32

            def pairs(a, heads):    # (B, S, heads d) -> 2 x (B, heads/2, S, d)
                a = a.reshape(b, s, heads // 2, 2, d).transpose(3, 0, 2, 1, 4)
                return a[0], a[1]

            q1, q2 = pairs(q_src[..., :h * d], h)
            with jax.named_scope("cross_kv" if cross else "kv"):
                at = h * d
                k1, k2 = pairs(kv_src[..., at:at + hkv * d], hkv)
                v = kv_src[..., at + hkv * d:at + 2 * hkv * d].reshape(
                    b, s, hkv // 2, 2 * d).transpose(0, 2, 1, 3)
            mask = SlidingWindowMask(s, window) \
                if window is not None and window < s else None
            # A_i v: one flash call a map, its value head twice the query's
            o1, o2 = (flash_attention(q, k, v, causal=mask is None,
                                      mask=mask)
                      for q, k in ((q1, k1), (q2, k2)))
            with jax.named_scope("diff_attn"):
                dot = lambda p, q: jnp.sum(  # noqa: E731
                    p.astype(f32) * q.astype(f32))
                lam = jnp.exp(dot(lq1, lk1)) - jnp.exp(dot(lq2, lk2)) + lam0
                o = o1.astype(f32) - lam * o2.astype(f32)
                o = rms_norm(o, gamma, eps).astype(f32) * (1.0 - lam0)
                return o.astype(q_src.dtype).transpose(0, 2, 1, 3) \
                    .reshape(b, s, h * d)

        # the maps run again in the backward pass from the projections: q,
        # k, v in the kernels' layout, the two calls' o and lse and the
        # combination are 0.2 GB a layer at 8,192 positions
        qkv = self.qkv(x)
        src = kv if cross else qkv
        out = self.out(apply_pure(recomputed(pure, 2), [
            qkv, src, lambda_q1, lambda_k1, lambda_q2, lambda_k2,
            subln_gamma]))
        return out if cross else (out, qkv)


class SambaYBlock(HybridBlock):
    """One layer: LayerNorm, its mixer (the child ``attn``), LayerNorm,
    the SwiGLU MLP (``mlp``). ``forward(x, memory, kv)`` -> ``(out,
    memory, kv)``, the memory and the key/value set replaced where this
    layer makes them."""

    def __init__(self, layer, embed_dim, num_heads, num_kv_heads, head_dim,
                 ffn_dim, d_inner, d_state, d_conv, dt_rank, depth,
                 epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._kind, _ = _layer_kind(layer)
        with self.name_scope():
            self.ln1 = nn.LayerNorm(epsilon=epsilon, in_channels=embed_dim)
            if self._kind == "mamba":
                self.attn = MambaMixer(embed_dim, d_inner, d_state, d_conv,
                                       dt_rank)
            elif self._kind == "gmu":
                self.attn = GatedMemoryUnit(embed_dim, d_inner)
            else:
                self.attn = DifferentialAttention(
                    embed_dim, num_heads, num_kv_heads, head_dim, depth,
                    layer, epsilon)
            self.ln2 = nn.LayerNorm(epsilon=epsilon, in_channels=embed_dim)
            self.mlp = SwiGLU(embed_dim, ffn_dim)

    def hybrid_forward(self, F, x, memory=None, kv=None):
        n, kind = self.ln1(x), self._kind
        if kind == "gmu":
            if memory is None:
                raise ValueError("a GMU layer needs a Mamba layer before it")
            y = self.attn(n, memory)
        elif kind == "cross":
            if kv is None:
                raise ValueError("a cross layer needs a self-attention "
                                 "layer before it")
            y = self.attn(n, kv)
        elif kind == "mamba":
            y, memory = self.attn(n)
        else:
            y, kv = self.attn(n)
        h = x + y
        return h + self.mlp(self.ln2(h)), memory, kv


class SwiGLU(HybridBlock):
    """``W2(up * silu(gate))``, ``[gate | up] = W1 x``, no bias."""

    def __init__(self, embed_dim, ffn_dim, **kwargs):
        super().__init__(**kwargs)
        self._f = int(ffn_dim)
        with self.name_scope():
            self.fc1 = nn.Dense(2 * self._f, use_bias=False, flatten=False,
                                in_units=embed_dim)
            self.fc2 = nn.Dense(embed_dim, use_bias=False, flatten=False,
                                in_units=self._f)

    def hybrid_forward(self, F, x):
        from ..ndarray.registry import apply_pure

        # both products run again in the backward pass from the normed
        # input: W1's (S, 2F) result is 2 GB over six layers at 8,192
        # positions, and XLA keeps it where W2's gradient would take the
        # (S, F) activation
        return apply_pure(recomputed(functools.partial(_swiglu, self._f), 1),
                          [x, self.fc1.weight.data(), self.fc2.weight.data()])


def _swiglu(f, x, w1, w2):
    """``W2(up * silu(gate))`` with W (out, in) as ``nn.Dense`` holds
    them; the activation in float32."""
    with jax.named_scope("fc1"):
        a = jnp.matmul(x, w1.T)
    act = (jax.nn.silu(a[..., :f].astype(jnp.float32))
           * a[..., f:].astype(jnp.float32)).astype(a.dtype)
    with jax.named_scope("fc2"):
        return jnp.matmul(act, w2.T)


def recomputed(fn, tied):
    """``fn`` under a custom VJP whose backward runs ``fn`` again from its
    inputs: nothing ``fn`` makes is kept from forward to backward. Its
    first ``tied`` inputs (the activations) pass an
    ``optimization_barrier`` with the cotangent, so that the second run
    is neither merged with the first and kept alive nor scheduled before
    the cotangent arrives; the rest (weights) are residuals as they are,
    so that XLA may take their compute-dtype copies again from the
    masters."""
    @jax.custom_vjp
    def run(*args):
        return fn(*args)

    def fwd(*args):
        return fn(*args), args

    def bwd(args, dy):
        lead, dy = jax.lax.optimization_barrier((args[:tied], dy))
        return jax.vjp(fn, *lead, *args[tied:])[1](dy)

    run.defvjp(fwd, bwd)
    return run


class SambaYLM(HybridBlock):
    """embed -> the layers of ``layers`` -> LayerNorm -> the head tied to
    the embedding. ``depths``: the index each layer has in the published
    stack (it sets differential attention's ``lambda_init``), the
    position in ``layers`` by default. Every parameter's shape is known
    at construction."""

    def __init__(self, vocab_size, embed_dim, layers, num_heads,
                 num_kv_heads, head_dim, ffn_dim, d_inner, d_state=16,
                 d_conv=4, dt_rank=None, depths=None, epsilon=1e-5,
                 **kwargs):
        super().__init__(**kwargs)
        layers = list(layers)
        depths = list(range(len(layers))) if depths is None else list(depths)
        if len(depths) != len(layers):
            raise ValueError(f"{len(depths)} depths for {len(layers)} layers")
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, embed_dim)
            self.blocks = nn.HybridSequential(prefix="blocks_")
            for layer, depth in zip(layers, depths):
                self.blocks.add(SambaYBlock(
                    layer, embed_dim, num_heads, num_kv_heads, head_dim,
                    ffn_dim, d_inner, d_state, d_conv, dt_rank, depth,
                    epsilon))
            self.ln_f = nn.LayerNorm(epsilon=epsilon, in_channels=embed_dim)

    def hybrid_forward(self, F, tokens):
        from .. import nd

        b, s = tokens.shape
        x = self.blocks(self.embed(tokens))[0]
        x = self.ln_f(x)
        w = self.embed.weight.data()
        with jax.named_scope("head"):   # no child block to open it
            return nd.dot(x.reshape(-1, w.shape[1]),
                          nd.transpose(w)).reshape(b, s, -1)
