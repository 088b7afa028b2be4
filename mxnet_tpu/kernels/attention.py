"""Fused attention cluster op: score → softmax → weighted sum.

The clustering pass matches the composed primitive form
``batch_dot(softmax(batch_dot(q, k, transpose_b=True) [*/ scale]), v)``
and lowers it here. Two implementations:

- ``lax`` (portable fallback, bit-identical): replay the registered
  ``batch_dot`` / scalar-scale / ``softmax`` bodies in one dispatch.
- ``pallas`` (TPU): the blockwise online-softmax flash kernel from
  ``kernels/flash_attention.py`` — O(S·D) memory instead of the
  materialized S² score matrix (documented-ulp: online softmax
  reassociates the reduction). ``impl="interpret"`` runs the same
  kernel interpreted for off-TPU parity tests.

Round 21 adds the **decode mode** — transformer incremental attention
as two single-output ops a KV-cache decoder block threads through the
stateful serving stack (the per-token op stream is tiny and
dispatch-bound, exactly the pattern XLA's automatic fusion handles
worst, so each is ONE registered kernel):

- ``_cache_append``: write this step's projected K (or V) row into the
  session's cache at its position — an exact XLA scatter, bitwise
  transparent to every other cache entry.
- ``_attention_decode``: one query row attends against the cache
  positions ``<= pos`` — no prefix re-execution, O(S·D) per step
  regardless of position. ``impl="lax"`` is the bitwise path;
  ``"pallas"``/``"interpret"`` ride the decode flash kernel
  (documented-ulp).
"""
from __future__ import annotations

from ..ndarray.registry import get_op, register
from . import _count
from .cost_model import pallas_fits_vmem

_NEG = -1e30


def _replay_lax(q, k, v, scale_op, scale, softmax_kw):
    """The unfused graph, replayed body-for-body in one dispatch."""
    bd = get_op("batch_dot").fn
    s = bd(q, k, transpose_b=True)
    if scale_op == "mul":
        s = get_op("broadcast_mul_scalar").fn(s, scalar=scale)
    elif scale_op == "div":
        s = get_op("broadcast_div_scalar").fn(s, scalar=scale)
    p = get_op("softmax").fn(s, **dict(softmax_kw))
    return bd(p, v)


@register("_fused_attention", namespaces=())
def _fused_attention(q, k, v, scale_op="none", scale=1.0, softmax_kw=(),
                     impl="lax"):
    """Fused score→softmax→weighted-sum attention cluster emitted by
    the analysis/fusion clustering pass over (B, S, D) operands.
    ``impl="lax"`` replays the registered batch_dot/softmax bodies in
    one dispatch (bit-identical to the unfused subgraph);
    ``impl="pallas"`` runs the flash-attention TPU kernel
    (documented-ulp: online softmax); ``impl="interpret"`` interprets
    that kernel off-TPU for parity tests. (Reference: the composed
    src/operator/tensor/dot.cc + nn/softmax.cc subgraph.)"""
    if impl in ("pallas", "interpret"):
        from .flash_attention import _flash

        sm_scale = (float(scale) if scale_op == "mul"
                    else 1.0 / float(scale) if scale_op == "div"
                    else 1.0)
        # flash operates on (B, H, S, D): ride a singleton head axis
        out = _flash(q[:, None], k[:, None], v[:, None], sm_scale,
                     False, impl)
        return out[:, 0]
    return _replay_lax(q, k, v, scale_op, scale, softmax_kw)


# ---------------------------------------------------------------------------
# decode mode: KV-cache incremental attention (round 21)

@register("_cache_append", differentiable=False, namespaces=())
def _cache_append(cache, step, pos):
    """Append one decode step's projected row into a KV cache: write
    ``step`` (B, E) into ``cache`` (B, S, E) at per-row position
    ``pos`` (B, 1) int — ONE exact XLA scatter. Every untouched cache
    entry passes through bitwise, which is what lets the paged state
    store write back only the page the step touched."""
    import jax.numpy as jnp

    B = cache.shape[0]
    idx = jnp.reshape(pos, (B,)).astype(jnp.int32)
    return cache.at[jnp.arange(B), idx].set(step.astype(cache.dtype))


@register("_attention_decode", differentiable=False, namespaces=())
def _attention_decode(q, k_cache, v_cache, pos, num_heads=1,
                      sm_scale=1.0, impl="lax"):
    """Incremental decode attention: ONE query row (B, E) against the
    session's KV cache (B, S, E), masked to positions ``<= pos``
    (inclusive — the step's own K/V was just appended at ``pos``).
    O(S·D) per step with no prefix re-execution; cache entries past
    the mask never contribute (their scores exp-underflow to exact
    +0.0), so gathered garbage/zero pages beyond the prefix are
    harmless. ``impl="lax"`` is the bitwise-reproducible path the
    offline unroll oracle shares; ``"pallas"``/``"interpret"`` run the
    decode flash kernel from ``kernels/flash_attention.py``
    (documented-ulp: fused masked softmax in fp32 scratch)."""
    import jax
    import jax.numpy as jnp

    B, S, E = k_cache.shape
    H = int(num_heads)
    D = E // H
    n = jnp.reshape(pos, (B,)).astype(jnp.int32) + 1  # visible length
    if impl == "pallas" and not pallas_fits_vmem(
            "attention_decode", (S, D), k_cache.dtype.itemsize):
        # the kernel holds a whole (S, D) K row and V row per grid
        # step: past the VMEM bound the chip's compiler refuses it
        _count("fallback_vmem_bound")
        impl = "lax"
    if impl in ("pallas", "interpret"):
        from .flash_attention import _decode_flash

        qh = q.reshape(B, H, D)
        kh = k_cache.reshape(B, S, H, D).transpose(0, 2, 1, 3)
        vh = v_cache.reshape(B, S, H, D).transpose(0, 2, 1, 3)
        out = _decode_flash(qh, kh, vh, n, float(sm_scale),
                            impl == "interpret")
        return out.reshape(B, E)
    qh = q.reshape(B, H, D)
    kh = k_cache.reshape(B, S, H, D)
    vh = v_cache.reshape(B, S, H, D)
    s = jnp.einsum("bhd,bshd->bhs", qh, kh,
                   preferred_element_type=jnp.float32) * float(sm_scale)
    mask = jnp.arange(S)[None, None, :] < n[:, None, None]
    s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", p.astype(vh.dtype), vh)
    return out.reshape(B, E)
