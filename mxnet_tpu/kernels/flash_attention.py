"""Flash attention: blockwise online-softmax attention as a Pallas kernel.

NEW capability beyond the reference (MXNet 1.5 has no attention op —
SURVEY §5.7: long-context handling is a first-class requirement of the TPU
rebuild, not a port). Design:

- forward: Pallas TPU kernel, grid (B*H, S_q/bq). Each program holds its
  q tile in VMEM and streams k/v tiles, keeping running (max, sumexp,
  acc) — attention memory is O(S·D) instead of O(S²), and the two matmuls
  per tile run back-to-back on the MXU from VMEM.
- backward: jax.custom_vjp with an XLA recompute of the tile softmax (the
  standard flash trade: no S² residuals saved; FLOPs are recomputed).
- off-TPU (tests, CPU) the same kernel runs under interpret=True, or the
  pure-XLA reference path via flash_attention(..., use_pallas=False).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_NEG = -1e30


def _ref_attention(q, k, v, sm_scale, causal, s_k_real):
    """Plain XLA attention, the correctness oracle + backward recompute.

    Causal masking is bottom-right aligned: query row i sits at global
    position i + (S_k - S_q), so decode-style calls (S_q=1 against a long
    KV cache) attend to the whole prefix."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    S_q, S_k = q.shape[2], k.shape[2]
    kid = jnp.arange(S_k)[None, :]
    mask = kid < s_k_real
    if causal:
        qid = jnp.arange(S_q)[:, None] + (s_k_real - S_q)
        mask = mask & (kid <= qid)
    s = jnp.where(mask[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *, bq, bk, nk,
               sm_scale, causal, s_k_real, causal_off):
    """Grid (BH, nq, nk), kb innermost: one (bq, bk) tile per step. Only a
    q tile, one k/v tile and the (m, l, acc) scratch live in VMEM — true
    streaming, O(bq·D + bk·D) on-chip whatever the sequence length. The
    scratch carries the online softmax across the kb sweep (TPU grid steps
    run sequentially, scratch persists)."""
    i = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    # causal: tiles entirely above the diagonal contribute nothing — skip
    # both MXU matmuls (halves causal-LM FLOPs)
    live = (kb * bk <= (i + 1) * bq - 1 + causal_off) if causal else True

    @pl.when(live)
    def _tile():
        q = q_ref[0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        kid = kb * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kid < s_k_real
        if causal:
            qid = i * bq + causal_off + \
                lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask &= kid <= qid
        s = jnp.where(mask, s, _NEG)
        m = m_s[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_s[:] = m_new
        l_s[:] = l_s[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[:] = acc_s[:] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _finalize():
        o_ref[0] = (acc_s[:] / jnp.maximum(l_s[:], 1e-30)).astype(
            o_ref.dtype)


def _pallas_forward(q, k, v, sm_scale, causal, interpret):
    from jax.experimental.pallas import tpu as pltpu

    B, H, S_q, D = q.shape
    S_k = k.shape[2]
    bq = min(128, S_q)
    bk = min(128, S_k)
    pq = (-S_q) % bq
    pk = (-S_k) % bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else v
    Sq_p, Sk_p = S_q + pq, S_k + pk
    qr = qp.reshape(B * H, Sq_p, D)
    kr = kp.reshape(B * H, Sk_p, D)
    vr = vp.reshape(B * H, Sk_p, D)
    nk = Sk_p // bk
    kern = functools.partial(_fa_kernel, bq=bq, bk=bk, nk=nk,
                             sm_scale=sm_scale, causal=causal,
                             s_k_real=S_k, causal_off=S_k - S_q)
    out = pl.pallas_call(
        kern,
        grid=(B * H, Sq_p // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, kb: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, kb: (b, kb, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, kb: (b, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, kb: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",  # the HLO instruction's name on a device trace
    )(qr, kr, vr)
    out = out.reshape(B, H, Sq_p, D)
    return out[:, :, :S_q] if pq else out


def _dec_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *, sm_scale):
    """Decode-mode kernel, grid (B*H,): one query row against its whole
    KV cache row in VMEM. Decode is a GEMV — the S² tiling of the
    training kernel buys nothing at S_q=1, so the cache row (S, D)
    streams in as one block (VMEM-bound: fine for serving prefix
    lengths; S·D·4 bytes must fit VMEM) and the masked softmax runs
    fused in fp32. Per-session visible lengths arrive as a prefetched
    scalar vector — one compiled kernel serves every mixed-length
    batch."""
    b = pl.program_id(0)
    n = len_ref[b]
    q = q_ref[0].astype(jnp.float32)  # (1, D)
    k = k_ref[0].astype(jnp.float32)  # (S, D)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
    kid = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kid < n, s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)  # masked scores underflow to exact +0.0
    o_ref[0] = (jnp.dot(p, v, preferred_element_type=jnp.float32)
                / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True),
                              1e-30)).astype(o_ref.dtype)


def _decode_flash(q, k, v, lengths, sm_scale, interpret):
    """One incremental decode step: q (B, H, D) attends against the
    cache k/v (B, H, S, D) masked to per-row prefix ``lengths`` (B,)
    int32. Returns (B, H, D). The Pallas path of the registered
    ``_attention_decode`` op (documented-ulp vs the lax path: fused
    fp32 softmax; the lax path is the bitwise oracle)."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = k.shape
    qr = q.reshape(B * H, 1, D)
    kr = k.reshape(B * H, S, D)
    vr = v.reshape(B * H, S, D)
    lens = jnp.repeat(lengths.astype(jnp.int32), H)  # (B*H,)
    kern = functools.partial(_dec_kernel, sm_scale=sm_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * H,),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda b, lens: (b, 0, 0)),
            pl.BlockSpec((1, S, D), lambda b, lens: (b, 0, 0)),
            pl.BlockSpec((1, S, D), lambda b, lens: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda b, lens: (b, 0, 0)),
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, 1, D), q.dtype),
        interpret=interpret,
        name="flash_decode",
    )(lens, qr, kr, vr)
    return out.reshape(B, H, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, sm_scale, causal, impl):
    if impl == "xla":
        return _ref_attention(q, k, v, sm_scale, causal, k.shape[2])
    return _pallas_forward(q, k, v, sm_scale, causal,
                           impl == "interpret")


def _flash_fwd(q, k, v, sm_scale, causal, impl):
    return _flash(q, k, v, sm_scale, causal, impl), (q, k, v)


def _flash_bwd(sm_scale, causal, impl, res, do):
    """Backward by q-chunk recompute (lax.scan): peak extra memory is
    O(chunk·S_k) instead of materializing the full S_q×S_k attention
    matrix — long-context training keeps the flash memory property."""
    q, k, v = res
    S_q, S_k = q.shape[2], k.shape[2]
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    chunk = min(512, S_q)
    pad = (-S_q) % chunk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))).astype(jnp.float32)
    dop = jnp.pad(do, ((0, 0), (0, 0), (0, pad), (0, 0))).astype(
        jnp.float32)  # zero do on padding → padded rows contribute nothing
    nchunk = (S_q + pad) // chunk
    B, H, _, D = q.shape
    qc = qp.reshape(B, H, nchunk, chunk, D).transpose(2, 0, 1, 3, 4)
    doc = dop.reshape(B, H, nchunk, chunk, D).transpose(2, 0, 1, 3, 4)
    kid = jnp.arange(S_k)[None, :]
    off = S_k - S_q  # bottom-right causal alignment

    def step(carry, xs):
        dk_acc, dv_acc, ci = carry
        qb, dob = xs  # (B, H, chunk, D)
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, kf) * sm_scale
        if causal:
            qid = ci * chunk + jnp.arange(chunk)[:, None] + off
            s = jnp.where((kid <= qid)[None, None], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        dv_acc += jnp.einsum("bhqk,bhqd->bhkd", p, dob)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dob, vf)
        ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
        dqb = jnp.einsum("bhqk,bhkd->bhqd", ds, kf) * sm_scale
        dk_acc += jnp.einsum("bhqk,bhqd->bhkd", ds, qb) * sm_scale
        return (dk_acc, dv_acc, ci + 1), dqb

    with jax.named_scope("flash_bwd"):
        (dk, dv, _), dqs = lax.scan(
            step, (jnp.zeros_like(kf), jnp.zeros_like(vf), 0), (qc, doc))
    dq = dqs.transpose(1, 2, 0, 3, 4).reshape(B, H, S_q + pad, D)[
        :, :, :S_q]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, sm_scale=None, causal=False, use_pallas=None):
    """Scaled dot-product attention over (B, H, S, D) tensors.

    use_pallas: None = pallas on TPU / XLA elsewhere; True forces the
    kernel (interpreted off-TPU — slow, for testing); False forces XLA.
    """
    if causal and q.shape[-2] > k.shape[-2]:
        # bottom-right-aligned causal with S_q > S_k gives query rows a
        # negative offset — rows with zero visible keys would come out of
        # the all-masked online-softmax as an unnormalized average of V
        raise ValueError(
            "flash_attention(causal=True) requires S_q <= S_k, got "
            f"S_q={q.shape[-2]} S_k={k.shape[-2]}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if use_pallas is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    elif use_pallas:
        impl = "pallas" if jax.default_backend() == "tpu" else "interpret"
    else:
        impl = "xla"
    with jax.named_scope("attn"):
        return _flash(q, k, v, float(sm_scale), bool(causal), impl)
