"""Gated delta rule: the recurrence of a Gated DeltaNet layer (Yang et
al., arXiv:2412.06464) in its chunked form (arXiv:2406.06484), as Pallas
kernels.

Per value head, a state ``M`` (dk x dv), ``M_0 = 0``, and for t = 1..S

    M   = exp(g_t) * M
    d_t = beta_t * (v_t - M^T k_t)
    M   = M + k_t d_t^T
    o_t = M^T q_t

with ``g <= 0`` the log of the decay and ``beta`` in [0, 1]. Position by
position that is S dependent steps of rank-one work; in chunks of ``C``
positions it is dense products. Within a chunk, ``c_i = sum_{j<=i} g_j``:

- *preparation*, chunk by chunk, independent of the state: ``A =
  strict_lower(beta_i k_i.k_j exp(c_i - c_j))``, ``T = (I + A)^-1`` (unit
  lower triangular; ``A`` is nilpotent, so ``T = (I - A)(I + A^2)(I +
  A^4)...``, log2(C) squarings), ``W = T (beta exp(c) k)``, ``U = T (beta
  v)``, ``P = lower(q_i.k_j exp(c_i - c_j))``, ``q~ = q exp(c)``, ``k~ =
  k exp(c_C - c)``, ``e = exp(c_C)``. Every exponent is <= 0 (the
  difference is masked before the exponential), so a strong decay
  underflows to 0 and nothing overflows.
- *the walk*, chunk after chunk with the state carried: ``V' = U - W M``,
  ``O = q~ M + P V'``, ``M <- e M + k~^T V'``.

``g``, the decays, ``A`` and ``T`` are float32; every product takes its
operands in the input's dtype with float32 accumulation (``T``, the state
and a float32 cotangent are rounded to it where they enter a product;
the series of ``T`` itself multiplies float32, whole for float32 inputs
and in three bfloat16 parts, a relative 2^-16, where ``T`` is rounded to
a narrower type anyway).

Four kernels, named so that a device trace shows them by instruction,
all on the grid (B, value heads, steps), a step ``_STEP_ROWS`` positions
(several chunks, unrolled):

- ``gdn_prep_fwd``: the preparation of a step's chunks in VMEM, from q,
  k (read in place at the key head), v, g, beta; it also writes ``T``.
  The backward's call of it, with ``T`` handed in, is named
  ``gdn_prep_refwd``, so that a trace tells the two passes apart.
- ``gdn_fwd``: the walk, steps innermost and in order, the state float32
  VMEM scratch that lives across a head's steps, each chunk's incoming
  state written out for the backward.
- ``gdn_bwd``: the walk's VJP, the same grid walked in reverse with
  ``dM`` carried in scratch, ``V'`` recomputed from the stored state; it
  gives the gradients of the walk's six inputs.
- ``gdn_prep_bwd``: the preparation's VJP, written out by hand: from the
  walk's six gradients to those of q, k (a value head each, summed over
  the key head's group after the kernel), v, g and beta. ``T``'s own
  derivative is two products of it, ``dA = -T^T dT T^T``.

One ``custom_vjp`` holds the rule: its forward keeps q, k, v, g, beta,
the states and ``T`` (float32, (C, C) a chunk); its backward runs the
preparation again with ``T`` handed in, so that the prepared arrays (five
the size of q and a (S, C) one, a head) do not live from forward to
backward.

The plain twin (``use_pallas=False``, off-TPU, and whatever ``eligible``
refuses) is the same algebra in ``jax.numpy`` (``_prepare``) with the
walk under ``lax.scan``, differentiated by JAX: what every test compares
the kernels with. ``kernels.counters()`` counts which one a trace
lowered, ``gdn_pallas`` or ``gdn_plain``, and ``gdn_chunks``: the chunks
a head a trace's passes walk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import _count
from .cost_model import _TILE_COLS, _VMEM_BUDGET_BYTES

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_NN = (((1,), (0,)), ((), ()))
_BMM = (((2,), (1,)), ((0,), (0,)))     # a @ b, a chunk a batch

#: positions a grid step takes (whole chunks of it, unrolled): a chunk
#: of 64 alone is 9 MFLOP of the walk, less than a grid step's own cost
_STEP_ROWS = 256


def _precision(dtype):
    """float32 operands go to the MXU whole; narrower ones as they are."""
    return lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _neumann(a, eye, mm):
    """``(I + a)^-1`` for strictly lower triangular ``a`` (..., C, C):
    ``a^C = 0``, so the Neumann series is the finite product
    ``(I - a)(I + a^2)(I + a^4)...(I + a^(C/2))``."""
    inv, power, reach = eye - a, a, 2
    while reach < a.shape[-1]:
        power = mm(power, power)
        inv = mm(inv, eye + power)
        reach *= 2
    return inv


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _unit_lower_inverse(a, exact):
    """``T = (I + a)^-1`` for strictly lower triangular ``a``
    (..., C, C), float32, in ``jax.numpy``. Its derivative is two
    products of the result, ``da = -T^T dT T^T``, not the series' own.
    ``exact``: float32 products whole, else in three bfloat16 parts."""
    return _inverse_fwd(a, exact)[0]


def _f32_matmul(exact):
    return functools.partial(jnp.matmul, precision=(
        lax.Precision.HIGHEST if exact else lax.Precision.HIGH))


def _inverse_fwd(a, exact):
    t = _neumann(a, jnp.eye(a.shape[-1], dtype=a.dtype), _f32_matmul(exact))
    return t, t


def _inverse_bwd(exact, t, dt):
    mm, tt = _f32_matmul(exact), jnp.swapaxes(t, -1, -2)
    return (-mm(mm(tt, dt), tt),)


_unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _prepare(q, k, v, g, beta, chunk):
    """The walk's inputs in ``jax.numpy``: ``(W, U, q~, k~ (B, Hv, S, d),
    P (B, Hv, S, C), e (B, Hv, S / C) float32)`` of q, k (B, Hk, S, dk),
    v (B, Hv, S, dv) and g, beta (B, Hv, S), S a multiple of the chunk;
    key head j serves value heads ``j * Hv / Hk`` onwards."""
    b, hv, s, dv = v.shape
    hk, dk = k.shape[1], k.shape[3]
    rep, n, dt = hv // hk, s // chunk, v.dtype
    prec = _precision(dt)
    qc = q.reshape(b, hk, 1, n, chunk, dk)
    kc = k.reshape(b, hk, 1, n, chunk, dk)
    vc = v.reshape(b, hk, rep, n, chunk, dv)
    gc = g.astype(jnp.float32).reshape(b, hk, rep, n, chunk)
    bc = beta.astype(jnp.float32).reshape(b, hk, rep, n, chunk)

    c = jnp.cumsum(gc, axis=-1)
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.exp(jnp.where(row >= col, c[..., :, None] - c[..., None, :],
                              -jnp.inf))            # 0 above the diagonal
    dots = functools.partial(jnp.einsum, "...cd,...md->...cm",
                             precision=prec,
                             preferred_element_type=jnp.float32)
    a = jnp.where(row > col, bc[..., None] * dots(kc, kc) * decay, 0.0)
    t = _unit_lower_inverse(a, dt == jnp.float32).astype(dt)
    p = (dots(qc, kc) * decay).astype(dt)
    ec = jnp.exp(c)
    mm = functools.partial(jnp.matmul, precision=prec,
                           preferred_element_type=jnp.float32)
    w = mm(t, ((bc * ec)[..., None] * kc).astype(dt)).astype(dt)
    u = mm(t, (bc[..., None] * vc).astype(dt)).astype(dt)
    qt = (qc * ec[..., None]).astype(dt)
    last = c[..., -1:]
    kt = (kc * jnp.exp(last - c)[..., None]).astype(dt)
    return (w.reshape(b, hv, s, dk), u.reshape(b, hv, s, dv),
            qt.reshape(b, hv, s, dk), kt.reshape(b, hv, s, dk),
            p.reshape(b, hv, s, chunk),
            jnp.exp(last[..., 0]).reshape(b, hv, n))


# ---------------------------------------------------------------------------
# the walk, plain

def _walk_scan(w, u, qt, kt, p, e, chunk):
    """The walk under ``lax.scan`` over chunks: o (B, H, S, dv)."""
    b, h, s, dv = u.shape
    dk, n, dt = w.shape[3], s // chunk, u.dtype
    prec = _precision(dt)
    dot = functools.partial(lax.dot_general, precision=prec,
                            preferred_element_type=jnp.float32)

    def head(w, u, qt, kt, p, e):       # (n, C, d) ..., e (n,)
        def step(m, xs):
            wc, uc, qc, kc, pc, ec = xs
            mo = m.astype(dt)
            vp = uc.astype(jnp.float32) - dot(wc, mo, _NN)
            vpo = vp.astype(dt)
            o = dot(qc, mo, _NN) + dot(pc, vpo, _NN)
            return m * ec + dot(kc, vpo, _TN), o.astype(dt)

        _, o = lax.scan(step, jnp.zeros((dk, dv), jnp.float32),
                        (w, u, qt, kt, p, e))
        return o

    chunks = lambda a: a.reshape(b, h, n, chunk, a.shape[-1])  # noqa: E731
    o = jax.vmap(jax.vmap(head))(chunks(w), chunks(u), chunks(qt),
                                 chunks(kt), chunks(p), e)
    return o.reshape(b, h, s, dv)


# ---------------------------------------------------------------------------
# the preparation, kernels

def _kernel_dots(dt):
    """``(dot, dot32)`` inside a kernel: products of operands in the
    input's dtype, and products of float32 operands, which for float32
    inputs go to the MXU whole (six passes) and otherwise, where the
    result is rounded to a narrower type anyway, as three bfloat16
    products of the operands' high and low halves, float32 accumulated
    (a relative 2^-16): Mosaic has no precision between its one pass and
    ``highest``."""
    dot = functools.partial(lax.dot_general, precision=_precision(dt),
                            preferred_element_type=jnp.float32)
    if dt == jnp.float32:
        return dot, dot

    def halves(x):
        high = x.astype(jnp.bfloat16)
        return high, (x - high.astype(jnp.float32)).astype(jnp.bfloat16)

    def dot32(x, y, dims):
        (xh, xl), (yh, yl) = halves(x), halves(y)
        return dot(xh, yh, dims) + dot(xh, yl, dims) + dot(xl, yh, dims)

    return dot, dot32


def _chunk_terms(q, k, v, g_row, b_row, dot, with_kk=True):
    """What both passes of the preparation need of one chunk, float32,
    vectors over the chunk's positions as columns (C, 1): ``beta``, the
    decay matrix (0 above the diagonal), ``q.k^T``, ``k.k^T`` (not where
    ``T`` is known), ``grow = exp(c)``, ``fade = exp(c_C - c)``, ``e =
    exp(c_C)`` (1, 1), and ``beta exp(c) k`` and ``beta v`` in the
    input's dtype; ``c`` is the running sum of g."""
    n = g_row.shape[-1]
    row = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    lower, eye = (row >= col).astype(jnp.float32), \
        (row == col).astype(jnp.float32)
    c_col = jnp.sum(lower * g_row, axis=1, keepdims=True)
    c_row = jnp.sum(eye * c_col, axis=0, keepdims=True)
    beta = jnp.sum(eye * b_row, axis=1, keepdims=True)
    decay = jnp.exp(jnp.where(row >= col, c_col - c_row, -jnp.inf))
    grow, last = jnp.exp(c_col), jnp.sum(g_row, axis=1, keepdims=True)
    kb = (beta * grow * k.astype(jnp.float32)).astype(k.dtype)
    vb = (beta * v.astype(jnp.float32)).astype(v.dtype)
    return dict(row=row, col=col, lower=lower, eye=eye, beta=beta,
                decay=decay, grow=grow, fade=jnp.exp(last - c_col),
                e=jnp.exp(last), kb=kb, vb=vb, qk=dot(q, k, _NT),
                kk=dot(k, k, _NT) if with_kk else None)


def _prep_fwd_kernel(*refs, chunk, sub, known):
    """Grid (B, H, steps): every chunk of the step on its own, but for
    the series of ``T``, whose ten dependent products run for the step's
    chunks as one batch (alone a chunk's wait for one another: 3.6 ms a
    layer for 1.7, PERF.md section 6, PR 36). With ``known`` the chunks'
    ``T`` comes in and is not computed."""
    q_ref, k_ref, v_ref, g_ref, b_ref = refs[:5]
    t_ref = refs[5] if known else refs[11]
    w_ref, u_ref, qt_ref, kt_ref, p_ref, e_ref = refs[5 + known:11 + known]
    dt = v_ref.dtype
    dot, dot32 = _kernel_dots(dt)
    terms = []
    for c in range(sub):
        rows = pl.ds(c * chunk, chunk)
        x = _chunk_terms(q_ref[rows, :], k_ref[rows, :], v_ref[rows, :],
                         g_ref[c], b_ref[c], dot, with_kk=not known)
        terms.append(x)
        if not known:       # A, staged in T's own block
            t_ref[c] = jnp.where(x["row"] > x["col"],
                                 x["beta"] * x["kk"] * x["decay"], 0.0)
    if not known:
        t_ref[...] = _neumann(t_ref[...], terms[0]["eye"],
                              lambda m, n: dot32(m, n, _BMM))
    for c, x in enumerate(terms):
        rows = pl.ds(c * chunk, chunk)
        t = t_ref[c].astype(dt)
        w_ref[rows, :] = dot(t, x["kb"], _NN).astype(dt)
        u_ref[rows, :] = dot(t, x["vb"], _NN).astype(dt)
        p_ref[rows, :] = (x["qk"] * x["decay"]).astype(dt)
        qt_ref[rows, :] = (q_ref[rows, :].astype(jnp.float32)
                           * x["grow"]).astype(dt)
        kt_ref[rows, :] = (k_ref[rows, :].astype(jnp.float32)
                           * x["fade"]).astype(dt)
        e_ref[c] = jnp.broadcast_to(x["e"], e_ref.shape[1:])


def _prep_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, t_ref, dw_ref, du_ref,
                     dqt_ref, dkt_ref, dp_ref, de_ref,
                     dq_ref, dk_ref, dv_ref, dg_ref, db_ref, *, chunk, sub):
    """The preparation's VJP, chunk by chunk: the forward's terms again,
    then each of its lines backwards. A float32 (C, C) cotangent is
    rounded to the input's dtype where it multiplies q or k."""
    dt = v_ref.dtype
    f32 = jnp.float32
    dot, dot32 = _kernel_dots(dt)
    for c in range(sub):
        rows = pl.ds(c * chunk, chunk)
        q, k, v = q_ref[rows, :], k_ref[rows, :], v_ref[rows, :]
        qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
        x = _chunk_terms(q, k, v, g_ref[c], b_ref[c], dot)
        beta, decay, grow, fade = x["beta"], x["decay"], x["grow"], x["fade"]
        t32 = t_ref[c]
        t = t32.astype(dt)
        dw, du = dw_ref[rows, :], du_ref[rows, :]
        # W = T kb, U = T vb
        dt_mat = dot(dw, x["kb"], _NT) + dot(du, x["vb"], _NT)
        dkb, dvb = dot(t, dw, _TN), dot(t, du, _TN)
        # T = (I + A)^-1, A strictly lower
        da = jnp.where(x["row"] > x["col"],
                       -dot32(dot32(t32, dt_mat, _TN), t32, _NT), 0.0)
        # A = beta_i kk_ij decay_ij;  P = qk_ij decay_ij
        dp = dp_ref[rows, :].astype(f32)
        gd = da * decay
        dkk = (beta * gd).astype(dt)
        dqk = (dp * decay).astype(dt)
        dbeta = jnp.sum(gd * x["kk"], axis=1, keepdims=True)
        ed = (da * beta * x["kk"] + dp * x["qk"]) * decay
        dc = jnp.sum(ed, axis=1, keepdims=True) - jnp.sum(
            x["eye"] * jnp.sum(ed, axis=0, keepdims=True), axis=1,
            keepdims=True)
        dk = dot(dkk, k, _NN) + dot(dkk, k, _TN) + dot(dqk, q, _TN)
        dq = dot(dqk, k, _NN)
        # q~ = q exp(c), k~ = k exp(c_C - c), e = exp(c_C)
        dqt, dkt = dqt_ref[rows, :].astype(f32), dkt_ref[rows, :].astype(f32)
        dq += dqt * grow
        dc += jnp.sum(dqt * qf, axis=1, keepdims=True) * grow
        dk += dkt * fade
        faded = jnp.sum(dkt * kf, axis=1, keepdims=True) * fade
        dc -= faded
        dlast = jnp.sum(faded, axis=0, keepdims=True) \
            + x["e"] * jnp.sum(de_ref[c], axis=1, keepdims=True)
        # kb = beta exp(c) k, vb = beta v
        scale = beta * grow
        dk += dkb * scale
        dscale = jnp.sum(dkb * kf, axis=1, keepdims=True)
        dbeta += dscale * grow + jnp.sum(dvb * vf, axis=1, keepdims=True)
        dc += dscale * scale
        dq_ref[rows, :] = dq.astype(dt)
        dk_ref[rows, :] = dk.astype(dt)
        dv_ref[rows, :] = (dvb * beta).astype(dt)
        # c = cumsum(g): c_C is the last row's
        dc += jnp.where(x["row"][:, :1] == chunk - 1, dlast, 0.0)
        dg_ref[c] = jnp.sum(x["lower"] * dc, axis=0, keepdims=True)
        db_ref[c] = jnp.sum(x["eye"] * dbeta, axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# the walk, kernels

def _fwd_kernel(w_ref, u_ref, q_ref, k_ref, p_ref, e_ref, o_ref, s_ref, m_scr,
                *, chunk, sub, prec):
    """Grid (B, H, steps), steps innermost: ``sub`` chunks a step, the
    state in ``m_scr`` from a head's first step to its last."""
    dt = o_ref.dtype
    dot = functools.partial(lax.dot_general, precision=prec,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == 0)
    def _first():
        m_scr[...] = jnp.zeros_like(m_scr)

    m = m_scr[...]
    for c in range(sub):
        rows = pl.ds(c * chunk, chunk)
        mo = m.astype(dt)
        s_ref[c] = mo
        vp = u_ref[rows, :].astype(jnp.float32) - dot(w_ref[rows, :], mo, _NN)
        vpo = vp.astype(dt)
        o = dot(q_ref[rows, :], mo, _NN) + dot(p_ref[rows, :], vpo, _NN)
        o_ref[rows, :] = o.astype(dt)
        m = m * e_ref[c] + dot(k_ref[rows, :], vpo, _TN)
    m_scr[...] = m


def _bwd_kernel(w_ref, u_ref, q_ref, k_ref, p_ref, e_ref, s_ref, do_ref,
                dw_ref, du_ref, dq_ref, dk_ref, dp_ref, de_ref, dm_scr,
                *, chunk, sub, prec):
    """The same grid, the index maps reversed: a head's last step first,
    in it the last chunk first, ``dM`` in ``dm_scr``."""
    dt = du_ref.dtype
    dot = functools.partial(lax.dot_general, precision=prec,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == 0)
    def _first():
        dm_scr[...] = jnp.zeros_like(dm_scr)

    dm = dm_scr[...]
    for c in reversed(range(sub)):
        rows = pl.ds(c * chunk, chunk)
        mo, do = s_ref[c], do_ref[rows, :]
        w, kt = w_ref[rows, :], k_ref[rows, :]
        vpo = (u_ref[rows, :].astype(jnp.float32)
               - dot(w, mo, _NN)).astype(dt)
        dmo = dm.astype(dt)
        dvp = dot(p_ref[rows, :], do, _TN) + dot(kt, dmo, _NN)
        dvpo = dvp.astype(dt)
        du_ref[rows, :] = dvpo
        dw_ref[rows, :] = (-dot(dvpo, mo, _NT)).astype(dt)
        dq_ref[rows, :] = dot(do, mo, _NT).astype(dt)
        dk_ref[rows, :] = dot(vpo, dmo, _NT).astype(dt)
        dp_ref[rows, :] = dot(do, vpo, _NT).astype(dt)
        de_ref[c] = jnp.sum(mo.astype(jnp.float32) * dm, axis=0,
                            keepdims=True)
        dm = dm * e_ref[c] + dot(q_ref[rows, :], do, _TN) \
            - dot(w, dvpo, _TN)
    dm_scr[...] = dm


def step_rows(s, chunk):
    """Positions a grid step takes: whole chunks, ``_STEP_ROWS`` of them
    or the (chunk-padded) sequence where that is shorter."""
    s_p = -(-s // chunk) * chunk
    return min(max(_STEP_ROWS // chunk, 1) * chunk, s_p)


def eligible(dk, dv, chunk, itemsize):
    """Can the kernels take these blocks? Both head sizes on the 128
    lanes, a chunk in whole sublane tiles of the dtype, and the blocks of
    the kernel that holds most (``gdn_prep_bwd``: q, k, three gradients
    in and two out of a key head's width, v, one gradient in and one out
    of a value head's, the (rows, C) one and a step's float32 ``T``),
    double-buffered by the pipeline, with the walk's float32 state and a
    chunk's float32 working copies inside the VMEM budget."""
    if dk % _TILE_COLS or dv % _TILE_COLS or chunk % (32 // itemsize):
        return False
    rows = max(_STEP_ROWS, chunk)
    lanes = max(chunk, _TILE_COLS)
    blocks = rows * itemsize * (7 * dk + 3 * dv + lanes) \
        + rows // chunk * (chunk * lanes * 4 + dk * dv * itemsize)
    work = 4 * (3 * dk * dv + 12 * chunk * max(dk, dv, lanes))
    return 2 * blocks + work <= _VMEM_BUDGET_BYTES


def _specs(rows, sub, chunk, dk, dv, at=lambda n: n, rep=1):
    """Block specs of a head's arrays at its step ``at(n)``: (rows, dk),
    (rows, dv) and (rows, C) of the sequence; a chunk's (1, dv), (1, C),
    (dk, dv) and (C, C); and (rows, dk) of the key head that serves the
    value head."""
    def seq(width, group=1):
        return pl.BlockSpec((None, None, rows, width),
                            lambda b, h, n: (b, h // group, at(n), 0))

    def per_chunk(*tail):
        return pl.BlockSpec((None, None, sub) + tail,
                            lambda b, h, n: (b, h, at(n)) + (0,) * len(tail))

    return dict(k=seq(dk), v=seq(dv), p=seq(chunk), key=seq(dk, rep),
                e=per_chunk(1, dv), g=per_chunk(1, chunk),
                state=per_chunk(dk, dv), t=per_chunk(chunk, chunk))


def _call(kernel, name, grid, in_specs, out_specs, out_shape, interpret,
          state=None):
    """One ``pallas_call`` on the grid (B, H, steps); a walk's steps run
    in order with a float32 scratch of the ``state``'s shape, the others'
    any way."""
    from jax.experimental.pallas import tpu as pltpu

    scratch = [pltpu.VMEM(state, jnp.float32)] if state else []
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary" if state else "parallel")),
        interpret=interpret,
        name=name)      # the HLO instruction's name on a device trace


def _by_chunk(a, chunk):
    """(B, H, S) -> (B, H, N, 1, C): a chunk's positions along the
    lanes."""
    b, h, s = a.shape
    return a.reshape(b, h, s // chunk, 1, chunk)


def _prepare_forward(q, k, v, g, beta, chunk, interpret, known=None):
    """The kernel ``gdn_prep_fwd``: ``(W, U, q~, k~, P, e (B, H, N, 1,
    dv), T (B, H, N, C, C) float32)``; ``known`` is a ``T`` kept from an
    earlier call on the same inputs, and the call then the backward's
    (``gdn_prep_refwd``)."""
    b, h, s, dv = v.shape
    dk, n, dt = k.shape[3], s // chunk, v.dtype
    rows = step_rows(s, chunk)
    at = _specs(rows, rows // chunk, chunk, dk, dv, rep=h // k.shape[1])
    shape = lambda *dims, dtype=dt: jax.ShapeDtypeStruct(  # noqa: E731
        (b, h) + dims, dtype)
    given = known is not None
    out = _call(
        functools.partial(_prep_fwd_kernel, chunk=chunk, sub=rows // chunk,
                          known=given),
        "gdn_prep_refwd" if given else "gdn_prep_fwd", (b, h, s // rows),
        [at["key"], at["key"], at["v"], at["g"], at["g"]]
        + [at["t"]] * given,
        [at["k"], at["v"], at["k"], at["k"], at["p"], at["e"]]
        + [at["t"]] * (not given),
        [shape(s, dk), shape(s, dv), shape(s, dk), shape(s, dk),
         shape(s, chunk), shape(n, 1, dv, dtype=jnp.float32)]
        + [shape(n, chunk, chunk, dtype=jnp.float32)] * (not given),
        interpret,
    )(q, k, v, _by_chunk(g, chunk), _by_chunk(beta, chunk),
      *([known] if given else []))
    return tuple(out[:6]), (known if given else out[6])


def _prepare_backward(q, k, v, g, beta, t, grads, chunk, interpret):
    """The kernel ``gdn_prep_bwd``: gradients of q, k, v, g, beta from
    those of the walk's six inputs."""
    b, h, s, dv = v.shape
    hk, dk, n, dt = k.shape[1], k.shape[3], s // chunk, v.dtype
    rows = step_rows(s, chunk)
    at = _specs(rows, rows // chunk, chunk, dk, dv, rep=h // hk)
    shape = lambda *dims, dtype=dt: jax.ShapeDtypeStruct(  # noqa: E731
        (b, h) + dims, dtype)
    dq, dk_, dv_, dg, db = _call(
        functools.partial(_prep_bwd_kernel, chunk=chunk, sub=rows // chunk),
        "gdn_prep_bwd", (b, h, s // rows),
        [at["key"], at["key"], at["v"], at["g"], at["g"], at["t"],
         at["k"], at["v"], at["k"], at["k"], at["p"], at["e"]],
        [at["k"], at["k"], at["v"], at["g"], at["g"]],
        [shape(s, dk), shape(s, dk), shape(s, dv),
         shape(n, 1, chunk, dtype=jnp.float32),
         shape(n, 1, chunk, dtype=jnp.float32)],
        interpret,
    )(q, k, v, _by_chunk(g, chunk), _by_chunk(beta, chunk), t, *grads)
    # a key head's gradient is the sum over the value heads it serves
    group = lambda a: a.reshape(b, hk, h // hk, s, dk).astype(  # noqa: E731
        jnp.float32).sum(2).astype(dt)
    return (group(dq), group(dk_), dv_, dg.reshape(b, h, s),
            db.reshape(b, h, s).astype(beta.dtype))


def _walk_forward(w, u, qt, kt, p, e, chunk, interpret):
    """(o, states): the kernel ``gdn_fwd``; ``e`` (B, H, N, 1, dv);
    states (B, H, N, dk, dv) in the input's dtype, each chunk's incoming
    state."""
    b, h, s, dv = u.shape
    dk, n, dt = w.shape[3], s // chunk, u.dtype
    rows = step_rows(s, chunk)
    _count("gdn_chunks", n)
    at = _specs(rows, rows // chunk, chunk, dk, dv)
    return _call(
        functools.partial(_fwd_kernel, chunk=chunk, sub=rows // chunk,
                          prec=_precision(dt)),
        "gdn_fwd", (b, h, s // rows),
        [at["k"], at["v"], at["k"], at["k"], at["p"], at["e"]],
        [at["v"], at["state"]],
        [jax.ShapeDtypeStruct((b, h, s, dv), dt),
         jax.ShapeDtypeStruct((b, h, n, dk, dv), dt)],
        interpret, state=(dk, dv),
    )(w, u, qt, kt, p, e)


def _walk_backward(w, u, qt, kt, p, e, states, do, chunk, interpret):
    """Gradients of the walk's six inputs, ``e``'s (B, H, N, 1, dv) with
    the sum over its lanes left to its reader: the kernel ``gdn_bwd``."""
    b, h, s, dv = u.shape
    dk, n, dt = w.shape[3], s // chunk, u.dtype
    rows = step_rows(s, chunk)
    steps = s // rows
    _count("gdn_chunks", n)
    at = _specs(rows, rows // chunk, chunk, dk, dv,
                at=lambda i: steps - 1 - i)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, dt)  # noqa: E731
    return _call(
        functools.partial(_bwd_kernel, chunk=chunk, sub=rows // chunk,
                          prec=_precision(dt)),
        "gdn_bwd", (b, h, steps),
        [at["k"], at["v"], at["k"], at["k"], at["p"], at["e"], at["state"],
         at["v"]],
        [at["k"], at["v"], at["k"], at["k"], at["p"], at["e"]],
        [like(w), like(u), like(qt), like(kt), like(p),
         jax.ShapeDtypeStruct(e.shape, jnp.float32)],
        interpret, state=(dk, dv),
    )(w, u, qt, kt, p, e, states, do.astype(dt))


# ---------------------------------------------------------------------------
# the differentiable entry

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, chunk, interpret):
    return _rule_fwd(q, k, v, g, beta, chunk, interpret)[0]


def _rule_fwd(q, k, v, g, beta, chunk, interpret):
    prepared, t = _prepare_forward(q, k, v, g, beta, chunk, interpret)
    o, states = _walk_forward(*prepared, chunk, interpret)
    return o, (q, k, v, g, beta, states, t)


def _rule_bwd(chunk, interpret, res, do):
    q, k, v, g, beta, states, t = res
    with jax.named_scope("gdn_bwd"):
        # the preparation again, all but T not kept from the forward
        prepared, _ = _prepare_forward(q, k, v, g, beta, chunk, interpret, t)
        grads = _walk_backward(*prepared, states, do, chunk, interpret)
        return _prepare_backward(q, k, v, g, beta, t, grads, chunk,
                                 interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk=64, use_pallas=None):
    """o (B, Hv, S, dv) of the gated delta rule for q, k (B, Hk, S, dk)
    (l2-normalised and scaled by the caller), v (B, Hv, S, dv), g
    (B, Hv, S) the log decays (<= 0, float32) and beta (B, Hv, S); key
    head j serves the ``Hv / Hk`` value heads from ``j * Hv / Hk`` on.
    Differentiable in all five.

    use_pallas: None = the kernels on TPU where ``eligible`` admits the
    blocks, the ``lax.scan`` twin elsewhere; True forces the kernels
    (interpreted off-TPU, for testing); False forces the twin.
    """
    b, hv, s, dv = v.shape
    hk, dk = k.shape[1], k.shape[3]
    if q.shape != k.shape or hv % hk or g.shape != (b, hv, s) \
            or beta.shape != g.shape:
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape}, g "
                         f"{g.shape}, beta {beta.shape}")
    fits = eligible(dk, dv, chunk, v.dtype.itemsize)
    on_tpu = jax.default_backend() == "tpu"
    if use_pallas is None:
        impl = "pallas" if on_tpu and fits else "plain"
    elif use_pallas:
        if not fits:
            raise ValueError(
                f"the gated-delta kernels cannot take heads of ({dk}, {dv}) "
                f"in chunks of {chunk}: head sizes in multiples of "
                f"{_TILE_COLS}, blocks inside VMEM")
        impl = "pallas" if on_tpu else "interpret"
    else:
        impl = "plain"
    _count("gdn_plain" if impl == "plain" else "gdn_pallas")
    # whole steps: padded positions neither write the state (beta 0)
    # nor decay it (g 0)
    pad = (-s) % step_rows(s, chunk)
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, 0), (0, pad))) for a in (g, beta))
    g = g.astype(jnp.float32)
    with jax.named_scope("gdn"):
        if impl == "plain":
            o = _walk_scan(*_prepare(q, k, v, g, beta, chunk), chunk)
        else:
            o = _rule(q, k, v, g, beta, chunk, impl == "interpret")
    return o[:, :, :s]
