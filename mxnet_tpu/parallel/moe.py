"""Expert parallelism: Switch-style Mixture-of-Experts FFN over an
'ep' mesh axis.

NEW capability alongside ring/Ulysses sequence parallelism (SURVEY
§5.7): experts are sharded across devices, tokens are top-1 routed with
a static capacity (compiler-friendly shapes — dropped tokens pass
through as zeros, callers add the residual), and TWO lax.all_to_all
collectives move each token to its expert's device and back over ICI
(the Switch/GShard dispatch-combine einsum scheme, arXiv 2101.03961 /
2006.16668, rebuilt on shard_map). The router's load-balancing
auxiliary loss is returned alongside the output.

Composes with data parallelism on a ('dp', 'ep') mesh: the batch shards
over BOTH axes, expert weights shard over 'ep' and replicate over 'dp',
so the all-to-alls ride within each dp row.

Beside it, the drop-free layer today's models train with
(``top_k_router`` + ``expert_ffn``): top-k of all the experts with
renormalised weights, no capacity and no dropped token, gated experts
(``activation``: SiLU, or ReLU for ReGLU), and a layer that is told which experts it holds
(``experts_held=(first, count)``) and computes exactly their part of the
result. The assignments that land on held experts are sorted by expert
into a bounded buffer of rows and go through the grouped matrix product
(``kernels/grouped_matmul.py``), whose tiles stop at the last routed
row: memory follows the expected number of assignments (times
``capacity_factor``), time the actual number, and what overflows the
buffer takes further passes through it, so nothing is ever dropped.
``expert_parallel_ffn`` is the same layer over an 'ep' mesh axis, each
device holding its slice of the experts.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["moe_ffn", "switch_router", "moe_specs", "top_k_router",
           "expert_ffn", "expert_parallel_ffn", "note_expert_rows",
           "note_expert_bias", "note_expert_load", "expert_load",
           "update_expert_bias", "ACTIVATIONS", "SCORES"]


def moe_specs(mesh, axis_name="ep", batch_axes=None):
    """(batch_axes, batch_spec, expert_spec, replicated_spec) for a MoE
    layout on ``mesh`` — the same defaulting moe_ffn applies
    internally. batch_axes rides alongside because PartitionSpec
    indexing collapses a 1-tuple of axes to its bare string."""
    if batch_axes is None:
        batch_axes = tuple(a for a in ("dp", axis_name)
                           if a in mesh.axis_names)
    return tuple(batch_axes), P(batch_axes), P(axis_name), P()


def switch_router(x, gate_w, n_experts, capacity):
    """Top-1 routing with static capacity (runs per device shard).

    Returns (dispatch (T,E,C), combine (T,E,C), aux_loss scalar).
    """
    gates = jax.nn.softmax(x @ gate_w, axis=-1)          # (T, E)
    idx = jnp.argmax(gates, axis=-1)                     # (T,)
    gate = jnp.max(gates, axis=-1)
    onehot = jax.nn.one_hot(idx, n_experts, dtype=x.dtype)
    # Switch aux loss: E * sum_e (token_frac_e * mean_gate_e) — minimized
    # at uniform routing
    aux = (onehot.mean(0) * gates.mean(0)).sum() * n_experts
    # position of each token within its expert's queue; beyond-capacity
    # tokens are dropped (the caller's residual carries them)
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot    # (T, E)
    onehot = onehot * (pos < capacity)
    pos_id = pos.sum(-1).astype(jnp.int32)
    slot = jax.nn.one_hot(pos_id, capacity, dtype=x.dtype)
    dispatch = onehot[:, :, None] * slot[:, None, :]     # (T, E, C)
    combine = dispatch * gate[:, None, None]
    return dispatch, combine, aux


def _moe_local(x, gate_w, w1, b1, w2, b2, axis_name, capacity, act):
    """Runs INSIDE shard_map: x (Tl, D) local tokens; w1 (El, D, H),
    b1 (El, H), w2 (El, H, D), b2 (El, D) local expert shards."""
    p = lax.axis_size(axis_name) if axis_name else 1
    n_local = w1.shape[0]
    n_experts = n_local * p
    d_model = x.shape[-1]
    dispatch, combine, aux = switch_router(x, gate_w, n_experts, capacity)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)   # (E, C, D)
    if p > 1:
        # (E, C, D) -> (p, El, C, D) blocks by owner device, exchange:
        # after all_to_all, block j holds peer j's queue for MY experts
        expert_in = expert_in.reshape(p, n_local, capacity, d_model)
        expert_in = lax.all_to_all(expert_in, axis_name, split_axis=0,
                                   concat_axis=0, tiled=False)
        # (p, El, C, D) -> (El, p*C, D): one fused queue per local expert
        expert_in = jnp.moveaxis(expert_in, 0, 1).reshape(
            n_local, p * capacity, d_model)
    h = act(jnp.einsum("ecd,edh->ech", expert_in, w1) + b1[:, None, :])
    out = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]
    if p > 1:
        # route results back: (El, p*C, D) -> (p, El, C, D) -> exchange
        # -> global (E, C, D) ordered by expert index
        out = jnp.moveaxis(
            out.reshape(n_local, p, capacity, d_model), 1, 0)
        out = lax.all_to_all(out, axis_name, split_axis=0,
                             concat_axis=0, tiled=False)
        out = out.reshape(n_experts, capacity, d_model)
    return jnp.einsum("tec,ecd->td", combine, out), aux


def moe_ffn(x, gate_w, w1, b1, w2, b2, mesh=None, axis_name="ep",
            batch_axes=None, capacity_factor=1.25, act=jax.nn.relu):
    """MoE FFN over a mesh: ``out, aux = moe_ffn(x, ...)``.

    x (B, S, D) with batch sharded over ``batch_axes`` (default:
    ('dp', axis_name) filtered to axes present in the mesh); gate_w
    (D, E) replicated; w1 (E, D, H), b1 (E, H), w2 (E, H, D), b2 (E, D)
    sharded over ``axis_name`` on the expert dim. Tokens per device are
    the flattened (B*S)/shards; capacity = ceil(cf * tokens_local / E).
    """
    from .mesh import current_mesh

    mesh = mesh or current_mesh()
    B, S, D = x.shape
    E = gate_w.shape[-1]
    if mesh is None or axis_name not in mesh.axis_names \
            or mesh.shape[axis_name] == 1:
        # single-shard fallback: same math, no collectives
        cap = max(1, math.ceil(capacity_factor * (B * S) / E))
        out, aux = _moe_local(x.reshape(B * S, D), gate_w, w1, b1, w2,
                              b2, None, cap, act)
        return out.reshape(B, S, D), aux
    batch_axes, bspec, espec, rep = moe_specs(mesh, axis_name,
                                              batch_axes)
    shards = 1
    for a in batch_axes:
        shards *= mesh.shape[a]
    tokens_local = (B * S) // shards
    cap = max(1, math.ceil(capacity_factor * tokens_local / E))

    def local(xl, gw, w1l, b1l, w2l, b2l):
        t = xl.reshape(-1, D)
        out, aux = _moe_local(t, gw, w1l, b1l, w2l, b2l, axis_name,
                              cap, act)
        # mean aux over the mesh so the scalar is replicated
        aux = lax.pmean(aux, axis_name)
        for a in batch_axes:
            if a != axis_name:
                aux = lax.pmean(aux, a)
        return out.reshape(xl.shape), aux

    def place(v, spec):
        # eager callers hand arrays committed to one device; commit them
        # to the mesh layout first (tracers inside jit pass through —
        # GSPMD owns their placement)
        from ..ndarray.ndarray import _is_tracer

        if _is_tracer(v):
            return v
        from jax.sharding import NamedSharding

        return jax.device_put(v, NamedSharding(mesh, spec))

    fn = _shard_map(
        local, mesh=mesh,
        in_specs=(bspec, rep, espec, espec, espec, espec),
        out_specs=(bspec, rep))
    return fn(place(x, bspec), place(gate_w, rep), place(w1, espec),
              place(b1, espec), place(w2, espec), place(b2, espec))


# ---------------------------------------------------------------------------
# drop-free top-k routing over the experts held here

#: the router's score of an expert by the name the layer is given
SCORES = {"softmax": lambda a: jax.nn.softmax(a, axis=-1),
          "sigmoid": jax.nn.sigmoid}


def top_k_router(x, gate_w, k, norm_topk_prob=True, score="softmax",
                 bias=None):
    """Top-k routing over ALL experts: ``(idx (T, k) int32, gates (T, k)
    float32)``. Logits accumulate and the ``score`` (``"softmax"`` over
    the experts, or each expert's ``"sigmoid"``) runs in float32; the k
    experts are chosen by score plus ``bias`` (E,) where one is given (a
    selection bias that enters nothing else: DeepSeek-V3's auxiliary-
    loss-free balancing, arXiv:2412.19437 section 2.1.2); with
    ``norm_topk_prob`` the k chosen scores are divided by their sum."""
    with jax.named_scope("router"):
        logits = jnp.dot(x, gate_w, preferred_element_type=jnp.float32)
        probs = SCORES[score](logits.astype(jnp.float32))
        chooser = probs if bias is None else probs + bias.astype(jnp.float32)
        _, idx = lax.top_k(lax.stop_gradient(chooser), k)
        # the k probabilities picked through a one-hot mask: its
        # derivative is dense too, where top_k's own would scatter
        picked = idx[..., None] == jnp.arange(probs.shape[-1])[None, None]
        gates = jnp.sum(jnp.where(picked, probs[:, None, :], 0.0), axis=-1)
        if norm_topk_prob:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        return idx.astype(jnp.int32), gates


def expert_load(idx, num_experts):
    """The load of each of the ``num_experts`` routed experts from ``idx``
    (T, k): its assignments over the mean, float32 (E,); 1 is an even
    share."""
    count = jnp.sum(idx.reshape(-1)[:, None]
                    == jnp.arange(num_experts, dtype=idx.dtype)[None],
                    axis=0, dtype=jnp.int32)
    return count.astype(jnp.float32) / (idx.size / num_experts)


def update_expert_bias(steps, load):
    """The selection bias after a step, in steps of its rate: ``n_e +
    sign(1 - load_e)`` from ``expert_load``'s shares (DeepSeek-V3's
    ``b_e + rate * sign(mean load - load_e)``, arXiv:2412.19437 section
    2.1.2, with ``b = rate * n``: whole numbers, which float32 holds
    exactly). An expert that got more than its share is chosen less, one
    that got less is chosen more."""
    return steps + jnp.sign(1.0 - load)


def _layout(idx, first, count):
    """Where each (token, choice) slot goes when the slots are sorted by
    held expert (those on experts not held here last): ``order`` (sorted
    position -> slot), ``rank`` (slot -> sorted position), each held
    expert's ``starts`` and ``sizes`` in that order."""
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    onehot = key[:, None] == jnp.arange(count + 1, dtype=key.dtype)[None]
    sizes = jnp.sum(onehot, axis=0, dtype=jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    within = jnp.cumsum(onehot, axis=0, dtype=jnp.int32)
    rank = starts[key] + jnp.take_along_axis(
        within, key[:, None], axis=1)[:, 0] - 1
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    return order, rank, starts[:count], sizes[:count]


def _token_runs(tok, valid, t):
    """The buffer's rows sorted by token, for ``_sum_by_token``: ``by_tok``
    (sorted position -> row), the sorted tokens ``stok`` (rows that are
    padding sort last, as token ``t``), and for every token the sorted
    position of its last row (``last``; ``has`` says it has one)."""
    key = jnp.where(valid, tok, t)
    by_tok = jnp.argsort(key).astype(jnp.int32)
    stok = key[by_tok]
    tokens = jnp.arange(t, dtype=stok.dtype)
    last = jnp.searchsorted(stok, tokens, side="right").astype(jnp.int32) - 1
    has = (last >= 0) & (stok[jnp.maximum(last, 0)] == tokens)
    return by_tok, stok, jnp.maximum(last, 0), has


#: rows of a tile of ``_sum_by_token``: a token's rows (one a choice) must
#: not span three tiles
_RUN_TILE = 256


def _sum_by_token(vals, runs, most):
    """``out[i] = sum of vals[r] over the rows r of token i`` in float32,
    for ``vals`` (cap, D) and ``runs`` from ``_token_runs``. The rows are
    brought into token order; within a tile of ``_RUN_TILE`` rows one
    product with the 0/1 matrix "same token, not later" gives every row
    the sum of its token's rows so far; a token's run (at most ``most``
    rows) may straddle one tile boundary, where the tile before hands
    over its last row's sum; each token reads its run's last row.
    Everything is the buffer's size or the tokens', never tokens x
    choices, and nothing scatters."""
    by_tok, stok, last, has = runs
    cap, d = vals.shape
    if most > _RUN_TILE or cap % _RUN_TILE:
        raise ValueError(f"{most} choices a token, a buffer of {cap} rows")
    tiles = cap // _RUN_TILE
    s = jnp.take(vals, by_tok, axis=0).reshape(tiles, _RUN_TILE, d)
    tk = stok.reshape(tiles, _RUN_TILE)
    i = lax.broadcasted_iota(jnp.int32, (_RUN_TILE, _RUN_TILE), 0)
    j = lax.broadcasted_iota(jnp.int32, (_RUN_TILE, _RUN_TILE), 1)
    so_far = (tk[:, :, None] == tk[:, None, :]) & (j <= i)[None]
    sums = jnp.einsum("gij,gjd->gid", so_far.astype(s.dtype), s,
                      preferred_element_type=jnp.float32)
    # the run that began in the tile before: its sum there, where the
    # token is the same
    before = jnp.pad(sums[:-1, -1], ((1, 0), (0, 0)))
    tok_before = jnp.pad(tk[:-1, -1], (1, 0), constant_values=-1)
    carried = tk == tok_before[:, None]
    sums = sums + jnp.where(carried[:, :, None], before[:, None, :], 0.0)
    out = jnp.take(sums.reshape(cap, d), last, axis=0)
    return jnp.where(has[:, None], out, 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, tok, runs, most):
    """``x[tok]``: the buffer's rows. Its derivative sums each token's
    rows (``_sum_by_token``), where autodiff would scatter."""
    return jnp.take(x, tok, axis=0)


def _dispatch_bwd(most, runs, dxg):
    return _sum_by_token(dxg, runs, most).astype(dxg.dtype), None, None


_dispatch.defvjp(
    lambda x, tok, runs, most: (jnp.take(x, tok, axis=0), runs),
    _dispatch_bwd)


@jax.custom_vjp
def _combine(yo, gates, tok, row_gate, rows, live, runs):
    """``y[t] = sum_j gates[t, j] * yo[rows[t, j]]`` over the slots that
    are live in this pass: each row times its slot's gate (``row_gate``,
    0 on padding) in ``yo``'s type, summed by token in float32."""
    weighted = (row_gate[:, None] * yo.astype(jnp.float32)).astype(yo.dtype)
    return _sum_by_token(weighted, runs, gates.shape[1]).astype(yo.dtype)


def _combine_fwd(yo, gates, tok, row_gate, rows, live, runs):
    return (_combine(yo, gates, tok, row_gate, rows, live, runs),
            (yo, tok, row_gate, rows, live))


def _combine_bwd(res, dy):
    yo, tok, row_gate, rows, live = res
    dy_rows = jnp.take(dy, tok, axis=0).astype(jnp.float32)
    dyo = (row_gate[:, None] * dy_rows).astype(yo.dtype)
    # a slot's gate meets one row: its derivative is that row's
    dgate_row = jnp.sum(dy_rows * yo.astype(jnp.float32), axis=-1)
    dgates = jnp.where(live, jnp.take(dgate_row, rows.reshape(-1))
                       .reshape(rows.shape), 0.0)
    return dyo, dgates, None, None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


#: the gate's activation by the name the layer is given
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _ffn_pass(p, x, gates, w13, w2, order, rank, starts, sizes, cap,
              use_pallas, activation):
    """The part of the result that the held assignments at sorted
    positions [p * cap, (p + 1) * cap) give: gather their tokens' rows,
    the two grouped products around the gate's ``activation``, and each
    token's weighted sum of its rows."""
    from ..kernels.grouped_matmul import grouped_matmul

    t, k = gates.shape
    lo = p * cap
    held = jnp.sum(sizes)
    pos = lo + jnp.arange(cap, dtype=jnp.int32)
    slot = order[jnp.minimum(pos, order.shape[0] - 1)]
    valid = pos < held
    tok = jnp.where(valid, slot // k, 0)
    row_gate = jnp.where(valid, gates.reshape(-1)[slot], 0.0)
    rows = rank.reshape(t, k) - lo
    live = (rank.reshape(t, k) < held) & (rows >= 0) & (rows < cap)
    rows = jnp.clip(rows, 0, cap - 1)
    ends = starts + sizes
    sizes_p = jnp.clip(jnp.minimum(ends, lo + cap) - jnp.maximum(starts, lo),
                       0, cap)
    f = w2.shape[1]
    with jax.named_scope("runs"):
        runs = _token_runs(tok, valid, t)
    with jax.named_scope("dispatch"):
        xg = _dispatch(x, tok, runs, k)
    h = grouped_matmul(xg, w13, sizes_p, use_pallas)
    a = (ACTIVATIONS[activation](h[:, :f].astype(jnp.float32))
         * h[:, f:].astype(jnp.float32)).astype(x.dtype)
    yo = grouped_matmul(a, w2, sizes_p, use_pallas)
    with jax.named_scope("combine"):
        return _combine(yo, gates, tok, row_gate, rows, live, runs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _ffn(x, gates, w13, w2, order, rank, starts, sizes, cap, use_pallas,
         activation):
    return _ffn_fwd(x, gates, w13, w2, order, rank, starts, sizes, cap,
                    use_pallas, activation)[0]


def _passes(sizes, cap):
    return (jnp.sum(sizes) + cap - 1) // cap


def _ffn_fwd(x, gates, w13, w2, order, rank, starts, sizes, cap, use_pallas,
             activation):
    """Pass 0 in the open, its residuals kept; what overflows the buffer
    (none, as a rule) in a loop that keeps nothing."""
    ints = (order, rank, starts, sizes)

    def one(p, x, gates, w13, w2):
        return _ffn_pass(p, x, gates, w13, w2, *ints, cap, use_pallas,
                         activation)

    y, vjp0 = jax.vjp(functools.partial(one, 0), x, gates, w13, w2)
    y = lax.while_loop(
        lambda c: c[0] < _passes(sizes, cap),
        lambda c: (c[0] + 1, c[1] + one(c[0], x, gates, w13, w2)),
        (jnp.int32(1), y))[1]
    return y, (vjp0, x, gates, w13, w2, ints)


def _ffn_bwd(cap, use_pallas, activation, res, dy):
    """Pass 0 from its residuals; the overflow passes recompute."""
    vjp0, x, gates, w13, w2, ints = res

    def more(c):
        p, grads = c
        _, vjp = jax.vjp(lambda *a: _ffn_pass(p, *a, *ints, cap, use_pallas,
                                              activation),
                         x, gates, w13, w2)
        return p + 1, tuple(g + d for g, d in zip(grads, vjp(dy)))

    grads = lax.while_loop(lambda c: c[0] < _passes(ints[3], cap), more,
                           (jnp.int32(1), tuple(vjp0(dy))))[1]
    return (*grads, None, None, None, None)


_ffn.defvjp(_ffn_fwd, _ffn_bwd)


def expert_ffn(x, idx, gates, w13, w2, experts_held=None, num_experts=None,
               capacity_factor=1.5, use_pallas=None, activation="silu"):
    """The held experts' part of a top-k mixture: ``(y (T, D), rows
    (count,) int32)``.

    x (T, D) tokens; idx, gates (T, k) from ``top_k_router`` over all
    ``num_experts``; w13 (count, D, 2F) each held expert's gate and up
    projections side by side, w2 (count, F, D) its down projection;
    ``experts_held=(first, count)`` (``first`` may be traced). Every
    assignment that lands on a held expert is computed, none is dropped:
    ``y[t] = sum over those of gate * (act(x W_gate) * (x W_up)) W_down``,
    ``activation`` naming act: ``"silu"``, or ``"relu"`` (ReGLU). The
    router that gave ``idx`` and ``gates`` may have read another input
    than ``x``.
    The row buffer holds ``capacity_factor`` times the expected number of
    held assignments; more than that take further passes. What moves the
    rows around costs time in proportion to the buffer, a further pass
    that of a whole buffer: at a quarter over the expected rows a layer's
    load passed the buffer in one batch in a hundred of seeded weights
    (PERF.md, PR 30), at half over it did not. ``rows`` counts the
    assignments each held expert got."""
    from ..kernels.grouped_matmul import ROWS

    if activation not in ACTIVATIONS:
        raise ValueError(f"activation {activation!r}: one of "
                         f"{sorted(ACTIVATIONS)}")
    count = w13.shape[0]
    first = 0 if experts_held is None else experts_held[0]
    num_experts = num_experts or count
    t, k = idx.shape
    expect = t * k * count / num_experts
    cap = max(1, math.ceil(capacity_factor * expect / ROWS)) * ROWS
    cap = min(cap, -(-t * k // ROWS) * ROWS)
    with jax.named_scope("layout"):
        order, rank, starts, sizes = _layout(idx, first, count)
    with jax.named_scope("moe"):
        y = _ffn(x, gates.astype(jnp.float32), w13, w2, order, rank, starts,
                 sizes, cap, use_pallas, activation)
    return y, sizes


def expert_parallel_ffn(x, gate_w, w13, w2, k, mesh, axis_name="ep",
                        norm_topk_prob=True, use_pallas=None,
                        activation="silu", router_input=None,
                        score="softmax", bias=None):
    """The same layer with the experts sharded over ``axis_name``: every
    device gathers the axis's tokens, routes them over all the experts
    (from ``router_input``'s rows where given, sharded as ``x``; ``score``
    and ``bias`` as ``top_k_router`` takes them), computes the
    part its own slice of ``w13`` / ``w2`` gives (``experts_held`` from
    its place on the axis) and the parts are summed back to the tokens'
    owners. x (T, D) sharded over the axis on its rows; returns ``(y,
    rows (E,))``, and with ``bias`` also ``load (E,)``, every routed
    expert's share of the gathered tokens' assignments (``expert_load``)."""
    n_experts = gate_w.shape[-1]
    extra = () if bias is None else (bias,)

    def local(xl, rl, gw, w13l, w2l, *bl):
        xa = lax.all_gather(xl, axis_name, axis=0, tiled=True)
        ra = xa if router_input is None else lax.all_gather(
            rl, axis_name, axis=0, tiled=True)
        idx, gates = top_k_router(ra, gw, k, norm_topk_prob, score, *bl)
        held = (lax.axis_index(axis_name) * w13l.shape[0], w13l.shape[0])
        y, rows = expert_ffn(xa, idx, gates, w13l, w2l, held, n_experts,
                             use_pallas=use_pallas, activation=activation)
        out = (lax.psum_scatter(y, axis_name, scatter_dimension=0,
                                tiled=True), rows)
        return out + tuple(expert_load(idx, n_experts) for _ in bl)

    espec = P(axis_name)
    return _shard_map(local, mesh=mesh,
                      in_specs=(espec, espec, P(), espec, espec)
                      + (P(),) * len(extra),
                      out_specs=(espec, espec) + (P(),) * len(extra),
                      check_vma=False)(
        x, x if router_input is None else router_input, gate_w, w13, w2,
        *extra)


# what the compiled step counted, published when its arrays are ready
# (``SPMDTrainer`` polls; nothing waits for the device)
_MOE_COUNTERS = None


def note_expert_rows(rows_by_layer):
    """Publish one step's per-expert row counts (one host array a layer)
    as ``telemetry`` counters: ``moe/steps``, ``moe/assignments_held``
    (summed over layers and steps), ``moe/max_expert_rows`` (the largest
    group of the last step read)."""
    counters = _moe_counters()
    counters.add("steps")
    counters.add("assignments_held",
                 int(sum(float(r.sum()) for r in rows_by_layer)))
    counters.set("max_expert_rows",
                 int(max(float(r.max()) for r in rows_by_layer)))


def _moe_counters():
    global _MOE_COUNTERS
    if _MOE_COUNTERS is None:
        from ..telemetry import metrics

        _MOE_COUNTERS = metrics.counter_family(
            "moe", {"steps": 0, "assignments_held": 0, "max_expert_rows": 0})
    return _MOE_COUNTERS


def note_expert_bias(steps_by_layer):
    """Publish one step's routing bias (one host array a layer, after
    the step's update, in steps of its rate) as the ``telemetry`` counter
    ``moe/bias_steps_max``: the largest |b| / rate of any layer."""
    _moe_counters().set("bias_steps_max", float(max(
        abs(n).max() for n in steps_by_layer)))


def note_expert_load(load_by_layer):
    """Publish one step's load over all the routed experts (one host
    array a layer, ``expert_load``'s shares) as the ``telemetry`` counter
    ``moe/load_max_over_mean``: the largest share of any layer, how far
    the bias has still to even it."""
    _moe_counters().set("load_max_over_mean", float(max(
        a.max() for a in load_by_layer)))
