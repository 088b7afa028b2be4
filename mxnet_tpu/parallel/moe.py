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
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["moe_ffn", "switch_router", "moe_specs"]


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
