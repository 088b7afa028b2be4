"""Plan -> fused-step sharding config, with opt-in ZeRO-1 state sharding.

``fused_shard_cfg`` is the bridge the Gluon trainer crosses: given the
parameter-group names/shapes and optimizer-state signatures, it turns
the scoped :class:`ShardingPlan` into the concrete ``NamedSharding``
trees the fused executable is compiled with (``in_shardings`` /
``out_shardings``) and the trainer places buffers with.

Optimizer-state layout:

- default: a state leaf with the parameter's shape follows the
  parameter's spec (momentum/variance co-located with the weight);
  other leaves (scalars, fp16 base copies of different shape)
  replicate;
- ZeRO-1 (``MXNET_SHARDING_ZERO1=1``): additionally shards every
  param-shaped state leaf's dim 0 over the mesh's FIRST axis — the
  cross-replica weight-update sharding of "Automatic Cross-Replica
  Sharding of Weight Update in Data-Parallel Training". Each device
  then stores 1/N of the optimizer state and computes 1/N of the
  update; GSPMD inserts the all-gather that re-materializes the
  updated weights at the parameters' plan layout. Dims the axis
  doesn't divide fall back to the default layout (counted as
  ``divisibility_fallbacks``).
"""
from __future__ import annotations

from jax.sharding import NamedSharding

from . import _count, zero1_enabled
from .plan import _to_pspec, current_plan

__all__ = ["fused_shard_cfg", "FusedShardCfg"]


class FusedShardCfg:
    """Resolved sharding for one fused-step parameter group."""

    __slots__ = ("mesh", "param_shardings", "state_shardings", "rep",
                 "salt", "zero1")

    def __init__(self, mesh, param_shardings, state_shardings, rep,
                 salt, zero1):
        self.mesh = mesh
        self.param_shardings = param_shardings
        self.state_shardings = state_shardings
        self.rep = rep
        self.salt = salt
        self.zero1 = zero1

    def place_args(self, pvals, gvals, svals, donate_params):
        """Move the step's input buffers to the declared layouts.

        jit with explicit ``in_shardings`` REJECTS a committed arg at a
        different layout (it only auto-reshards uncommitted arrays), so
        the first sharded step — and the first one after a checkpoint
        restore re-binds single-device buffers — must place inputs
        itself. Already-placed buffers pass through by identity, so the
        steady-state cost is one sharding comparison per buffer.

        Buffers the executable DONATES (states always; params under
        ``donate_params``) are additionally laundered through a
        device-side copy: a computation output donates safely
        everywhere, a raw transfer's buffer was seen not to (the
        round-12 corruption bug, ``fused_step.state_adopt``)."""
        import jax
        import jax.numpy as jnp

        def leaf(x, sh, launder):
            if x is None or sh is None:
                return x
            if getattr(x, "sharding", None) == sh:
                return x
            x = jax.device_put(x, sh)
            return jnp.array(x, copy=True) if launder else x

        def tree(x, sh, launder):
            if isinstance(sh, tuple):
                return tuple(tree(a, b, launder) for a, b in zip(x, sh))
            return leaf(x, sh, launder)

        pvals = tuple(leaf(p, sh, donate_params)
                      for p, sh in zip(pvals, self.param_shardings))
        gvals = tuple(leaf(g, sh, False)
                      for g, sh in zip(gvals, self.param_shardings))
        svals = tuple(tree(s, sh, True)
                      for s, sh in zip(svals, self.state_shardings))
        return pvals, gvals, svals


def _zero1_entries(pentries, shape, axis, axis_sizes):
    """Prepend the ZeRO-1 axis to dim 0 of a param-shaped state spec;
    None when the combined extent doesn't divide dim 0."""
    if not shape:
        return None
    entries = list(pentries) + [None] * (len(shape) - len(pentries))
    head_axes = entries[0] or ()
    if axis in head_axes:
        return None  # dim 0 already sharded over this axis by the plan
    # existing extent on dim 0 multiplies in — the combined split must
    # still divide
    extent = axis_sizes[axis]
    for a in head_axes:
        extent *= axis_sizes[a]
    return None if extent <= 0 or shape[0] % extent != 0 else \
        tuple([(axis,) + tuple(head_axes)] + entries[1:])


def _state_shardings(sig, pspec, pshape, mesh, zero1_axis):
    """state_sig tree -> matching tree of NamedSharding/None leaves.
    Returns (tree, used_zero1)."""
    if sig is None:
        return None, False
    is_leaf = (len(sig) == 2 and isinstance(sig[0], tuple)
               and isinstance(sig[1], str))
    if not is_leaf:  # nested tuple of sub-state sigs
        parts = [_state_shardings(s, pspec, pshape, mesh, zero1_axis)
                 for s in sig]
        return tuple(p[0] for p in parts), any(p[1] for p in parts)
    shape, _dtype = sig
    shape = tuple(shape)
    axis_sizes = dict(mesh.shape)
    if shape != tuple(pshape) or not shape or all(d <= 1 for d in shape):
        return NamedSharding(mesh, _to_pspec(())), False
    pentries = [None if e is None else
                (tuple(e) if isinstance(e, (tuple, list)) else (e,))
                for e in tuple(pspec)]
    if zero1_axis is not None:
        z = _zero1_entries(pentries, shape, zero1_axis, axis_sizes)
        if z is not None:
            return NamedSharding(mesh, _to_pspec(z)), True
        _count("divisibility_fallbacks")
    return NamedSharding(mesh, _to_pspec(pentries)), False


def fused_shard_cfg(named_shapes, state_sigs):
    """The :class:`FusedShardCfg` for the scoped plan, or None when no
    plan is active. ``named_shapes``: ordered (name, shape) pairs for
    the group's params; ``state_sigs``: the matching
    ``fused_step.state_sig`` trees."""
    ctx = current_plan()
    if ctx is None:
        return None
    plan, mesh = ctx
    zero1 = zero1_enabled()
    zero1_axis = next(iter(dict(mesh.shape))) if zero1 else None
    pshards, sshards = [], []
    any_zero1 = False
    for (name, shape), sig in zip(named_shapes, state_sigs):
        spec = plan.spec_for(name, shape, mesh)
        pshards.append(NamedSharding(mesh, spec))
        tree, used = _state_shardings(sig, spec, shape, mesh, zero1_axis)
        sshards.append(tree)
        any_zero1 = any_zero1 or used
    rep = NamedSharding(mesh, _to_pspec(()))
    # deliberate legacy site: this salt rides the fused-step cache KEY
    # (FusedShardCfg travels through the trainer into cache_key), not
    # a CompiledArtifact salts=() declaration — the "sharding" provider
    # covers the serving path only
    salt = plan.fingerprint_salt(mesh) + (  # graft-lint: allow(L1001)
        "zero1", zero1)
    _count("fused_sharded_groups")
    if any_zero1:
        _count("zero1_groups")
    return FusedShardCfg(mesh, tuple(pshards), tuple(sshards), rep,
                         salt, any_zero1)
