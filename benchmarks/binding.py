"""Ties a gluon net's parameters to the reference's leaves and gives it
the benchmark's weights: made on the device in one jitted call from the
seed by the reference's ``init``, in the program's layout."""
from __future__ import annotations

import numpy as onp

import refcommon


def bind_leaves(net, leaves, to_program):
    """{gluon name: (Parameter, reference leaf)}: both sides list their
    parameters in the order the network is built."""
    params = list(net.collect_params().items())
    if len(params) != len(leaves):
        raise RuntimeError(f"{len(params)} parameters, {len(leaves)} leaves")
    out = {}
    for (name, p), (leaf, (shape, _)) in zip(params, leaves.items()):
        want = to_program(leaf, onp.empty(shape, "bool")).shape
        # a dimension the net has not inferred yet reads 0
        if len(p.shape) != len(want) or any(
                a and a != b for a, b in zip(p.shape, want)):
            raise RuntimeError(f"{name} {p.shape} is not {leaf} {want}")
        out[name] = (p, leaf)
    return out


def seed_weights(ctx, net):
    """Set the seed's weights into ``net``; returns (leaf_of,
    program_weights) where ``program_weights(key)`` is the traceable
    function that made them ({leaf: array in the program's layout})."""
    import jax

    from mxnet_tpu.ndarray import NDArray

    cfg, model, ref = ctx.cfg, ctx.model, ctx.ref
    leaf_of = bind_leaves(net, ref.leaf_shapes(cfg), model.to_program)

    def program_weights(key):
        params, aux = ref.init(cfg, key)
        return {k: model.to_program(k, v)
                for k, v in dict(params, **aux).items()}

    w0 = jax.jit(program_weights)(refcommon.key_from_seed(ctx.seed))
    for p, leaf in leaf_of.values():
        p.set_data(NDArray(w0[leaf]))
    return leaf_of, program_weights
