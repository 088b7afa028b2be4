"""Gluon layers over the mixture-of-experts FFNs of parallel/moe.py: the
expert-parallel Switch layer (top-1, static capacity) and the drop-free
top-k layer that is told which experts it holds. NEW capability vs the
reference zoo — the Gluon face of SURVEY §5.7's scale features,
alongside SyncBatchNorm.
"""
from __future__ import annotations

from ...block import HybridBlock

__all__ = ["SwitchMoE", "TopKMoE"]


class SwitchMoE(HybridBlock):
    """Mixture-of-experts FFN block: top-1 (Switch) routing, experts
    sharded over the mesh's ``axis_name`` axis when a mesh is active
    (``parallel.mesh_scope`` or an explicit ``mesh=``), single-device
    math otherwise.

    forward(x) -> (out, aux_loss): add ``aux_weight * aux_loss`` to the
    training objective for load balancing; out excludes the residual
    (callers add ``x + out`` — dropped-over-capacity tokens then pass
    through untouched).

    Eager calls on a mesh bridge single-device buffers to the mesh and
    back each step (re-tracing the vjp) — fine for interactive use;
    production training should run the layer inside one compiled step
    (SPMDTrainer / jax.jit), where inputs are tracers and the bridge is
    bypassed entirely.
    """

    def __init__(self, num_experts, hidden_size, in_units=0,
                 capacity_factor=1.25, axis_name="ep", mesh=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._E = int(num_experts)
        self._H = int(hidden_size)
        self._cf = float(capacity_factor)
        self._axis = axis_name
        self._mesh = mesh
        D = int(in_units)
        with self.name_scope():
            self.gate_weight = self.params.get(
                "gate_weight", shape=(D, self._E),
                allow_deferred_init=True)
            self.expert_w1 = self.params.get(
                "expert_w1", shape=(self._E, D, self._H),
                allow_deferred_init=True)
            self.expert_b1 = self.params.get(
                "expert_b1", shape=(self._E, self._H), init="zeros")
            self.expert_w2 = self.params.get(
                "expert_w2", shape=(self._E, self._H, D),
                allow_deferred_init=True)
            self.expert_b2 = self.params.get(
                "expert_b2", shape=(self._E, D), init="zeros",
                allow_deferred_init=True)

    def infer_param_shapes(self, x, *args):
        D = x.shape[-1]
        self.gate_weight.shape = (D, self._E)
        self.expert_w1.shape = (self._E, D, self._H)
        self.expert_w2.shape = (self._E, self._H, D)
        self.expert_b2.shape = (self._E, D)

    def hybrid_forward(self, F, x, gate_weight, expert_w1, expert_b1,
                       expert_w2, expert_b2):
        import jax

        from ....ndarray.registry import apply_pure
        from ....parallel.mesh import current_mesh
        from ....parallel.moe import moe_ffn, moe_specs

        mesh = self._mesh or current_mesh()
        axis, cf = self._axis, self._cf
        args = [x, gate_weight, expert_w1, expert_b1, expert_w2,
                expert_b2]
        from ....ndarray.ndarray import _is_tracer

        caller_dev = None
        if mesh is not None and axis in mesh.axis_names \
                and mesh.shape[axis] > 1 \
                and getattr(x, "_data", None) is not None \
                and not _is_tracer(x._data):
            devs = getattr(x._data.sharding, "device_set", None)
            if devs and len(devs) == 1:
                caller_dev = next(iter(devs))

        def pure(xv, gw, w1, b1, w2, b2):
            return moe_ffn(xv, gw, w1, b1, w2, b2, mesh=mesh,
                           axis_name=axis, capacity_factor=cf)

        if caller_dev is None:
            out, aux = apply_pure(pure, args)
            return out, aux
        # eager on a mesh: record the tape node ourselves with placement
        # shims — cotangents arrive committed to the caller's device and
        # must ride the mesh through the vjp; gradients come back to the
        # caller's device for the (single-device) optimizer update
        from ....ndarray import NDArray
        from .... import autograd
        from jax.sharding import NamedSharding

        _axes, bspec, espec, rep = moe_specs(mesh, axis)
        specs = [bspec, rep, espec, espec, espec, espec]
        # mesh-committed COPIES feed the computation; the caller's
        # buffers stay on their device (mutating them would poison
        # downstream eager math with mixed commitments)
        datas = [jax.device_put(a.data, NamedSharding(mesh, s))  # graft-lint: allow(L701)
                 for a, s in zip(args, specs)]
        if not autograd.is_recording():
            out_d, aux_d = pure(*datas)  # no vjp residuals at inference
            return (NDArray(jax.device_put(out_d, caller_dev)),
                    NDArray(jax.device_put(aux_d, caller_dev)))
        (out_d, aux_d), vjp_fn = jax.vjp(pure, *datas)

        def placed_vjp(cots, _vjp=vjp_fn):
            co, ca = cots
            co = jax.device_put(co, NamedSharding(mesh, bspec))  # graft-lint: allow(L701)
            ca = jax.device_put(ca, NamedSharding(mesh, rep))  # graft-lint: allow(L701)
            grads = _vjp((co, ca))
            return [jax.device_put(g, caller_dev) for g in grads]

        out = NDArray(jax.device_put(out_d, caller_dev))
        aux = NDArray(jax.device_put(aux_d, caller_dev))
        autograd._record_op(placed_vjp, list(args), [out, aux])
        return out, aux

    def __repr__(self):
        return (f"SwitchMoE(experts={self._E}, hidden={self._H}, "
                f"axis='{self._axis}')")


class TopKMoE(HybridBlock):
    """Drop-free top-k mixture of gated experts
    (``parallel.moe.top_k_router`` + ``expert_ffn``), the gate's
    ``activation`` ``"silu"`` or ``"relu"`` (ReGLU).

    The router is ``num_experts`` wide and takes the ``top_k`` largest
    probabilities, renormalised with ``norm_topk_prob``. The layer holds
    ``experts_held=(first, count)`` of the experts (all of them by
    default) and computes exactly the part of the result that those
    give, for every assignment that lands on them: what the experts held
    elsewhere would add is not in ``out``. forward(x (B, S, D)) -> out
    (B, S, D) without the residual; forward(x, router_input) routes by
    ``router_input`` (B, S, D) instead of ``x`` (a router placed before
    the attention of its block): the experts and their weights come
    from it, the rows they compute on from ``x``, and the router's
    gradient flows to it. ``expert_rows`` (no gradient) holds
    the rows each held expert got in the last training forward; a
    compiled step carries it back like BatchNorm's running statistics,
    and ``SPMDTrainer`` publishes it as the ``moe/*`` telemetry counters.

    ``shared_expert=width`` adds a dense gated expert of that width that
    every token passes, weighted by its own sigmoid gate (``w_sg``: D x
    1): ``sigmoid(x w_sg) * (act(x W_gate) * (x W_up)) W_down``, outside
    the row buffer and the grouped products, added to the held experts'
    part (a layer that is one share of a group holds it whole).

    ``score`` is the router's: ``"softmax"`` over the experts, or each
    expert's ``"sigmoid"``; the chosen scores are renormalised with
    ``norm_topk_prob``.
    ``expert_bias=rate`` adds DeepSeek-V3's auxiliary-loss-free balancing
    (arXiv:2412.19437 section 2.1.2): a selection bias ``rate *
    expert_bias`` (``expert_bias`` (num_experts,) in whole steps of the
    rate, no gradient) that enters only which experts are chosen, and
    that a training forward moves by one step, ``sign(mean load -
    load_e)``, from the load over all the routed experts, which it keeps
    in ``expert_load`` (each expert's share over the mean, no gradient).
    A compiled step carries both back like ``expert_rows``;
    ``SPMDTrainer`` publishes them as ``moe/bias_steps_max`` and
    ``moe/load_max_over_mean`` and does not let its one-sample
    shape-inference forward move the bias (``Parameter.carried``).

    With ``axis_name`` on the active mesh (``mesh=`` or
    ``parallel.mesh_scope``) and every expert held, the experts are
    sharded over that axis (``parallel.moe.expert_parallel_ffn``).
    """

    def __init__(self, num_experts, hidden_size, top_k, in_units=0,
                 experts_held=None, norm_topk_prob=True, axis_name="ep",
                 mesh=None, activation="silu", shared_expert=None,
                 score="softmax", expert_bias=None, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        from ....parallel.moe import (ACTIVATIONS, SCORES, note_expert_bias,
                                      note_expert_load, note_expert_rows)

        if activation not in ACTIVATIONS:
            raise ValueError(f"activation {activation!r}: one of "
                             f"{sorted(ACTIVATIONS)}")
        if score not in SCORES:
            raise ValueError(f"score {score!r}: one of {sorted(SCORES)}")
        self._act = activation
        self._score = score
        self._rate = None if expert_bias is None else float(expert_bias)

        self._E, self._F, self._k = int(num_experts), int(hidden_size), \
            int(top_k)
        first, count = experts_held or (0, self._E)
        if not 0 <= first <= first + count <= self._E or count < 1:
            raise ValueError(f"experts_held {experts_held} of {self._E}")
        self._held = (int(first), int(count))
        self._norm = bool(norm_topk_prob)
        self._axis, self._mesh = axis_name, mesh
        self._S = int(shared_expert or 0)
        D = int(in_units)
        with self.name_scope():
            self.gate_weight = self.params.get(
                "gate_weight", shape=(D, self._E),
                allow_deferred_init=True)
            self.expert_w13 = self.params.get(
                "expert_w13", shape=(count, D, 2 * self._F),
                allow_deferred_init=True)
            self.expert_w2 = self.params.get(
                "expert_w2", shape=(count, self._F, D),
                allow_deferred_init=True)
            self.expert_rows = self.params.get(
                "expert_rows", grad_req="null", shape=(count,),
                init="zeros", differentiable=False)
            if self._rate is not None:
                self.expert_bias = self.params.get(
                    "expert_bias", grad_req="null", shape=(self._E,),
                    init="zeros", differentiable=False)
                self.expert_load = self.params.get(
                    "expert_load", grad_req="null", shape=(self._E,),
                    init="zeros", differentiable=False)
            if self._S:
                self.shared_gate_weight = self.params.get(
                    "shared_gate_weight", shape=(D, 1),
                    allow_deferred_init=True)
                self.shared_w13 = self.params.get(
                    "shared_w13", shape=(D, 2 * self._S),
                    allow_deferred_init=True)
                self.shared_w2 = self.params.get(
                    "shared_w2", shape=(self._S, D),
                    allow_deferred_init=True)
        self.expert_rows.step_stat = note_expert_rows
        if self._rate is not None:
            self.expert_bias.step_stat = note_expert_bias
            self.expert_bias.carried = True
            self.expert_load.step_stat = note_expert_load

    def infer_param_shapes(self, x, *args):
        D, count = x.shape[-1], self._held[1]
        self.gate_weight.shape = (D, self._E)
        self.expert_w13.shape = (count, D, 2 * self._F)
        self.expert_w2.shape = (count, self._F, D)
        if self._S:
            self.shared_gate_weight.shape = (D, 1)
            self.shared_w13.shape = (D, 2 * self._S)
            self.shared_w2.shape = (self._S, D)

    def hybrid_forward(self, F, x, router_input=None, *, gate_weight,
                       expert_w13, expert_w2, expert_rows,
                       shared_gate_weight=None, shared_w13=None,
                       shared_w2=None, expert_bias=None, expert_load=None):
        from .... import autograd
        from ....ndarray.registry import apply_pure
        from ....parallel import moe
        from ....parallel.mesh import current_mesh

        mesh = self._mesh or current_mesh()
        sharded = mesh is not None and self._axis in mesh.axis_names \
            and mesh.shape[self._axis] > 1 and self._held[1] == self._E
        k, held, n, norm = self._k, self._held, self._E, self._norm
        act, shared = self._act, self._S
        score, rate = self._score, self._rate

        def shared_part(flat, sg, s13, s2):
            """sigmoid(x w_sg) * (act(x W_gate) * (x W_up)) W_down."""
            import jax
            import jax.numpy as jnp

            f32 = jnp.float32
            with jax.named_scope("shared"):
                h = jnp.dot(flat, s13)
                a = (moe.ACTIVATIONS[act](h[:, :shared].astype(f32))
                     * h[:, shared:].astype(f32)).astype(flat.dtype)
                gate = jax.nn.sigmoid(jnp.dot(flat, sg).astype(f32))
                return (gate * jnp.dot(a, s2).astype(f32)).astype(
                    flat.dtype)

        routed = router_input is not None

        def pure(xv, gw, w13, w2, *rest):
            rv, rest = (rest[0], rest[1:]) if routed else (None, rest)
            extra, steps = (rest[:-1], rest[-1]) if rate is not None \
                else (rest, None)
            bias = None if steps is None else rate * steps
            flat = xv.reshape(-1, xv.shape[-1])
            by = flat if rv is None else rv.reshape(flat.shape)
            if sharded:
                y, rows, *load = moe.expert_parallel_ffn(
                    flat, gw, w13, w2, k, mesh, self._axis, norm,
                    activation=act,
                    router_input=None if rv is None else by, score=score,
                    bias=bias)
            else:
                idx, gates = moe.top_k_router(by, gw, k, norm, score, bias)
                y, rows = moe.expert_ffn(flat, idx, gates, w13, w2, held, n,
                                         activation=act)
                load = [] if bias is None else [moe.expert_load(idx, n)]
            if shared:
                y = y + shared_part(flat, *extra)
            out = (y.reshape(xv.shape), rows.astype("float32"))
            if bias is None:
                return out
            return out + (load[0], moe.update_expert_bias(steps, load[0]))

        inputs = [x, gate_weight, expert_w13, expert_w2]
        if routed:
            inputs.append(router_input)
        if shared:
            inputs += [shared_gate_weight, shared_w13, shared_w2]
        if rate is not None:
            inputs.append(expert_bias)
        out, rows, *balance = apply_pure(pure, inputs)
        if autograd.is_training():
            expert_rows._data = rows.data
            if balance:
                expert_load._data = balance[0].data
                expert_bias._data = balance[1].data
        return out

    def __repr__(self):
        return (f"TopKMoE(experts={self._E}, top_k={self._k}, "
                f"held={self._held}, hidden={self._F})")
