"""Gluon Block / HybridBlock / CachedOp.

TPU-native redesign of python/mxnet/gluon/block.py (reference: Block:228
child registry + collect_params:372; HybridBlock:838 deferred symbolic
trace, _build_cache:932 → CachedOp:969, hybridize:1039, export:1077) and
src/imperative/cached_op.{h,cc}.

Design: because every registered op body is traceable JAX, hybridization
does NOT need a separate symbolic language — ``hybridize()`` wraps the
block's imperative ``forward`` into a pure function over (param values,
PRNG key, inputs) and compiles it with ``jax.jit``. Parameter mutation
during forward (BatchNorm running stats) is detected at trace time and
returned as extra outputs, then written back — giving MXNet's stateful
semantics on a functional runtime. Under ``autograd.record`` the CachedOp
contributes ONE tape node whose vjp is the XLA-compiled transpose, exactly
like the reference records one node for the whole cached graph
(cached_op.cc Forward with recording).
"""
from __future__ import annotations

import contextlib
import re
import threading
from collections import OrderedDict

import jax

from ..base import MXNetError
from .. import ndarray as nd
from ..ndarray import NDArray
from .. import autograd
from .. import random as mxrandom
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock", "CachedOp"]


class _BlockScope(threading.local):
    def __init__(self):
        self.current = None
        self.counters = {}


_SCOPE = _BlockScope()


def _gen_prefix(hint):
    if _SCOPE.current is None:
        counters = _SCOPE.counters
        base = ""
    else:
        counters = _SCOPE.current._counters
        base = _SCOPE.current.prefix
    idx = counters.get(hint, 0)
    counters[hint] = idx + 1
    return f"{base}{hint}{idx}_"


class _NameScope:
    def __init__(self, block):
        self._block = block
        self._old = None

    def __enter__(self):
        self._old = _SCOPE.current
        _SCOPE.current = self._block
        return self

    def __exit__(self, *exc):
        _SCOPE.current = self._old


class HookHandle:
    """Detachable registration (reference: gluon/utils.py HookHandle)."""

    def __init__(self, hooks, hook):
        self._hooks = hooks
        self._hook = hook

    def detach(self):
        try:
            self._hooks.remove(self._hook)
        except ValueError:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.detach()


class Block:
    """Base building block (reference: gluon/block.py:228)."""

    def __init__(self, prefix=None, params=None):
        hint = type(self).__name__.lower()
        self._prefix = prefix if prefix is not None else _gen_prefix(hint)
        # Parameter NAMES may live under a different prefix than the
        # block (reference _BlockScope.create): with shared `params`,
        # this block's params are created under the SHARED dict's prefix
        # so lookups hit the shared entries; children of a sharing
        # parent inherit the parent's param-prefix remapping + _shared.
        parent = _SCOPE.current
        if params is not None:
            self._params = ParameterDict(params.prefix, shared=params)
        elif parent is not None and \
                parent.params.prefix != parent.prefix and \
                self._prefix.startswith(parent.prefix):
            local = self._prefix[len(parent.prefix):]
            self._params = ParameterDict(parent.params.prefix + local,
                                         shared=parent.params._shared)
        elif parent is not None and parent.params._shared is not None \
                and self._prefix.startswith(parent.prefix):
            self._params = ParameterDict(self._prefix,
                                         shared=parent.params._shared)
        else:
            self._params = ParameterDict(self._prefix)
        self._children = OrderedDict()
        self._reg_params = {}
        self._counters = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(f"  ({key}): {block!r}"
                           for key, block in self._children.items())
        return s.format(name=type(self).__name__, modstr=modstr)

    #: the name the first parent registered this block under: the scope
    #: its forward runs in (``jax.named_scope``), so that a compiled
    #: program's op names read ``.../blocks/2/attn/...``; None for a block
    #: no parent registered
    _scope_name = None

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = getattr(self, "_children", None)
            if existing is not None:
                self._children[name] = value
                value._adopt(name)
        elif isinstance(value, Parameter):
            if hasattr(self, "_reg_params"):
                self._reg_params[name] = value
        super().__setattr__(name, value)

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._prefix[:-1] if self._prefix.endswith("_") else self._prefix

    def name_scope(self):
        """Reference: gluon/block.py name_scope."""
        return _NameScope(self)

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None):
        """Reference: gluon/block.py:372 collect_params with regex select."""
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def register_child(self, block, name=None):
        name = name or str(len(self._children))
        self._children[name] = block
        block._adopt(name)

    def _adopt(self, name):
        if self._scope_name is None:
            self._scope_name = name

    def _scope(self):
        """The forward's scope: metadata at trace time, nothing in a
        compiled step (docs/TELEMETRY.md, "Scopes")."""
        if self._scope_name is None:
            return contextlib.nullcontext()
        return jax.named_scope(self._scope_name)

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return HookHandle(self._forward_hooks, hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return HookHandle(self._forward_pre_hooks, hook)

    def register_op_hook(self, callback, monitor_all=False):
        """Tap every descendant block's outputs during forward
        (reference: block.py register_op_hook over CachedOp monitor
        callbacks). ``callback(name, array)``; with ``monitor_all``
        inputs are reported too. While any hook is attached, hybridized
        execution runs eagerly (the reference's monitor-mode slowdown)
        so taps fire with concrete arrays on EVERY call — on the whole
        subtree, including independently hybridized descendants. Returns
        a handle whose ``detach()`` removes this hook; the tap layer per
        block is shared, so handles detach safely in any order."""
        # a unique token per registration keys this hook's per-block
        # labels: a second hook registered deeper in the tree gets its
        # OWN prefix-relative labels, not the first registration's
        entry = (object(), callback, bool(monitor_all))
        touched = []

        def install(blk, prefix):
            for cname, child in blk._children.items():
                name = getattr(child, "name", None) or cname
                install(child, prefix + name + ".")
            label = prefix.rstrip(".") or (getattr(blk, "name", "") or
                                           type(blk).__name__)
            labels = getattr(blk, "_op_hook_labels", None)
            if labels is None:
                labels = blk._op_hook_labels = {}
            labels[entry[0]] = label
            cbs = getattr(blk, "_op_hook_cbs", None)
            if cbs is None:
                cbs = blk._op_hook_cbs = []
                orig = blk.forward

                def tap(*args, _orig=orig, _blk=blk, **kw):
                    from ..ndarray.ndarray import _is_tracer

                    def concrete(v):
                        # a hook registered BELOW a hybridized ancestor
                        # meets tracers during that ancestor's cache
                        # trace — skip those calls (register on the
                        # outermost block for every-call taps) rather
                        # than crash value-reading callbacks
                        return hasattr(v, "data") and not _is_tracer(
                            v.data)

                    # snapshot both together: detach() during the
                    # forward (capture-once callbacks) pops the label
                    hooks = list(_blk._op_hook_cbs)
                    lbls = dict(_blk._op_hook_labels)
                    for tok, cb, mon_all in hooks:
                        if mon_all:
                            for i, a in enumerate(args):
                                if concrete(a):
                                    cb(f"{lbls[tok]}_data{i}", a)
                    out = _orig(*args, **kw)
                    outs = out if isinstance(out, (list, tuple)) \
                        else [out]
                    for tok, cb, _mon_all in hooks:
                        for i, o in enumerate(outs):
                            if concrete(o):
                                suffix = "_output" if len(outs) == 1 \
                                    else f"_output{i}"
                                cb(f"{lbls[tok]}{suffix}", o)
                    return out

                blk._op_hook_fwd = (tap, orig)
                blk.forward = tap
            cbs.append(entry)
            # eager-path flag on EVERY block so nested hybridized
            # children also bypass their caches while tapped
            blk._op_hooks_active = getattr(blk, "_op_hooks_active",
                                           0) + 1
            touched.append(blk)

        install(self, "")

        class _OpHookHandle:
            def detach(self_inner):
                for blk in touched:
                    getattr(blk, "_op_hook_labels", {}).pop(entry[0], None)
                    cbs = getattr(blk, "_op_hook_cbs", None)
                    if cbs is not None and entry in cbs:
                        cbs.remove(entry)
                        blk._op_hooks_active = max(
                            0, getattr(blk, "_op_hooks_active", 1) - 1)
                        if not cbs:
                            tap, orig = blk._op_hook_fwd
                            if blk.forward is tap:
                                blk.forward = orig
                            del blk._op_hook_fwd
                            blk._op_hook_cbs = None
                touched.clear()

        return _OpHookHandle()

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        from .. import initializer

        self.collect_params().initialize(init or initializer.Uniform(), ctx,
                                         verbose, force_reinit)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def _collect_params_with_prefix(self, prefix=""):
        """Structure-based parameter names ("0.weight", "body.1.bias") so
        checkpoints are independent of name-counter state
        (reference: gluon/block.py _collect_params_with_prefix)."""
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename, deduplicate=False):
        """Reference: gluon/block.py:416."""
        params = self._collect_params_with_prefix()
        arg_dict = {key: val.data() for key, val in params.items()
                    if val._ndarray is not None}
        nd.save(filename, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        """Reference: gluon/block.py:472. Accepts both structure-based
        files (save_parameters) and arg:/aux:-prefixed export/Module
        checkpoints, matching the latter by full parameter name as the
        reference does."""
        self._load_loaded_parameters(nd.load(filename), filename,
                                     allow_missing, ignore_extra)

    def _load_loaded_parameters(self, loaded, filename,
                                allow_missing=False, ignore_extra=False):
        """Apply an already-deserialized ``nd.load`` dict (callers that
        inspected the file — SymbolBlock.imports — pass it through so
        big param files parse and device-upload once, not twice)."""
        if loaded and all(k.startswith(("arg:", "aux:")) for k in loaded):
            loaded = {k.split(":", 1)[1]: v for k, v in loaded.items()}
            params = dict(self.collect_params().items())
        else:
            params = self._collect_params_with_prefix()
            if loaded and not any(k in params for k in loaded):
                # reference-era zoo checkpoints use full parameter names
                # ("resnetv10_conv0_weight"), not structure paths
                by_name = dict(self.collect_params().items())
                if any(k in by_name for k in loaded):
                    params = by_name
        if not allow_missing:
            for name in params.keys():
                if name not in loaded:
                    raise IOError(f"Parameter '{name}' is missing in file "
                                  f"'{filename}'")
        for name in loaded:
            if name not in params:
                if not ignore_extra:
                    raise IOError(f"Parameter '{name}' loaded from file "
                                  f"'{filename}' is not present in Block")
                continue
            params[name]._load_init_from(loaded[name])

    save_params = save_parameters
    load_params = load_parameters

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def summary(self, *inputs):
        """Print a parameter/shape summary (reference: gluon/block.py
        summary)."""
        rows = []

        def walk(block, indent=0):
            n_params = sum(p.data().size for p in block._reg_params.values()
                           if p._ndarray is not None)
            rows.append("  " * indent + f"{type(block).__name__}"
                        f" ({block.name}): {n_params} params")
            for c in block._children.values():
                walk(c, indent + 1)

        walk(self)
        print("\n".join(rows))

    def __call__(self, *args, **kwargs):
        for hook in list(self._forward_pre_hooks):
            hook(self, args)
        with self._scope():
            out = self.forward(*args, **kwargs)
        for hook in list(self._forward_hooks):
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError


class CachedOp:
    """jit-compiled replay of a block's forward
    (reference: src/imperative/cached_op.{h,cc}; flags static_alloc etc. map
    to XLA donation/caching which jit already provides)."""

    def __init__(self, block, static_alloc=False, static_shape=False,
                 inline_limit=2, forward_bulk_size=None,
                 backward_bulk_size=None):
        from .. import env

        self._block = block
        self._param_list = None  # list[Parameter], fixed order
        self._out_treedefs = {}
        fn = self._pure
        # MXNET_BACKWARD_DO_MIRROR=1 (reference: src/nnvm/gradient.cc:275
        # mirror pass) — on TPU the memory-vs-compute lever is remat:
        # jax.checkpoint drops this op's forward activations and
        # recomputes them during backward
        if env.get_bool("MXNET_BACKWARD_DO_MIRROR"):
            fn = jax.checkpoint(fn, static_argnums=(0, 1))
        from ..utils import compile_cache as _cc

        self._jitted = _cc.counting_jit(fn, label="cached_op",
                                        static_argnums=(0, 1))

    def _ensure_params(self):
        if self._param_list is None:
            self._param_list = [p for _, p in
                                sorted(self._block.collect_params().items())]
        return self._param_list

    def _pure(self, amp_ver, train, param_vals, key, input_datas):
        # amp_ver is a static cache key only: a set_amp() bump forces a
        # retrace so the current AMP policy is baked into the new trace
        del amp_ver
        params = self._ensure_params()
        pnds = [p._ndarray for p in params]
        saved = [p._data for p in pnds]
        try:
            for p, v in zip(pnds, param_vals):
                p._data = v
            with autograd.pause(train_mode=train), mxrandom.key_provider(key):
                args = [NDArray(d) for d in input_datas]
                outs = self._block.forward(*args)
            flat, treedef = _flatten_outputs(outs)
            self._out_treedefs[bool(train)] = treedef
            mutated = {str(i): p._data for i, (p, v) in
                       enumerate(zip(pnds, param_vals)) if p._data is not v}
            return tuple(o.data for o in flat), mutated
        finally:
            for p, v in zip(pnds, saved):
                p._data = v

    def __call__(self, *args):
        params = self._ensure_params()
        # finish any deferred init with one throwaway eager pass
        if any(p._ndarray is None for p in params):
            with autograd.pause(train_mode=autograd.is_training()):
                self._block.forward(*args)
            self._param_list = None
            params = self._ensure_params()
        pnds = [p._ndarray for p in params]
        param_vals = [p._data for p in pnds]
        input_datas = [a.data for a in args]
        key = mxrandom.next_key()
        train = autograd.is_training()
        from ..ndarray import registry as _op_registry
        _amp_ver = _op_registry.amp_version()

        if autograd.is_recording():
            (out_datas, mutated), vjp_fn, = _vjp2(
                lambda pv, iv: self._jitted(_amp_ver, train, pv, key, iv),
                param_vals, input_datas)
            outs = [NDArray(d) for d in out_datas]

            def tape_vjp(cotangents, _vjp=vjp_fn, _n=len(out_datas)):
                cots = (cotangents,) if _n == 1 else tuple(cotangents)
                pv_grads, iv_grads = _vjp(cots)
                return list(pv_grads) + list(iv_grads)

            def tape_fun(*xs, _npv=len(pnds), _ver=_amp_ver,
                         _train=train, _key=key, _self=self):
                # primal for higher-order grads: replay the cached jit
                # (same RNG key -> same dropout mask as the recording)
                pv, iv = list(xs[:_npv]), list(xs[_npv:])
                out_d, _mut = _self._jitted(_ver, _train, pv, _key, iv)
                return tuple(out_d) if len(out_d) > 1 else out_d[0]

            autograd._record_op(tape_vjp, pnds + list(args), outs,
                                fun=tape_fun)
        else:
            out_datas, mutated = self._jitted(_amp_ver, train, param_vals,
                                              key, input_datas)
            outs = [NDArray(d) for d in out_datas]
        for i_str, val in mutated.items():
            pnds[int(i_str)]._data = val
        treedef = self._out_treedefs.get(bool(train))
        return _unflatten_outputs(outs, treedef)


def _vjp2(fn, pv, iv):
    out, vjp_fn, aux = jax.vjp(fn, pv, iv, has_aux=True)
    return (out, aux), vjp_fn


def _flatten_outputs(outs):
    if isinstance(outs, NDArray):
        return [outs], "single"
    if isinstance(outs, (list, tuple)):
        flat = []
        spec = []
        for o in outs:
            if isinstance(o, NDArray):
                flat.append(o)
                spec.append(1)
            else:
                sub = list(o)
                flat.extend(sub)
                spec.append(len(sub))
        return flat, ("seq", type(outs).__name__, spec)
    raise MXNetError(f"unsupported forward output type {type(outs)}")


def _unflatten_outputs(flat, treedef):
    if treedef == "single" or treedef is None:
        return flat[0] if len(flat) == 1 else tuple(flat)
    _, typ, spec = treedef
    out = []
    i = 0
    for n in spec:
        if n == 1:
            out.append(flat[i])
        else:
            out.append(tuple(flat[i:i + n]))
        i += n
    return tuple(out) if typ == "tuple" else out


class HybridBlock(Block):
    """Block that can be compiled (reference: gluon/block.py:838)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op = None
        self._cached_op_args = {}

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        """Reference: gluon/block.py:1039. Compilation == jax.jit."""
        self._active = active
        self._cached_op = None
        self._cached_op_args = dict(static_alloc=static_alloc,
                                    static_shape=static_shape, **kwargs)
        super().hybridize(active=False)  # only the outermost block compiles

    def _build_cache(self):
        self._cached_op = CachedOp(self, **self._cached_op_args)

    def _verify_on_hybridize(self, args):
        """MXNET_GRAPH_VERIFY-gated trace verification before the first
        CachedOp build: one paused eager forward is recorded
        (analysis.record_trace) and the dataflow passes — PRNG key
        reuse, use-after-donate, dead values — disposition per the mode.
        Runs once per cache build, never on the hot path."""
        from .. import analysis

        if analysis.verify_mode() == "off":
            return
        try:
            report = analysis.verify_block_call(
                self, args, subject=f"hybridize:{self.name}")
        except DeferredInitializationError:
            return  # params not yet shaped; CachedOp's own pass inits
        report.disposition()

    def infer_shape(self, *args):
        """Finish deferred param init from example inputs."""
        with autograd.pause():
            self.forward(*args)

    def cast(self, dtype):
        super().cast(dtype)
        self._cached_op = None

    def __call__(self, *args, **kwargs):
        # op hooks force the eager path so taps fire on EVERY call, not
        # just the trace (the reference's monitor-mode slowdown)
        if self._active and not kwargs \
                and not getattr(self, "_op_hooks_active", 0):
            if all(isinstance(a, NDArray) for a in args):
                if self._cached_op is None:
                    self._verify_on_hybridize(args)
                    self._build_cache()
                for hook in list(self._forward_pre_hooks):
                    hook(self, args)
                with self._scope():
                    out = self._cached_op(*args)
                for hook in list(self._forward_hooks):
                    hook(self, args, out)
                return out
        return super().__call__(*args, **kwargs)

    def forward(self, x, *args):
        """Dispatch to hybrid_forward with params as kwargs
        (reference: gluon/block.py:1127). Symbol inputs trace the block
        through the sym namespace instead — the reference's F-dispatch
        (gluon/block.py:1146 _call_cached_op symbol branch) that powers
        ``export`` and ONNX."""
        from .. import symbol as _sym

        if isinstance(x, _sym.Symbol):
            params = {name: _sym.var(param.name)
                      for name, param in self._reg_params.items()}
            return self.hybrid_forward(_sym, x, *args, **params)
        params = {}
        for name, param in self._reg_params.items():
            try:
                params[name] = param.data()
            except DeferredInitializationError:
                self._infer_param_shapes(x, *args)
                params[name] = param.data()
        return self.hybrid_forward(nd, x, *args, **params)

    def _infer_param_shapes(self, x, *args):
        """Layers override `infer_param_shapes(x)`; generic fallback errors."""
        infer = getattr(self, "infer_param_shapes", None)
        if infer is None:
            raise DeferredInitializationError(
                f"{type(self).__name__} has deferred parameters and no "
                "shape-inference hook; call initialize() with known shapes")
        infer(x, *args)
        for p in self._reg_params.values():
            if p._ndarray is None and p._deferred_init is not None:
                p._finish_deferred_init()

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def export(self, path, epoch=0, input_names=("data",)):
        """Write ``path-symbol.json`` (reference-format nnvm JSON, via the
        F=sym trace) + ``path-{epoch:04d}.params`` (reference binary with
        arg:/aux: prefixes) — full parity with reference
        gluon/block.py:1077 export, loadable by SymbolBlock.imports, the
        Module API, and reference-era tooling. ``input_names`` sets the
        traced data-input variable names for multi-input blocks."""
        from .. import symbol as _sym
        from .. import ndarray as _nd

        out = self(*[_sym.var(n) for n in input_names])
        out.save(f"{path}-symbol.json")
        # aux states are what the graph says they are — the stat inputs
        # of batch_norm nodes — not "anything frozen": a weight with
        # grad_req='null' is still a graph argument
        aux_names = set()
        for s in out._walk():
            if s._op == "batch_norm" and len(s._inputs) >= 5:
                aux_names.update(i._name for i in s._inputs[3:5]
                                 if i._op is None)
        payload = {}
        for name, p in self.collect_params().items():
            tag = "aux" if name in aux_names else "arg"
            payload[f"{tag}:{name}"] = p.data()
        fname = f"{path}-{epoch:04d}.params"
        _nd.save(fname, payload)
        return fname

    def optimize_for(self, x, *args, backend=None, **kwargs):
        self.hybridize()
        return self(x, *args)


class SymbolBlock(HybridBlock):
    """Construct a block from a symbol graph (reference: gluon/block.py:1190).
    Implemented with the symbolic layer (mxnet_tpu.symbol)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        self._outputs = outputs
        self._inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        # every free variable of the graph that is not a declared input
        # becomes a Parameter (reference: gluon/block.py:1246 — arg/aux
        # inputs of the imported symbol turn into block params)
        input_names = {i.name for i in self._inputs}
        for s in outputs._walk():
            if s._op is None and not s._group \
                    and s._name not in input_names \
                    and s._name not in self._reg_params:
                self._reg_params[s._name] = self.params.get(
                    s._name, allow_deferred_init=True)

    @staticmethod
    def imports(symbol_file, input_names=None, param_file=None, ctx=None):
        """Reference: gluon/block.py SymbolBlock.imports. Serving loader
        glue: ``input_names=None`` infers the data inputs as the graph's
        free variables NOT present in ``param_file`` — the exported
        (symbol, params) pair fully determines which variables are fed
        per request, so a model server can load any export without
        out-of-band input metadata."""
        from .. import symbol as sym
        from .. import ndarray as _nd

        outputs = sym.load(symbol_file)
        loaded = _nd.load(param_file) if param_file is not None else None
        if input_names is None:
            if loaded is None:
                raise MXNetError(
                    "SymbolBlock.imports(input_names=None) needs "
                    "param_file to tell data inputs from parameters")
            saved = {k.split(":", 1)[1] if k.startswith(("arg:", "aux:"))
                     else k for k in loaded}
            input_names = [n for n in outputs.list_arguments()
                           if n not in saved]
            if not input_names:
                raise MXNetError(
                    f"no free variables of {symbol_file!r} remain after "
                    f"binding {param_file!r}; pass input_names "
                    "explicitly")
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym.var(n) for n in input_names]
        ret = SymbolBlock(outputs, inputs)
        if loaded is not None:
            ret._load_loaded_parameters(loaded, param_file)
        return ret

    def _optimized_outputs(self):
        """MXNET_GRAPH_OPT-gated rewrite of the output graph, cached per
        (level, pipeline version, fusion salt, autotune salt) so
        toggling the fusion knobs — or a tuning record/trial landing —
        re-optimizes. Every forward — eager, under the hybridized
        CachedOp trace, and the serving session's ``_pure`` — evaluates
        this graph, so one rewrite covers all three."""
        from ..analysis import graph_opt

        level = graph_opt.opt_level()
        if level <= 0:
            return self._outputs
        from .. import autotune as _autotune
        from .. import kernels

        tag = (level, graph_opt.PIPELINE_VERSION,
               kernels.fusion_salt(),
               _autotune.autotune_salt())
        cached = getattr(self, "_graph_opt_cache", None)
        if cached is None or cached[0] != tag:
            opt, _ = graph_opt.optimize_symbol(
                self._outputs, level=level,
                subject=f"hybridize:{self.name or 'symbol_block'}")
            self._graph_opt_cache = (tag, opt)
            cached = self._graph_opt_cache
        return cached[1]

    def forward(self, *args):
        from .. import symbol as sym

        feed = {i.name: a for i, a in zip(self._inputs, args)}
        for name, p in self.collect_params().items():
            feed[name] = p.data()
        return self._optimized_outputs().eval_with(feed)
