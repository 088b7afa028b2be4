"""Gluon Parameter / ParameterDict.

TPU-native equivalent of python/mxnet/gluon/parameter.py (reference:
Parameter:48 with deferred init, grad_req, lr_mult/wd_mult, per-ctx
replicas; ParameterDict; Constant). On TPU there is one logical copy of
each parameter — replication/sharding across chips is a jax.sharding
decision made by the parallel layer, not N explicit NDArray replicas as in
the reference's per-GPU `_ctx_list` model.
"""
from __future__ import annotations

import re
import warnings
from collections import OrderedDict

import numpy as onp

from ..base import MXNetError
from .. import ndarray as nd
from ..ndarray import NDArray
from .. import initializer
from ..context import current_context
from ..telemetry import tracer as _telem

__all__ = ["DeferredInitializationError", "Parameter", "Constant",
           "ParameterDict", "tensor_types"]

tensor_types = (NDArray,)


class DeferredInitializationError(MXNetError):
    """Error for unfinished deferred initialization (reference:
    gluon/parameter.py:40)."""


class Parameter:
    """A Block parameter (reference: gluon/parameter.py:48)."""

    def __init__(self, name, grad_req="write", shape=None, dtype=onp.float32,
                 lr_mult=1.0, wd_mult=1.0, init=None,
                 allow_deferred_init=False, differentiable=True,
                 stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        if isinstance(shape, int):
            shape = (shape,)
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        self._ndarray = None
        self._deferred_init = None  # (init, ctx, default_init)

    def __repr__(self):
        return f"Parameter {self.name} (shape={self.shape}, dtype={self.dtype})"

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        assert req in ("write", "add", "null")
        self._grad_req = req
        if self._ndarray is not None:
            if req == "null":
                self._ndarray._ag_marked = False
                self._ndarray._grad = None
            else:
                self._attach_grad()

    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        # per-dim MERGE, 0 = unknown on EITHER side (reference
        # parameter.py get() inferred_shape): a sharing block created
        # with in_units=0 must not clobber the shared param's known dims
        assert len(self._shape) == len(new_shape) and all(
            i == 0 or j == 0 or i == j
            for i, j in zip(new_shape, self._shape)), \
            f"Expected shape {self._shape} is incompatible with given shape " \
            f"{new_shape} for Parameter {self.name}"
        self._shape = tuple(j if i == 0 else i
                            for i, j in zip(new_shape, self._shape))

    def _shape_complete(self):
        return self._shape is not None and all(s > 0 for s in self._shape)

    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        """Reference: gluon/parameter.py initialize (deferred when shape
        unknown)."""
        if default_init is None:
            default_init = initializer.Uniform()
        if self._ndarray is not None and not force_reinit:
            return
        if not self._shape_complete():
            if not self.allow_deferred_init:
                raise ValueError(
                    f"Cannot initialize Parameter {self.name} because it has "
                    f"invalid shape {self._shape}")
            self._deferred_init = (init, ctx, default_init)
            return
        self._finish_init(init, ctx, default_init)

    def _finish_init(self, init, ctx, default_init, discarded=False):
        """Allocate and run the initializer. ``discarded`` marks the
        span of a run whose result ``set_data`` overwrites at once."""
        with _telem.span("gluon.param_init", cat="setup", param=self.name,
                         discarded=discarded) as sp:
            arr = nd.zeros(self._shape,
                           ctx=ctx if not isinstance(ctx, list) else ctx[0],
                           dtype=self.dtype)
            actual = init if init is not None else (
                self.init if self.init is not None else default_init)
            if isinstance(actual, str):
                actual = initializer.create(actual)
            actual(initializer.InitDesc(self.name), arr)
            self._ndarray = arr
            self._deferred_init = None
            if self._grad_req != "null":
                self._attach_grad()
            sp.set(bytes=int(arr.data.nbytes))

    def _finish_deferred_init(self, inferred_shape=None, discarded=False):
        if inferred_shape is not None:
            self.shape = inferred_shape
        if self._deferred_init is None:
            raise DeferredInitializationError(
                f"Parameter {self.name} has not been initialized")
        init, ctx, default_init = self._deferred_init
        self._finish_init(init, ctx, default_init, discarded=discarded)

    def _attach_grad(self):
        from .. import autograd

        g = nd.zeros(self._ndarray.shape, dtype=self._ndarray.data.dtype)
        autograd.mark_variables([self._ndarray], [g], self._grad_req)

    def _check_initialized(self):
        if self._ndarray is None:
            if self._deferred_init is not None:
                raise DeferredInitializationError(
                    f"Parameter {self.name} has not been initialized yet "
                    "because initialization was deferred. Actual "
                    "initialization happens during the first forward pass.")
            raise RuntimeError(
                f"Parameter {self.name} has not been initialized. You should "
                "initialize parameters with Block.initialize() first")

    def data(self, ctx=None):
        self._check_initialized()
        return self._ndarray

    def list_data(self):
        return [self.data()]

    def grad(self, ctx=None):
        self._check_initialized()
        if self._grad_req == "null" or self._ndarray._grad is None:
            raise RuntimeError(
                f"Cannot get gradient array for Parameter {self.name} "
                "because grad_req='null'")
        return self._ndarray._grad

    def list_grad(self):
        return [self.grad()]

    def list_ctx(self):
        self._check_initialized()
        return [self._ndarray.context]

    def zero_grad(self):
        if self._ndarray is not None and self._ndarray._grad is not None:
            g = self._ndarray._grad
            g._data = nd.zeros(g.shape, dtype=g.data.dtype).data

    def reset_ctx(self, ctx):
        """Move the parameter's buffer (and grad) to another context
        (reference: parameter.py reset_ctx — raises for uninitialized
        parameters rather than silently placing them elsewhere later)."""
        import jax

        dev = getattr(ctx, "jax_device", ctx)
        if self._ndarray is None:
            raise ValueError(
                f"Cannot reset context for Parameter '{self.name}' "
                f"because it has not been initialized (deferred init "
                f"finishes on the first forward)")
        self._ndarray._data = jax.device_put(self._ndarray._data, dev)
        if self._ndarray._grad is not None:
            g = self._ndarray._grad
            g._data = jax.device_put(g._data, dev)

    def set_data(self, data):
        self.shape = data.shape
        if self._ndarray is None:
            if self._deferred_init is not None and self._shape_complete():
                # the initializer runs only to be overwritten below
                self._finish_deferred_init(discarded=True)
            else:
                raise RuntimeError(
                    f"Parameter {self.name} has not been initialized")
        if isinstance(data, NDArray):
            self._ndarray._data = data.data.astype(self._ndarray.data.dtype)
        else:
            self._ndarray._data = nd.array(
                data, dtype=self._ndarray.data.dtype).data

    def cast(self, dtype):
        self.dtype = dtype
        if self._ndarray is not None:
            had_grad = self._ndarray._grad is not None
            self._ndarray = self._ndarray.astype(dtype)
            if had_grad and self._grad_req != "null":
                self._attach_grad()

    def var(self):
        from .. import symbol

        return symbol.var(self.name, shape=self.shape, dtype=self.dtype)


class Constant(Parameter):
    """Non-trainable constant (reference: gluon/parameter.py Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = nd.array(value)
        self.value = value

        class _Init(initializer.Initializer):
            def _init_weight(self2, _, arr):
                value.copyto(arr)

            _init_default = _init_weight

        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=_Init(),
                         differentiable=False)


class ParameterDict:
    """Dict of Parameters with prefix (reference: gluon/parameter.py
    ParameterDict)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = OrderedDict()
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def __getitem__(self, key):
        return self._params[key]

    def __iter__(self):
        return iter(self._params)

    def __repr__(self):
        s = "\n".join(repr(p) for p in self._params.values())
        return f"ParameterDict {self._prefix}(\n{s}\n)"

    def __len__(self):
        return len(self._params)

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def get(self, name, **kwargs):
        """Create-or-retrieve (reference behavior incl. shared lookup)."""
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
        else:
            for k, v in kwargs.items():
                if hasattr(param, k) and getattr(param, k) is not None:
                    if k == "shape" and v is not None:
                        param.shape = v
                else:
                    setattr(param, k, v)
        return param

    def get_constant(self, name, value=None):
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise KeyError(f"No constant named '{name}'")
            param = Constant(name, value)
            self._params[name] = param
        return param

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared._params:
            self._params[name] = self._shared._params[name]
            return self._params[name]
        return None

    def update(self, other):
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise ValueError(f"Cannot update self with other because they "
                                 f"have different Parameters with the same "
                                 f"name '{k}'")
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        if init is None:
            init = initializer.Uniform()
        for _, v in self.items():
            v.initialize(None, ctx, init, force_reinit=force_reinit)

    def zero_grad(self):
        for v in self.values():
            v.zero_grad()

    def reset_ctx(self, ctx):
        """Reference: parameter.py ParameterDict.reset_ctx."""
        for v in self.values():
            v.reset_ctx(ctx)

    def setattr(self, name, value):
        for v in self.values():
            setattr(v, name, value)

    def save(self, filename, strip_prefix=""):
        arg_dict = {}
        for param in self.values():
            block = param.list_data()
            weight = sum(w.copy() for w in block) / len(block)
            if not param.name.startswith(strip_prefix):
                raise ValueError(f"Prefix '{strip_prefix}' is to be striped "
                                 f"before saving, but Parameter's name "
                                 f"'{param.name}' does not start with it")
            arg_dict[param.name[len(strip_prefix):]] = weight
        nd.save(filename, arg_dict)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        arg_dict = nd.load(filename)
        arg_dict = {restore_prefix + k: v for k, v in arg_dict.items()}
        if not allow_missing:
            for name in self.keys():
                if name not in arg_dict:
                    raise IOError(f"Parameter {name} is missing in file "
                                  f"{filename}")
        for name in arg_dict:
            if name not in self._params:
                if not ignore_extra:
                    raise IOError(f"Parameter {name} loaded from file "
                                  f"{filename} is not present in this dict")
                continue
            self[name]._load_init_from(arg_dict[name])


def _load_init_from(self, data):
    if self._ndarray is None:
        self.shape = data.shape
        if self._deferred_init is not None:
            self._finish_deferred_init()
        else:
            self._finish_init(None, None, initializer.Uniform())
    self.set_data(data)


Parameter._load_init_from = _load_init_from
