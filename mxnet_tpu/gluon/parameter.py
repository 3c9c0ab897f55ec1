"""Gluon Parameter / ParameterDict.

Reference: ``python/mxnet/gluon/parameter.py`` (~1k LoC: Parameter with
deferred shape init via ``_finish_deferred_init``, per-ctx data copies, grad
arrays, grad_req, row_sparse support; ParameterDict with prefix scoping —
SURVEY.md §3.5 "Gluon core").

TPU-native: one NDArray per context (jax places buffers); sharded training
replaces per-ctx copies with a NamedSharding (parallel/), threaded through
``Trainer``.
"""
from __future__ import annotations

from collections import OrderedDict

import numpy as _np

from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..ndarray import ndarray as _ndm
from ..ndarray.ndarray import NDArray
from .. import initializer as init_mod
from .. import autograd

__all__ = ["Parameter", "Constant", "ParameterDict", "DeferredInitializationError"]


class DeferredInitializationError(MXNetError):
    """Parameter accessed before its deferred shape inference completed."""


class Parameter:
    """A weight of a Block: its data a context, and how its gradient is kept
    (``grad_req``: ``"write"``, ``"add"`` or ``"null"``).

    ``initialize`` marks the data of a trained parameter as an autograd
    variable and allocates no gradient buffer: the first ``grad()`` /
    ``list_grad()`` (or ``data().grad``) makes zeros of the weight's shape,
    dtype and context, the first ``backward`` that reaches the weight stores
    its gradient, and either way the same ``NDArray`` is returned ever after
    (``p.grad() is p.data().grad``).  So ``grad()`` before any backward
    gives zeros, as in MXNet 1.x, which allocates at ``initialize``; a net
    trained through ``parallel.data_parallel.TrainStep``, whose gradients
    live inside the compiled step, never asks and holds no buffers
    (``mxnet_parameter_grad_buffers_total`` counts those made)."""

    def __init__(self, name, grad_req="write", shape=None, dtype=_np.float32,
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        if isinstance(shape, int):
            shape = (shape,)
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self._allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        self._data = None      # dict ctx -> NDArray
        self._deferred_init = ()
        self._ctx_list = None
        self._stype = stype

    # -- shape with deferred (0/None) dims --------------------------------
    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape) if new_shape is not None else None
            return
        if new_shape is None:
            return
        if len(self._shape) != len(new_shape) or any(
                s not in (0, n) for s, n in zip(self._shape, new_shape)):
            raise MXNetError(
                f"Parameter {self.name}: incompatible shape {new_shape} vs "
                f"{self._shape}")
        self._shape = tuple(new_shape)

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        if req not in ("write", "add", "null"):
            raise MXNetError(f"invalid grad_req {req}")
        if not self._differentiable:
            req = "null"
        if self._grad_req == req:
            return
        self._grad_req = req
        if self._data is None:
            return
        if req == "null":
            for d in self._data.values():
                d._grad = None     # a backward then leaves it alone
        else:
            self._init_grad()

    def _shape_known(self):
        return self._shape is not None and all(s > 0 for s in self._shape)

    # -- initialization ----------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        default_init = default_init or init_mod.Uniform()
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        self._ctx_list = list(ctx)
        if not self._shape_known():
            if self._allow_deferred_init:
                self._deferred_init = (init, ctx, default_init)
                return
            raise MXNetError(
                f"cannot initialize Parameter {self.name} because it has "
                f"invalid shape {self._shape} (set allow_deferred_init or "
                "give a full shape)")
        self._finish_deferred_init(init, ctx, default_init)

    def _finish_deferred_init(self, initializer=None, ctx=None, default_init=None):
        """Reference: Parameter._finish_deferred_init — runs at first forward
        once input shapes pin the deferred dims."""
        if self._deferred_init:
            initializer, ctx, default_init = self._deferred_init
            self._deferred_init = ()
        if not self._shape_known():
            raise DeferredInitializationError(
                f"Parameter {self.name} has unknown shape {self._shape}")
        with autograd.pause():
            data = _ndm.invoke("zeros", [], {"shape": self._shape,
                                             "dtype": _np.dtype(self.dtype).name
                                             if self.dtype != "bfloat16" else "bfloat16"},
                               ctx=ctx[0])
            actual_init = initializer or self.init or default_init
            if isinstance(actual_init, str):
                actual_init = init_mod.create(actual_init)
            desc = init_mod.InitDesc(self.name)
            actual_init(desc, data)
            # initializers write a value built on JAX's default device
            data._set(_ndm._place(data._get(), ctx[0]))
            self._init_impl(data, ctx)

    def _init_impl(self, data, ctx_list):
        self._data = OrderedDict()
        for c in ctx_list:
            self._data[c] = data if c == ctx_list[0] else data.copyto(c)
        if self._grad_req != "null":
            self._init_grad()

    def _init_grad(self):
        """Marks every context's copy as a variable with no gradient buffer
        yet: the first ``grad()`` or backward makes it.  One made before is
        dropped."""
        for d in self._data.values():
            d._mark_variable(None, self._grad_req)

    # -- access ------------------------------------------------------------
    def _check_initialized(self, ctx=None):
        if self._data is None:
            if self._deferred_init:
                raise DeferredInitializationError(
                    f"Parameter {self.name} has not been initialized yet "
                    "(deferred init pending first forward)")
            raise MXNetError(
                f"Parameter {self.name} has not been initialized. Call "
                ".initialize() first")

    def data(self, ctx=None):
        # under a jit/functionalize trace, hand back the traced stand-in so
        # plain Blocks (not just HybridBlocks) read the traced value instead
        # of baking the concrete buffer in as a constant
        from .block import _TRACE

        tc = _TRACE.ctx
        if tc is not None and self in tc.param_map:
            return tc.param_map[self]
        self._check_initialized()
        if ctx is None:
            return next(iter(self._data.values()))
        if ctx not in self._data:
            raise MXNetError(f"Parameter {self.name} not initialized on {ctx}; "
                             f"available: {list(self._data)}")
        return self._data[ctx]

    def list_data(self):
        self._check_initialized()
        return list(self._data.values())

    def _check_trained(self):
        self._check_initialized()
        if self._grad_req == "null":
            raise MXNetError(f"Parameter {self.name} has grad_req='null'")

    def grad(self, ctx=None):
        self._check_trained()
        if ctx is None:
            return next(iter(self._data.values())).grad
        return self._data[ctx].grad

    def list_grad(self):
        self._check_trained()
        return [d.grad for d in self._data.values()]

    def list_ctx(self):
        if self._data is None and self._deferred_init:
            return self._deferred_init[1]
        self._check_initialized()
        return list(self._data.keys())

    def zero_grad(self):
        if self._data is None:
            return
        for d in self._data.values():
            d.zero_grad()

    def set_data(self, data):
        self.shape = data.shape
        if self._data is None:
            if self._deferred_init:
                self._finish_deferred_init()
            else:
                raise MXNetError(f"Parameter {self.name} not initialized")
        for c, d in self._data.items():
            src = data if isinstance(data, NDArray) else _ndm.array(data)
            d._set(src.as_in_context(c)._get().astype(d._get().dtype))

    def reset_ctx(self, ctx):
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data is not None:
            data = next(iter(self._data.values()))
            self._init_impl(data.copy(), ctx)
        self._ctx_list = list(ctx)

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is None:
            return
        with autograd.pause():
            for c in list(self._data):
                self._data[c] = self._data[c].astype(dtype)
            if self._grad_req != "null":
                self._init_grad()

    def var(self):
        from ..symbol.symbol import var

        return var(self.name, shape=self.shape, dtype=self.dtype)

    def __repr__(self):
        return f"Parameter {self.name} (shape={self._shape}, dtype={self.dtype})"


class Constant(Parameter):
    """Non-trainable constant parameter (reference: gluon.Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = _ndm.array(value)
        self.value = value

        class _CInit(init_mod.Initializer):
            def _init_weight(s, _, arr):
                arr._set(value._get())

            def _init_default(s, _, arr):
                arr._set(value._get())

            def __call__(s, desc, arr):
                arr._set(value._get().astype(arr._get().dtype))

        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=_CInit(), differentiable=False)


class ParameterDict:
    """Prefix-scoped dict of Parameters (reference: gluon.ParameterDict)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = OrderedDict()
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def __getitem__(self, key):
        return self._params[key]

    def __contains__(self, key):
        return key in self._params

    def get(self, name, **kwargs):
        """Get-or-create (reference semantics: shared lookup first)."""
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
        else:
            for k, v in kwargs.items():
                if k == "shape":
                    param.shape = v
                elif k == "init" and v is not None and param.init is None:
                    param.init = v
        return param

    def get_constant(self, name, value=None):
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise MXNetError(f"no constant named {name} and no value given")
            param = Constant(name, value)
            self._params[name] = param
        return param

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared:
            self._params[name] = self._shared[name]
            return self._params[name]
        return None

    def update(self, other):
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise MXNetError(f"duplicate parameter name {k}")
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        default = init or init_mod.Uniform()
        for _, v in self.items():
            v.initialize(None, ctx, default, force_reinit=force_reinit)

    def zero_grad(self):
        for v in self.values():
            v.zero_grad()

    def reset_ctx(self, ctx):
        for v in self.values():
            v.reset_ctx(ctx)

    def setattr(self, name, value):
        for v in self.values():
            setattr(v, name, value)

    def save(self, filename, strip_prefix=""):
        from ..ndarray.serialization import save as _save

        arg = {}
        for name, param in self.items():
            weight = param.data()
            if not name.startswith(strip_prefix):
                raise MXNetError(f"prefix {strip_prefix} not in {name}")
            arg[name[len(strip_prefix):]] = weight
        _save(filename, arg)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        from ..ndarray.serialization import load as _load

        loaded = _load(filename)
        loaded = {restore_prefix + k.replace("arg:", "").replace("aux:", ""): v
                  for k, v in loaded.items()}
        if not allow_missing:
            for name in self.keys():
                if name not in loaded:
                    raise MXNetError(f"Parameter {name} missing in {filename}")
        for name, v in loaded.items():
            if name not in self._params:
                if not ignore_extra:
                    raise MXNetError(f"Parameter {name} in file is not in this "
                                     "ParameterDict (set ignore_extra=True)")
                continue
            p = self._params[name]
            if p._data is None and not p._deferred_init:
                p.shape = v.shape
                p.initialize(ctx=ctx or [current_context()])
            p.set_data(v)

    def __repr__(self):
        s = "\n".join(repr(v) for v in self.values())
        return f"ParameterDict(prefix={self._prefix!r})\n{s}"
