"""NDArray: MXNet's mutable tensor, rebuilt as a handle over ``jax.Array``.

Reference: ``python/mxnet/ndarray/ndarray.py`` (class NDArray) over
``include/mxnet/ndarray.h`` + ``src/ndarray/ndarray.cc`` — SURVEY.md §3.1.

TPU-native mapping of the reference's engine semantics (SURVEY.md §2 key
invariant, §4.1):
- async dispatch: jax ops dispatch asynchronously; results are futures.
  ``wait_to_read()`` = ``block_until_ready`` (≙ engine WaitToRead);
  ``asnumpy()`` is the blocking device→host sync point.
- in-place mutation (``a[:]=x``, ``a+=1``): jax arrays are immutable, so the
  handle swaps in a functionally-updated buffer (``.at[].set``). XLA's buffer
  donation recovers the memory; the *semantics* (every alias sees the write)
  are preserved via write-through views.
- views (``Reshape``/``Slice``/``At``): a view NDArray keeps (base, spec
  chain); reads recompose from the base, writes write through to the base —
  emulating the reference's shared-Chunk aliasing.
- async error propagation: XLA raises at the sync point, matching the
  engine's exception-on-var contract (§3.1).
"""
from __future__ import annotations

import functools
import time as _time
import weakref

import numpy as _np

from ..base import MXNetError, numeric_types, integer_types
from ..context import Context, current_context
from .. import autograd as _ag
from ..ops.registry import get_op
from . import dispatch_cache as _dc

__all__ = ["NDArray", "invoke", "array", "waitall", "concatenate"]


def _jnp():
    import jax.numpy as jnp

    return jnp


def _jax():
    import jax

    return jax


# live-array tracking for waitall() (reference: Engine::WaitForAll)
_LIVE = weakref.WeakSet()


def waitall():
    """Block until all outstanding computation on live NDArrays finishes.

    Reference: mx.nd.waitall -> Engine::WaitForAll (src/engine/).
    """
    # errors must surface at sync points (engine contract): wait_to_read
    # already wraps async XLA failures as MXNetError — propagate everything
    for arr in list(_LIVE):
        arr.wait_to_read()


class NDArray:
    """n-dimensional array on a Context, with imperative (mutable) semantics.

    Owning arrays hold ``_data`` (a jax.Array). Views hold ``_base`` + a spec
    chain and recompose lazily.
    """

    __slots__ = ("_data", "_base", "_spec", "_ctx", "_version",
                 "_ag_entry", "_grad", "_grad_req",
                 "__weakref__")

    # higher than numpy's so ndarray.__add__(np, NDArray) defers to us
    __array_priority__ = 1000.0

    def __init__(self):
        raise MXNetError("use mx.nd.array / mx.nd.zeros / ... to create NDArrays")

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    @classmethod
    def _new(cls):
        self = object.__new__(cls)
        self._data = None
        self._base = None
        self._spec = ()
        self._ctx = None
        self._version = 0
        self._ag_entry = None
        self._grad = None
        self._grad_req = "write"
        _LIVE.add(self)
        return self

    @classmethod
    def _from_jax(cls, value, ctx=None):
        self = cls._new()
        self._data = value
        self._ctx = ctx or current_context()
        return self

    @classmethod
    def _view(cls, base, spec_item):
        root = base._base if base._base is not None else base
        chain = base._spec + (spec_item,)
        self = cls._new()
        self._base = root
        self._spec = chain
        self._ctx = base.context
        return self

    # ------------------------------------------------------------------
    # value access (functional core)
    # ------------------------------------------------------------------
    def _get(self):
        """Current jax value of this handle (recomposing views)."""
        if self._base is None:
            return self._data
        v = self._base._get()
        for kind, arg in self._spec:
            if kind == "index":
                v = v[arg]
            elif kind == "reshape":
                v = v.reshape(arg)
            else:  # pragma: no cover
                raise MXNetError(f"bad view spec {kind}")
        return v

    def _set(self, value):
        """Write a new value through this handle (write-through for views)."""
        if self._base is None:
            if self._data is not None and (tuple(value.shape) != self.shape):
                raise MXNetError(
                    f"cannot assign shape {tuple(value.shape)} to NDArray of "
                    f"shape {self.shape}")
            self._data = value
            self._version += 1
            return
        # recompose: apply the spec chain in reverse against the base
        base = self._base
        jnp = _jnp()

        def apply(v, chain, new):
            if not chain:
                return jnp.asarray(new, dtype=v.dtype)
            (kind, arg), rest = chain[0], chain[1:]
            if kind == "index":
                sub = apply(v[arg], rest, new)
                return v.at[arg].set(sub)
            elif kind == "reshape":
                sub = apply(v.reshape(arg), rest, new)
                return sub.reshape(v.shape)
            raise MXNetError(f"bad view spec {kind}")

        base._set(apply(base._get(), list(self._spec), value))

    # ------------------------------------------------------------------
    # basic properties
    # ------------------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._get().shape)

    @property
    def dtype(self):
        return _np.dtype(self._get().dtype)

    @property
    def size(self):
        s = 1
        for d in self.shape:
            s *= d
        return s

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def context(self):
        return self._ctx or current_context()

    ctx = context

    @property
    def stype(self):
        return "default"

    @property
    def grad(self):
        if self._grad is _ag.UNMADE:
            self._make_grad()
        return self._grad

    @property
    def T(self):
        return self.transpose()

    @property
    def handle(self):  # legacy compat: the jax array IS the handle
        return self._get()

    # ------------------------------------------------------------------
    # sync / host transfer  (reference §4.1: asnumpy == WaitToRead + D2H)
    # ------------------------------------------------------------------
    def wait_to_read(self):
        try:
            v = self._get()
            if hasattr(v, "block_until_ready"):
                v.block_until_ready()
        except Exception as e:  # surface async XLA errors as MXNetError
            raise MXNetError(str(e)) from e
        return self

    def asnumpy(self):
        try:
            return _np.asarray(self._get())
        except Exception as e:
            raise MXNetError(str(e)) from e

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def tolist(self):
        return self.asnumpy().tolist()

    # ------------------------------------------------------------------
    # autograd
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        """Allocate a gradient buffer and mark this array as a variable.

        Reference: NDArray.attach_grad -> MXAutogradMarkVariables.
        """
        jnp = _jnp()
        g = NDArray._from_jax(jnp.zeros(self.shape, self.dtype), self.context)
        self._mark_variable(g, grad_req)

    def _mark_variable(self, grad_nd, grad_req="write"):
        """``grad_nd`` the gradient buffer, or ``None`` (a Gluon parameter's
        data): the first that asks for the buffer makes it, ``grad`` zeros
        and a backward its cotangent (``_make_grad``)."""
        self._grad = _ag.UNMADE if grad_nd is None else grad_nd
        self._grad_req = grad_req
        self._ag_entry = _ag.Entry(variable=self, grad_req=grad_req,
                                   shape=self.shape, dtype=self.dtype)

    def _make_grad(self, value=None):
        """The buffer of a variable marked without one: ``value`` (a
        backward's first cotangent), or zeros of this array's shape, dtype
        and context."""
        from .. import telemetry

        if value is None:
            value = _jnp().zeros_like(self._get())
        self._grad = NDArray._from_jax(value, self.context)
        telemetry.PARAMETER_GRAD_BUFFERS.inc()
        telemetry.PARAMETER_GRAD_BYTES.inc(value.size * value.dtype.itemsize)

    def zero_grad(self):
        if self._grad is not None and self._grad is not _ag.UNMADE:
            jnp = _jnp()
            self._grad._set(jnp.zeros(self._grad.shape, self._grad.dtype))

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        _ag.backward([self], [out_grad], retain_graph=retain_graph,
                     train_mode=train_mode)

    def detach(self):
        out = NDArray._from_jax(self._get(), self.context)
        return out

    # ------------------------------------------------------------------
    # copies / casts / movement
    # ------------------------------------------------------------------
    def copy(self):
        return NDArray._from_jax(self._get(), self.context)

    def copyto(self, other):
        """Copy into another NDArray (cross-device: ≙ CopyFromTo,
        src/ndarray/ndarray.cc) or to a Context."""
        jax = _jax()
        if isinstance(other, Context):
            v = jax.device_put(self._get(), other.device)
            return NDArray._from_jax(v, other)
        v = jax.device_put(self._get(), other.context.device)
        if tuple(v.shape) != other.shape:
            raise MXNetError("copyto: shape mismatch")
        other._set(v.astype(other.dtype))
        return other

    def as_in_context(self, ctx):
        if ctx == self.context:
            return self
        return self.copyto(ctx)

    as_in_ctx = as_in_context

    def astype(self, dtype, copy=True):
        jnp = _jnp()
        v = self._get().astype(_resolve_dtype(dtype))
        return NDArray._from_jax(v, self.context)

    def as_nd_ndarray(self):
        return self

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    def __getitem__(self, key):
        key = _sanitize_key(key)
        if _ag.is_recording() and _on_tape(self):
            # route through an op so the slice is differentiable (reference
            # records slice ops on the tape too)
            return invoke("_slice_key", [self], {"key": key})
        return NDArray._view(self, ("index", key))

    def __setitem__(self, key, value):
        jnp = _jnp()
        key = _sanitize_key(key)
        if _ag.is_recording() and (
                _on_tape(self) or (isinstance(value, NDArray) and _on_tape(value))):
            # record the sliced write as a differentiable scatter so gradients
            # don't silently vanish (reference hard-part 1: in-place writes
            # are write-var ops on the tape); the handle's tape entry rebinds
            # to the scatter output
            if isinstance(value, NDArray):
                vnd = value
            else:
                vnd = NDArray._from_jax(
                    jnp.asarray(value if isinstance(value, numeric_types)
                                else _np.asarray(value)), self.context)
            out = invoke("_scatter_set_key", [self, vnd], {"key": key})
            self._set(out._get())
            self._ag_entry = out._ag_entry
            return
        if isinstance(value, NDArray):
            v = value._get()
        elif isinstance(value, numeric_types):
            v = value
        else:
            v = jnp.asarray(_np.asarray(value))
        cur = self._get()
        self._set(cur.at[key].set(v))

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # ------------------------------------------------------------------
    # operators — all dispatch through the registry so autograd sees them
    # ------------------------------------------------------------------
    def _binary(self, op, other, reverse=False):
        if isinstance(other, NDArray):
            args = [other, self] if reverse else [self, other]
            return invoke(op, args, {})
        if isinstance(other, numeric_types):
            attrs = {"scalar": float(other), "reverse": reverse}
            return invoke(op + "_scalar", [self], attrs)
        if isinstance(other, (_np.ndarray, list, tuple)):
            o = array(other, ctx=self.context)
            args = [o, self] if reverse else [self, o]
            return invoke(op, args, {})
        return NotImplemented

    def __add__(self, o):
        return self._binary("broadcast_add", o)

    def __radd__(self, o):
        return self._binary("broadcast_add", o, reverse=True)

    def __sub__(self, o):
        return self._binary("broadcast_sub", o)

    def __rsub__(self, o):
        return self._binary("broadcast_sub", o, reverse=True)

    def __mul__(self, o):
        return self._binary("broadcast_mul", o)

    def __rmul__(self, o):
        return self._binary("broadcast_mul", o, reverse=True)

    def __truediv__(self, o):
        return self._binary("broadcast_div", o)

    def __rtruediv__(self, o):
        return self._binary("broadcast_div", o, reverse=True)

    def __mod__(self, o):
        return self._binary("broadcast_mod", o)

    def __rmod__(self, o):
        return self._binary("broadcast_mod", o, reverse=True)

    def __pow__(self, o):
        return self._binary("broadcast_power", o)

    def __rpow__(self, o):
        return self._binary("broadcast_power", o, reverse=True)

    def __matmul__(self, o):
        from . import dot as _dot  # storage-dispatching (csr SpMM path)

        return _dot(self, o)

    def __neg__(self):
        return invoke("negative", [self], {})

    def __abs__(self):
        return invoke("abs", [self], {})

    # in-place: functional update + handle swap (donation-friendly)
    def __iadd__(self, o):
        r = self._binary("broadcast_add", o)
        self._set(r._get().astype(self._get().dtype))
        return self

    def __isub__(self, o):
        r = self._binary("broadcast_sub", o)
        self._set(r._get().astype(self._get().dtype))
        return self

    def __imul__(self, o):
        r = self._binary("broadcast_mul", o)
        self._set(r._get().astype(self._get().dtype))
        return self

    def __itruediv__(self, o):
        r = self._binary("broadcast_div", o)
        self._set(r._get().astype(self._get().dtype))
        return self

    # comparisons (non-differentiable)
    def __eq__(self, o):
        if o is None:
            return False
        return self._binary("broadcast_equal", o)

    def __ne__(self, o):
        if o is None:
            return True
        return self._binary("broadcast_not_equal", o)

    def __gt__(self, o):
        return self._binary("broadcast_greater", o)

    def __ge__(self, o):
        return self._binary("broadcast_greater_equal", o)

    def __lt__(self, o):
        return self._binary("broadcast_lesser", o)

    def __le__(self, o):
        return self._binary("broadcast_lesser_equal", o)

    __hash__ = object.__hash__  # identity hash (mutable container semantics)

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("ambiguous truth value of multi-element NDArray")

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __repr__(self):
        try:
            s = str(self.asnumpy())
        except MXNetError as e:
            s = f"<error: {e}>"
        return f"\n{s}\n<NDArray {'x'.join(map(str, self.shape))} @{self.context}>"

    # ------------------------------------------------------------------
    # common method surface (delegating to ops)
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = kwargs.get("shape", shape)
        new_shape = _infer_reshape(self.shape, tuple(shape))
        if _ag.is_recording() and _on_tape(self):
            return invoke("reshape", [self], {"shape": new_shape})
        return NDArray._view(self, ("reshape", new_shape))

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def transpose(self, axes=None):
        return invoke("transpose", [self], {"axes": axes})

    def flatten(self):
        return invoke("flatten", [self], {})

    def squeeze(self, axis=None):
        return invoke("squeeze", [self], {"axis": axis})

    def expand_dims(self, axis):
        return invoke("expand_dims", [self], {"axis": axis})

    def swapaxes(self, dim1, dim2):
        return invoke("swapaxes", [self], {"dim1": dim1, "dim2": dim2})

    def broadcast_to(self, shape):
        return invoke("broadcast_to", [self], {"shape": shape})

    def broadcast_like(self, other):
        return invoke("broadcast_to", [self], {"shape": other.shape})

    def slice_axis(self, axis, begin, end):
        return invoke("slice_axis", [self], {"axis": axis, "begin": begin, "end": end})

    def tile(self, reps):
        return invoke("tile", [self], {"reps": reps})

    def repeat(self, repeats, axis=None):
        return invoke("repeat", [self], {"repeats": repeats, "axis": axis})

    def pad(self, *a, **kw):
        return invoke("pad", [self], kw)

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return invoke("split", [self], {"num_outputs": num_outputs, "axis": axis,
                                        "squeeze_axis": squeeze_axis})

    def clip(self, a_min=None, a_max=None):
        return invoke("clip", [self], {"a_min": a_min, "a_max": a_max})

    def abs(self):
        return invoke("abs", [self], {})

    def sign(self):
        return invoke("sign", [self], {})

    def sqrt(self):
        return invoke("sqrt", [self], {})

    def square(self):
        return invoke("square", [self], {})

    def exp(self):
        return invoke("exp", [self], {})

    def log(self):
        return invoke("log", [self], {})

    def relu(self):
        return invoke("relu", [self], {})

    def sigmoid(self):
        return invoke("sigmoid", [self], {})

    def tanh(self):
        return invoke("tanh", [self], {})

    def softmax(self, axis=-1):
        return invoke("softmax", [self], {"axis": axis})

    def log_softmax(self, axis=-1):
        return invoke("log_softmax", [self], {"axis": axis})

    def sum(self, axis=None, keepdims=False):
        return invoke("sum", [self], {"axis": axis, "keepdims": keepdims})

    def nansum(self, axis=None, keepdims=False):
        return invoke("nansum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return invoke("mean", [self], {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False):
        return invoke("max", [self], {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False):
        return invoke("min", [self], {"axis": axis, "keepdims": keepdims})

    def prod(self, axis=None, keepdims=False):
        return invoke("prod", [self], {"axis": axis, "keepdims": keepdims})

    def norm(self, ord=2, axis=None, keepdims=False):
        return invoke("norm", [self], {"ord": ord, "axis": axis, "keepdims": keepdims})

    def argmax(self, axis=None, keepdims=False):
        return invoke("argmax", [self], {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False):
        return invoke("argmin", [self], {"axis": axis, "keepdims": keepdims})

    def argsort(self, axis=-1, is_ascend=True):
        return invoke("argsort", [self], {"axis": axis, "is_ascend": is_ascend})

    def sort(self, axis=-1, is_ascend=True):
        return invoke("sort", [self], {"axis": axis, "is_ascend": is_ascend})

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        return invoke("topk", [self], {"axis": axis, "k": k, "ret_typ": ret_typ,
                                       "is_ascend": is_ascend})

    def dot(self, other, transpose_a=False, transpose_b=False):
        from . import dot as _dot  # storage-dispatching (csr SpMM path)

        return _dot(self, other, transpose_a=transpose_a,
                    transpose_b=transpose_b)

    def one_hot(self, depth, on_value=1.0, off_value=0.0):
        return invoke("one_hot", [self], {"depth": depth, "on_value": on_value,
                                          "off_value": off_value})

    def take(self, indices, axis=0, mode="clip"):
        return invoke("take", [self, indices], {"axis": axis, "mode": mode})

    def tostype(self, stype):
        if stype == "default":
            return self
        from .sparse import RowSparseNDArray, CSRNDArray

        if stype == "row_sparse":
            return RowSparseNDArray.from_dense(self._get(), self.context)
        if stype == "csr":
            return CSRNDArray.from_dense(self._get(), self.context)
        raise MXNetError(f"unknown storage type {stype!r}")

    def to_dlpack_for_read(self):
        return self._get().__dlpack__()

    def to_dlpack_for_write(self):
        return self._get().__dlpack__()


# --------------------------------------------------------------------------
# the imperative invoke path (reference: MXImperativeInvokeEx ->
# Imperative::Invoke -> PushFCompute, SURVEY.md §4.1)
# --------------------------------------------------------------------------
def invoke(opname, nd_args, attrs, out=None, ctx=None):
    """Execute a registered op on NDArray inputs.

    1. unwrap inputs (snapshot jax values — free, they're immutable)
    2. run the pure fn (jax dispatches async ≙ Engine::PushAsync) — repeat
       calls go through a jit-cached executable (dispatch_cache.py, the
       CachedOp-style fast path) instead of per-primitive eager dispatch
    3. record on the autograd tape if needed (≙ Imperative::RecordOp)
    4. wrap outputs in NDArrays
    """
    od = get_op(opname)
    if _SYMTRACE["on"]:
        from ..symbol.symbol import SymbolTracer, trace_invoke

        if any(isinstance(a, SymbolTracer) for a in nd_args if a is not None):
            return trace_invoke(opname, nd_args, attrs)
    nd_args = [a for a in nd_args if a is not None]  # optional inputs omitted
    in_vals = []
    out_ctx = ctx
    for a in nd_args:
        if isinstance(a, NDArray):
            in_vals.append(a._get())
            if out_ctx is None:
                out_ctx = a.context
        else:
            in_vals.append(_jnp().asarray(a))
    if od.needs_rng:
        from .. import random as _rnd
        in_vals = [_rnd._next_key()] + in_vals
        nd_args = [None] + list(nd_args)
    if od.creation and out_ctx is None:
        out_ctx = current_context()

    # jit-cache fast path (dispatch_cache.py): serve a compiled executable
    # keyed on (op, static attrs, input avals, AMP state, ctx kind, train
    # mode).  Keyed on the RAW attrs — filtering is deterministic per raw
    # attrs, so a hit skips it entirely.  Any incompatible mode (unhashable
    # attrs, tracer inputs, trace-scoped RNG, NaiveEngine, blocklisted op)
    # falls through to the plain eager path below.
    fn = None
    call_fn = None
    cache_key = None
    if (_dc.enabled() and od.jit_safe and not _dc.is_blocked(od.name)
            and not _rng_in_trace(od)):
        cache_key = _dc.make_key(
            od.name, attrs, in_vals,
            (_AMP["epoch"] if _AMP["on"] else None),
            (out_ctx.device_type if out_ctx is not None else None),
            _ag.is_training(), stats_name=opname)
        if cache_key is not None:
            # stats keyed on the CALL-SITE name (so aliased ops line up
            # with the profiler's per-op rows); the cache key and the
            # blocklist use the canonical od.name so aliases share entries
            call_fn = _dc.lookup(opname, cache_key)
    if call_fn is None:
        attrs = {k: v for k, v in attrs.items()
                 if v is not None or k in ("axis", "a_min", "a_max")}
        fn = functools.partial(_call_with_attrs, od.fn, attrs)
        if _AMP["on"]:
            # mixed-precision cast policy (contrib.amp): wraps fn so per-op
            # input casts are part of the traced/vjp'd computation —
            # gradients flow back to the original (fp32 master) dtype
            # through the cast's transpose
            fn = _AMP["wrap"](od, fn)
        call_fn = _jax().jit(fn) if cache_key is not None else fn

    recording = (_ag.is_recording() and od.differentiable
                 and any(isinstance(a, NDArray) and _on_tape(a) for a in nd_args if a is not None))

    # per-op timing (reference: engine profiler op events).  Honest timing
    # of an async dispatch requires a sync — same trade the reference's
    # profiler makes via engine bulk-flush.  Snapshot the recorder: another
    # thread's profiler.stop() must not null it mid-op.
    _prof_rec = _PROFILE["record"] if _PROFILE["on"] else None
    if _prof_rec is not None:
        _prof_t0 = _time.perf_counter()

    # fresh compile about to happen (miss path only — hits never get here
    # with fn set): time it for the telemetry compile-event tracer
    _compile_t0 = _time.perf_counter() \
        if (fn is not None and cache_key is not None) else None

    try:
        if recording:
            entries = [(a._ag_entry if isinstance(a, NDArray) else None)
                       for a in nd_args]
            # jit under record_op's vjp: the forward executes compiled and
            # the vjp's transpose compiles too (pjit jvp/transpose rules)
            out_vals, out_entries, multi = _ag.record_op(
                call_fn, in_vals, entries, name=opname)
        else:
            out_vals = call_fn(*in_vals)
            multi = isinstance(out_vals, (tuple, list))
            out_entries = None
    except Exception:
        if fn is None or call_fn is fn:
            raise  # plain eager path (or cached-hit): the error is real
        # first compile of this key failed: retry eagerly.  A real data
        # error raises identically from the eager run and propagates; if
        # eager *succeeds* this (op, attrs, avals) variant is
        # trace-incompatible — cache the EAGER fn in its slot (no retrace
        # on repeats, other variants keep the fast path) and record the
        # failure, escalating to an op-wide block only if more keys fail.
        call_fn = fn
        if recording:
            entries = [(a._ag_entry if isinstance(a, NDArray) else None)
                       for a in nd_args]
            out_vals, out_entries, multi = _ag.record_op(
                fn, in_vals, entries, name=opname)
        else:
            out_vals = fn(*in_vals)
            multi = isinstance(out_vals, (tuple, list))
            out_entries = None
        _dc.mark_unsafe(od.name, cache_key)
    if fn is not None and cache_key is not None:
        _dc.insert(cache_key, call_fn)
        _dc.record_compile(od.name, cache_key,
                           _time.perf_counter() - _compile_t0,
                           failed=call_fn is fn)

    if _prof_rec is not None:
        _sync = out_vals[0] if multi else out_vals
        if hasattr(_sync, "block_until_ready"):
            _sync.block_until_ready()
        _prof_rec(opname, _prof_t0, _time.perf_counter())

    outs = list(out_vals) if multi else [out_vals]
    if od.creation:
        outs = [_place(v, out_ctx) for v in outs]
    if _NAN_CHECK["on"]:
        _check_finite(opname, outs)
    nd_outs = []
    for i, v in enumerate(outs):
        o = NDArray._from_jax(v, out_ctx)
        if out_entries is not None:
            o._ag_entry = out_entries[i]
        nd_outs.append(o)

    if out is not None:
        targets = out if isinstance(out, (list, tuple)) else [out]
        for t, o in zip(targets, nd_outs):
            t._set(o._get().astype(t._get().dtype))
            if out_entries is not None:
                t._ag_entry = o._ag_entry
        return out
    return nd_outs if multi else nd_outs[0]


# flag flipped by symbol-export tracing (symbol/symbol.py trace_invoke) so the
# hot imperative path pays one dict lookup, not an isinstance sweep
_SYMTRACE = {"on": False}

# mixed-precision state, owned by contrib.amp (reference: amp.init()
# monkey-patches op namespaces — here one dict lookup gates the hot path).
# "wrap": callable(opdef, fn) -> fn installed by contrib.amp.  "epoch" is a
# monotonic token bumped on every policy (re)install: the dispatch cache
# keys executables on it so a policy change can never serve stale casts.
_AMP = {"on": False, "wrap": None, "epoch": 0}


def _rng_in_trace(od):
    """True when this needs_rng op draws from a trace-scoped key (inside a
    hybridize/TrainStep trace): the outer jit owns compilation then."""
    if not od.needs_rng:
        return False
    from .. import random as _rnd

    return _rnd._in_trace()

# per-op profiling state, owned by profiler.py ("record": callable(opname,
# t0, t1) installed while profiling imperative ops is enabled)
_PROFILE = {"on": False, "record": None}

# NaN/Inf sanitizer state, owned by engine.set_nan_check (SURVEY.md §6.2:
# the TPU analog of the reference's sanitizer lane — device-side checkify)
_NAN_CHECK = {"on": False}


def _call_with_attrs(fn, attrs, *arrays):
    return fn(*arrays, **attrs)


def _place(v, ctx):
    """Put a freshly created value on the device its context names.

    A creation op has no input to follow, so JAX builds its result on the
    default device whatever ``ctx`` says.  A value already there is left
    as it is (uncommitted); any other is committed to ``ctx.device``, as
    ``nd.array`` does.  A tracer has no device: inside a jit trace the
    enclosing computation decides."""
    jax = _jax()
    if isinstance(v, jax.core.Tracer) or not isinstance(v, jax.Array):
        return v
    dev = ctx.device
    return v if dev in v.devices() else jax.device_put(v, dev)


def _check_finite(opname, vals):
    """NaN/Inf sanitizer (engine.set_nan_check): synchronous check at the
    dispatch seam — the imperative analog of wrapping the program in
    jax.experimental.checkify.  Eager-only: under a trace the values are
    abstract, and the jit path is covered by the loss-finiteness checks."""
    jnp = _jnp()
    import jax

    for v in vals:
        if isinstance(v, jax.core.Tracer) or not hasattr(v, "dtype"):
            continue
        if jnp.issubdtype(v.dtype, jnp.floating) and v.size:
            if not bool(jnp.isfinite(v).all()):
                from ..base import MXNetError

                raise MXNetError(
                    f"nan_check: op {opname!r} produced non-finite values")


def apply_fn(fn, nd_args, name="custom_fn", ctx=None):
    """Run an ad-hoc pure jax function over NDArray inputs with full autograd
    integration — the escape hatch for composite ops (fused RNN scan, pallas
    kernels) that aren't in the registry.  Same tape semantics as invoke()."""
    jnp = _jnp()
    in_vals = []
    out_ctx = ctx
    for a in nd_args:
        if isinstance(a, NDArray):
            in_vals.append(a._get())
            if out_ctx is None:
                out_ctx = a.context
        else:
            in_vals.append(jnp.asarray(a))

    recording = _ag.is_recording() and any(
        isinstance(a, NDArray) and _on_tape(a) for a in nd_args)
    if recording:
        entries = [(a._ag_entry if isinstance(a, NDArray) else None)
                   for a in nd_args]
        out_vals, out_entries, multi = _ag.record_op(fn, in_vals, entries,
                                                     name=name)
    else:
        out_vals = fn(*in_vals)
        multi = isinstance(out_vals, (tuple, list))
        out_entries = None

    outs = list(out_vals) if multi else [out_vals]
    nd_outs = []
    for i, v in enumerate(outs):
        o = NDArray._from_jax(v, out_ctx)
        if out_entries is not None:
            o._ag_entry = out_entries[i]
        nd_outs.append(o)
    return nd_outs if multi else nd_outs[0]


def _on_tape(a):
    return a._ag_entry is not None


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------
def _resolve_dtype(dtype):
    if isinstance(dtype, str) and dtype == "bfloat16":
        return _jnp().bfloat16
    return _np.dtype(dtype) if not isinstance(dtype, type(_jnp().bfloat16)) else dtype


def _sanitize_key(key):
    def conv(k):
        if isinstance(k, NDArray):
            return k._get()
        return k

    if isinstance(key, tuple):
        return tuple(conv(k) for k in key)
    return conv(key)


def _infer_reshape(cur_shape, shape):
    """MXNet reshape specials: 0 = copy dim, -1 = infer, -2..-4 partial.
    Supports 0 and -1 (the overwhelmingly common cases)."""
    size = 1
    for d in cur_shape:
        size *= d
    out = []
    for i, d in enumerate(shape):
        if d == 0:
            out.append(cur_shape[i])
        else:
            out.append(d)
    if -1 in out:
        known = 1
        for d in out:
            if d != -1:
                known *= d
        out[out.index(-1)] = size // max(known, 1)
    return tuple(out)


def array(source_array, ctx=None, dtype=None):
    """Create an NDArray from any array-like (reference: mx.nd.array)."""
    jax = _jax()
    jnp = _jnp()
    ctx = ctx or current_context()
    if isinstance(source_array, NDArray):
        v = source_array._get()
    else:
        from_pylist = not hasattr(source_array, "dtype")
        v = _np.asarray(source_array)
        if dtype is None:
            # MXNet default dtype discipline: python lists -> float32;
            # numpy keeps dtype except 64-bit (x64 disabled on the jax side)
            if from_pylist or v.dtype == _np.float64:
                dtype = _np.float32
            elif v.dtype == _np.int64:
                dtype = _np.int32
    if dtype is not None:
        v = _np.asarray(v).astype(_resolve_dtype(dtype)) if not hasattr(v, "astype") else v.astype(_resolve_dtype(dtype))
    if getattr(v, "ndim", 1) == 0:
        # reference semantics: the LEGACY nd namespace has no zero-dim
        # arrays — scalars become shape (1,) — unless npx.set_np(shape=
        # True) is active (mx.np.array is unaffected: numpy semantics are
        # native there)
        from ..numpy_extension import is_np_shape

        if not is_np_shape():
            v = _np.asarray(v).reshape(1)
    out = jax.device_put(jnp.asarray(v), ctx.device)
    return NDArray._from_jax(out, ctx)


def concatenate(arrays, axis=0, always_copy=True):
    return invoke("concat", list(arrays), {"dim": axis})
