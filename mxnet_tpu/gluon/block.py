"""Gluon Block / HybridBlock: define-by-run layers with jit staging.

Reference: ``python/mxnet/gluon/block.py`` (~1.5k LoC — Block child/param
registration, forward hooks, save/load_parameters; HybridBlock._build_cache
traces ``hybrid_forward`` with Symbol proxies into a CachedOp; SymbolBlock —
SURVEY.md §3.5, §4.6).

TPU-native staging: ``hybridize()`` swaps the Symbol trace for a ``jax.jit``
trace (SURVEY.md §4.6 calls this "the exact seam where the TPU build swaps in
jax.jit").  The cached computation is a pure function

    fn(param_values, rng_key, *input_values) -> (outputs..., state_updates...)

jit-compiled per (input avals, training-mode, param dtypes).  Parameters ride
as arguments (not constants) so the same executable serves every step;
running-state mutations (BatchNorm moving stats) are threaded out as extra
outputs and written back to their Parameters after the call — the functional
equivalent of the reference's stateful FCompute.  Under ``autograd.record``
the whole cached op lands on the tape as ONE node whose vjp is jax's vjp of
the jitted function (≙ CachedOp backward caching).
"""
from __future__ import annotations

import re
import threading
from collections import OrderedDict
from contextlib import contextmanager

import numpy as _np

from ..base import MXNetError
from ..context import current_context
from .. import autograd as _ag
from .. import ndarray as _F
from ..ndarray.ndarray import NDArray
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


class _BlockScope(threading.local):
    def __init__(self):
        self.counters = {}
        self.scope_stack = []  # active name_scope() (prefix, counters) pairs

    def next_name(self, hint):
        if self.scope_stack:
            # inside `with block.name_scope()`: numbering is per-block
            # (reference: each Block owns a _BlockScope), so two instances
            # of the same model class produce identical child names and
            # save/load round-trips match
            prefix, counters = self.scope_stack[-1]
        else:
            prefix, counters = "", self.counters
        n = counters.get(hint, 0)
        counters[hint] = n + 1
        return f"{prefix}{hint}{n}_"


_NAME_SCOPE = _BlockScope()


class _TraceState(threading.local):
    def __init__(self):
        self.ctx = None  # active _TraceContext or None


_TRACE = _TraceState()


class _TraceContext:
    """Active while hybrid_forward is being traced under jax.jit."""

    def __init__(self, param_map):
        self.param_map = param_map          # Parameter -> traced NDArray
        self.state_updates = []             # [(Parameter, jax value)]


class Block:
    """Base container (reference: gluon.Block)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        if prefix is not None:
            # an explicit prefix is relative to the enclosing name_scope
            # (reference: BlockScope.create prepends the current scope)
            scope = _NAME_SCOPE.scope_stack[-1][0] if \
                _NAME_SCOPE.scope_stack else ""
            self._prefix = scope + prefix
        else:
            self._prefix = _NAME_SCOPE.next_name(self._alias())
        self._params = ParameterDict(self._prefix, shared=params)
        self._children = OrderedDict()
        self._reg_params = OrderedDict()
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._prefix[:-1] if self._prefix.endswith("_") else self._prefix

    @property
    def params(self):
        return self._params

    @contextmanager
    def name_scope(self):
        """Names of blocks/params created inside are prefixed with this
        block's prefix (reference: Block.name_scope — the idiom every Gluon
        model definition uses).  Numbering restarts per block instance."""
        if not hasattr(self, "_scope_counters"):
            self._scope_counters = {}
        _NAME_SCOPE.scope_stack.append((self._prefix, self._scope_counters))
        try:
            yield
        finally:
            _NAME_SCOPE.scope_stack.pop()

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = getattr(self, "_children", None)
            if existing is not None:
                self._children[name] = value
        elif isinstance(value, Parameter):
            if getattr(self, "_reg_params", None) is not None:
                self._reg_params[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block
        return block

    def register_forward_hook(self, hook):
        key = len(self._forward_hooks)
        self._forward_hooks[key] = hook
        return _HookHandle(self._forward_hooks, key)

    def register_forward_pre_hook(self, hook):
        key = len(self._forward_pre_hooks)
        self._forward_pre_hooks[key] = hook
        return _HookHandle(self._forward_pre_hooks, key)

    def collect_params(self, select=None):
        """All Parameters of self + descendants (reference semantics)."""
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self._params)
        else:
            pattern = re.compile(select)
            ret.update({k: v for k, v in self._params.items() if pattern.match(k)})
        for child in self._children.values():
            ret.update(child.collect_params(select))
        return ret

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, p in self._params.items():
            p.cast(dtype)
        self._bump_cache_version()

    def _bump_cache_version(self):
        pass

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def save_parameters(self, filename, deduplicate=False):
        """Reference: Block.save_parameters — params only, by block-path name."""
        params = self._collect_params_with_prefix()
        from ..ndarray.serialization import save as _save

        _save(filename, {k: v.data() for k, v in params.items()})

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False, dtype_source="current"):
        from ..ndarray.serialization import load as _load

        loaded = _load(filename)
        params = self._collect_params_with_prefix()
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    raise MXNetError(f"Parameter {name} missing in {filename}")
        for name, v in loaded.items():
            if name not in params:
                if not ignore_extra:
                    raise MXNetError(f"Parameter {name} in file not in Block "
                                     "(set ignore_extra=True)")
                continue
            p = params[name]
            if p._data is None:
                p.shape = v.shape
                p.initialize(ctx=ctx or [current_context()])
            p.set_data(v)
        self._bump_cache_version()

    # legacy aliases
    save_params = save_parameters
    load_params = load_parameters

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + k: v for k, v in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-layer summary (reference: Block.summary)."""
        rows = []

        def make_hook(name, block):
            def hook(blk, inp, out):
                shape = out.shape if hasattr(out, "shape") else \
                    [o.shape for o in out] if isinstance(out, (list, tuple)) else "?"
                n_params = sum(int(_np.prod(p.shape)) for p in
                               blk._reg_params.values() if p._shape_known())
                rows.append((name or "self", type(blk).__name__, shape, n_params))
            return hook

        handles = []
        for name, child in self._children.items():
            handles.append(child.register_forward_hook(make_hook(name, child)))
        try:
            self(*inputs)
        finally:
            for h in handles:
                h.detach()
        header = f"{'Layer':<24}{'Type':<20}{'Output shape':<24}{'Params':<12}"
        print(header)
        print("-" * len(header))
        total = 0
        for name, typ, shape, n in rows:
            print(f"{name:<24}{typ:<20}{str(shape):<24}{n:<12}")
            total += n
        print("-" * len(header))
        print(f"Total params (shown layers): {total}")

    def __repr__(self):
        s = f"{type(self).__name__}(\n"
        for key, child in self._children.items():
            s += f"  ({key}): {repr(child)}\n"
        return s + ")"


class _HookHandle:
    def __init__(self, hooks, key):
        self._hooks, self._key = hooks, key

    def detach(self):
        self._hooks.pop(self._key, None)


class HybridBlock(Block):
    """Block that can be staged into a jit-compiled cached op.

    Subclasses implement ``hybrid_forward(F, x, *args, **params)`` — same
    contract as the reference (F is the op namespace; registered params are
    passed as kwargs).
    """

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._flags = {}
        self._cached_graph = {}
        self._cache_version = 0

    def _bump_cache_version(self):
        self._cache_version += 1
        self._cached_graph = {}

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  inline_limit=2, forward_bulk_size=None, backward_bulk_size=None):
        """Reference: HybridBlock.hybridize (flags map to CachedOp config;
        here jit owns memory planning so the flags are accepted no-ops)."""
        self._active = active
        self._flags = {"static_alloc": static_alloc, "static_shape": static_shape}
        self._cached_graph = {}
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape)

    def cast(self, dtype):
        self._cached_graph = {}
        super().cast(dtype)

    def infer_shape(self, *args):
        """Resolve deferred param shapes from input shapes.  Parametric leaf
        layers override this; containers resolve compositionally."""
        raise MXNetError(
            f"{type(self).__name__} has deferred-init parameters but does not "
            "implement infer_shape; give explicit in_units/in_channels or "
            "run one eager forward first")

    # -- eager path --------------------------------------------------------
    def _resolve_params(self, *args):
        kwargs = {}
        tc = _TRACE.ctx
        for name, p in self._reg_params.items():
            if tc is not None and p in tc.param_map:
                kwargs[name] = tc.param_map[p]
                continue
            try:
                kwargs[name] = p.data()
            except DeferredInitializationError:
                self.infer_shape(*args)
                p._finish_deferred_init()
                kwargs[name] = p.data()
        return kwargs

    def _update_running_state(self, param, new_value_nd):
        """Write a non-differentiable state update (BatchNorm moving stats).
        Traced: collected as an extra jit output; eager: written in place."""
        tc = _TRACE.ctx
        val = new_value_nd._get() if isinstance(new_value_nd, NDArray) else new_value_nd
        if tc is not None:
            tc.state_updates.append((param, val))
        else:
            with _ag.pause():
                param.data()._set(val)

    def forward(self, x, *args):
        if self._active and isinstance(x, NDArray) and _TRACE.ctx is None:
            return self._call_cached_op(x, *args)
        params = self._resolve_params(x, *args)
        return self.hybrid_forward(_F, x, *args, **params)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # -- cached (jit) path -------------------------------------------------
    def _call_cached_op(self, *args):
        """Reference: _call_cached_op -> CachedOp::Forward (SURVEY.md §4.2).
        Here the cached op is a jax.jit'd pure function."""
        import jax

        # remembered for export() so the symbol trace can re-run shape-true
        # (avals only — holding the arrays would pin the last batch on device)
        self._last_input_shapes = tuple(
            jax.ShapeDtypeStruct(tuple(a.shape), _np.dtype(a.dtype))
            for a in args if isinstance(a, NDArray))

        # deferred param shapes unresolved -> run the eager path once (it
        # settles them, recording normally); the next call builds the cache
        all_params = [p for _, p in sorted(self.collect_params().items())]
        if any(p._data is None for p in all_params):
            params = self._resolve_params(*args)
            return self.hybrid_forward(_F, *args, **params)

        in_vals = [a._get() if isinstance(a, NDArray) else a for a in args]
        from ..ndarray.ndarray import _AMP

        key = (tuple((tuple(v.shape), str(v.dtype)) for v in in_vals),
               _ag.is_training(), _ag.is_recording(), self._cache_version,
               _AMP["target"] if _AMP["on"] else None)
        entry = self._cached_graph.get(key)
        if entry is None:
            entry = self._build_cache(key, all_params, args)
        jitted, params_list, n_state = entry

        param_vals = [p.data()._get() for p in params_list]
        from .. import random as _rnd
        from jax import random as _jr

        rng_key = _rnd._next_key()

        flat_in = param_vals + in_vals
        if _ag.is_recording():
            def fn_for_tape(*flat):
                pv = list(flat[:len(param_vals)])
                iv = list(flat[len(param_vals):])
                return jitted(pv, rng_key, *iv)

            entries = [p.data()._ag_entry for p in params_list] + \
                      [(a._ag_entry if isinstance(a, NDArray) else None) for a in args]
            out_vals, out_entries, _ = _ag.record_op(fn_for_tape, flat_in, entries,
                                                     name=f"cached_op:{self.name}")
        else:
            out_vals = jitted(param_vals, rng_key, *in_vals)
            out_entries = None

        out_vals = list(out_vals)
        state_vals = out_vals[len(out_vals) - n_state:] if n_state else []
        real_vals = out_vals[:len(out_vals) - n_state] if n_state else out_vals

        # write state updates back (BatchNorm stats etc.)
        state_params = self._cached_state_params.get(key, [])
        with _ag.pause():
            for p, v in zip(state_params, state_vals):
                p.data()._set(v)

        ctx = args[0].context if isinstance(args[0], NDArray) else current_context()
        outs = []
        for i, v in enumerate(real_vals):
            o = NDArray._from_jax(v, ctx)
            if out_entries is not None:
                o._ag_entry = out_entries[i]
            outs.append(o)
        if self._cached_single.get(key, len(outs) == 1):
            return outs[0]
        return tuple(outs)

    def _build_cache(self, key, all_params, args):
        """Trace hybrid_forward once into a jit executable (reference:
        _build_cache / CachedOp construction, SURVEY.md §4.6)."""
        import time as _time

        import jax

        # telemetry compile tracer: a fresh build on a block that already
        # has cached entries is a retrace (new input signature / train
        # mode / AMP target) — the thing a retrace storm is made of
        _compile_t0 = _time.perf_counter()
        _compile_cause = "new_block" if not self._cached_graph \
            else "new_signature"
        params_list = all_params
        training = _ag.is_training()
        if not hasattr(self, "_cached_state_params"):
            self._cached_state_params = {}
            self._cached_single = {}

        state_params_box = []
        single_box = []
        block = self

        def fn(param_vals, rng_key, *input_vals):
            from .. import random as _rnd

            pmap = {}
            for p, v in zip(params_list, param_vals):
                nd = NDArray._from_jax(v, None)
                pmap[p] = nd
            tc = _TraceContext(pmap)
            prev = _TRACE.ctx
            _TRACE.ctx = tc
            _rnd._push_trace_key(rng_key)
            prev_rec = _ag.set_recording(False)
            try:
                nd_args = [NDArray._from_jax(v, None) for v in input_vals]
                out = block.forward(*nd_args)
            finally:
                _ag.set_recording(prev_rec)
                _rnd._pop_trace_key()
                _TRACE.ctx = prev
            if isinstance(out, NDArray):
                outs = [out._get()]
                single = True
            else:
                outs = [o._get() for o in out]
                single = False
            state_params = [p for p, _ in tc.state_updates]
            state_vals = [v for _, v in tc.state_updates]
            if not state_params_box:
                state_params_box.append(state_params)
                single_box.append(single)
            return tuple(outs + state_vals)

        jitted = jax.jit(fn, static_argnums=())
        # the jit's own abstract trace discovers state updates and output
        # arity, and the first call finds it cached: forward runs once
        in_vals = [a._get() if isinstance(a, NDArray) else a for a in args]
        param_vals = [p.data()._get() for p in params_list]
        from jax import random as _jr

        jitted.eval_shape(param_vals, _jr.PRNGKey(0), *in_vals)
        state_params = state_params_box[0]
        n_state = len(state_params)
        self._cached_state_params[key] = state_params
        self._cached_single[key] = single_box[0]
        entry = (jitted, params_list, n_state)
        self._cached_graph[key] = entry
        from .. import telemetry as _telemetry

        _telemetry.compile_event(
            "block", getattr(self, "name", type(self).__name__) or
            type(self).__name__,
            _time.perf_counter() - _compile_t0, _compile_cause)
        return entry

    def _trace_to_symbol(self, *args):
        """Trace ``forward`` with SymbolTracer proxies → (Symbol, arg_params,
        aux_params).  Reference: _get_graph building the Symbol from
        hybrid_forward (SURVEY.md §4.6); here imperative forward code runs
        unmodified against graph-building proxies."""
        import jax

        from ..ndarray import ndarray as _ndmod
        from ..symbol.symbol import SymbolTracer, _Node, Symbol

        plist = sorted(self._collect_params_with_prefix().items())
        param_map = {}
        tracers = {}
        for name, p in plist:
            d = p.data()
            aval = jax.ShapeDtypeStruct(d.shape, _np.dtype(d.dtype))
            node = _Node(None, name, {})
            param_map[p] = SymbolTracer((node, 0), aval)
            tracers[name] = param_map[p]

        in_tracers = []
        for i, a in enumerate(args):
            name = "data" if len(args) == 1 else f"data{i}"
            aval = jax.ShapeDtypeStruct(tuple(a.shape), _np.dtype(a.dtype))
            in_tracers.append(SymbolTracer((_Node(None, name, {}), 0), aval))

        tc = _TraceContext(param_map)
        prev = _TRACE.ctx
        _TRACE.ctx = tc
        prev_train = _ag.set_training(False)
        prev_rec = _ag.set_recording(False)
        _ndmod._SYMTRACE["on"] = True
        try:
            out = self.forward(*in_tracers)
        finally:
            _ndmod._SYMTRACE["on"] = False
            _ag.set_recording(prev_rec)
            _ag.set_training(prev_train)
            _TRACE.ctx = prev
        outs = out if isinstance(out, (list, tuple)) else [out]
        heads = [o._symhead for o in outs]
        sym = Symbol(heads)
        # classify by graph position (the symbol knows which vars feed
        # state-op aux slots), not by name suffix
        aux_names = set(sym.list_auxiliary_states())
        arg_params, aux_params = {}, {}
        for name, p in plist:
            if name in aux_names:
                aux_params[name] = p.data()
            else:
                arg_params[name] = p.data()
        return sym, arg_params, aux_params

    def export(self, path, epoch=0, *example_inputs, manifest=True):
        """Reference: HybridBlock.export → ``path-symbol.json`` +
        ``path-{epoch:04d}.params`` (deploy format, loadable by
        SymbolBlock.imports / Module.load_checkpoint).

        Also writes ``path-artifact.json`` — the serving manifest
        (input avals, AMP epoch, StableHLO IR per signature) consumed by
        ``mxnet_tpu.serving.load_artifact``, which reconstructs the
        block and AOT-warms every manifest signature so a server pays
        zero fresh traces in steady state (ISSUE 8; the Relay/TVM
        deployment-IR boundary).  ``manifest=False`` skips it (callers
        like ``serving.export_artifact`` that write a multi-signature
        manifest themselves)."""
        example = example_inputs or getattr(self, "_last_input_shapes", None)
        if not example:
            raise MXNetError(
                "export needs an input signature: call hybridize() and run a "
                "forward pass first, or pass example inputs — "
                "net.export(path, epoch, x) (reference raises the same way)")
        sym, arg_params, aux_params = self._trace_to_symbol(*example)
        from ..module.module import save_checkpoint as _save_ckpt

        _save_ckpt(path, epoch, sym, arg_params, aux_params)
        if manifest:
            from ..serving.artifact import write_manifest

            write_manifest(self, path, epoch=epoch, signatures=[example])


class SymbolBlock(HybridBlock):
    """Run a Symbol graph as a Gluon block (reference: gluon.SymbolBlock —
    python/mxnet/gluon/block.py:~1100, used to reload ``export``ed models).

    Execution interprets the graph with the registered jax op functions via
    ``ndarray.apply_fn``, so autograd works through it and ``hybridize``
    wraps it in one jit computation."""

    def __init__(self, outputs, inputs, params=None, prefix=None):
        super().__init__(prefix=prefix or "")
        from .. import symbol as _sym

        if isinstance(outputs, (list, tuple)):
            outputs = _sym.Group(outputs)
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        self._sym = outputs
        self._input_names = [s.name if hasattr(s, "name") else str(s)
                             for s in inputs]
        arg_names = outputs.list_arguments()
        aux_names = outputs.list_auxiliary_states()
        self._sym_aux_names = list(aux_names)
        self._sym_param_names = [n for n in arg_names
                                 if n not in self._input_names] + aux_names
        for n in self._sym_param_names:
            grad_req = "null" if n in aux_names else "write"
            self.params.get(n, grad_req=grad_req, allow_deferred_init=True)
        if params:
            for n, v in params.items():
                key = n.replace("arg:", "").replace("aux:", "")
                if key in self._sym_param_names:
                    self._set_symbol_param(key, v, None)

    def _set_symbol_param(self, key, value, ctx):
        p = self.params.get(key)
        p.shape = tuple(value.shape)
        p.initialize(ctx=ctx, force_reinit=False)
        p.set_data(value)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as _sym
        from ..ndarray.serialization import load as _load

        sym = _sym.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [_sym.var(n) for n in input_names]
        blk = SymbolBlock(sym, inputs)
        if param_file is not None:
            loaded = _load(param_file)
            for k, v in loaded.items():
                key = k.replace("arg:", "").replace("aux:", "")
                if key in blk._sym_param_names:
                    blk._set_symbol_param(key, v, ctx)
        return blk

    def _optimized_heads(self):
        """Graph-tier heads: the loaded symbol run through the pass
        pipeline once per cache version (serving's SymbolBlock path runs
        the optimized graph too).  Unoptimizable -> the raw heads."""
        from .. import graph as _graph

        ent = getattr(self, "_opt_heads_entry", None)
        if ent is not None and ent[0] == self._cache_version:
            return ent[1]
        try:
            sym = _graph.default_pipeline().run_symbol(
                self._sym, input_names=self._input_names)
            heads = sym._heads
        except Exception:
            _graph.record_fallback()
            heads = self._sym._heads
        self._opt_heads_entry = (self._cache_version, heads)
        return heads

    def forward(self, *args):
        from .. import random as _rnd
        from ..ndarray.ndarray import NDArray, apply_fn
        from ..symbol.symbol import evaluate

        heads = self._optimized_heads()
        pvals = []
        for n in self._sym_param_names:
            pvals.append(self.params.get(n).data())
        names = self._input_names + self._sym_param_names
        training = _ag.is_training()
        # during training forwards, thread aux-state updates (BatchNorm
        # moving stats) out of the evaluation and write them back into the
        # aux parameters — the reference's CachedOp mutates aux states
        # in-place (ADVICE r1: without this, fine-tuned SymbolBlocks served
        # stale imported running stats)
        aux_names = self._sym_aux_names
        collect = training and bool(aux_names)
        n_main = {}
        key = NDArray._from_jax(_rnd._next_key(), None)

        def pure(key_val, *vals):
            from jax import lax

            feed = dict(zip(names, vals))
            outs, state = evaluate(heads, feed, rng_key=key_val,
                                   training=training, collect_state=collect)
            res = list(outs)
            n_main["n"] = len(res)
            if collect:
                res += [lax.stop_gradient(state.get(n, feed[n]))
                        for n in aux_names]
            return tuple(res) if len(res) != 1 else res[0]

        out = apply_fn(pure, [key] + list(args) + pvals, name="symbol_block")
        if not collect:
            return out
        outs = out if isinstance(out, (list, tuple)) else [out]
        n = n_main["n"]
        main, aux_new = outs[:n], outs[n:]
        tc = _TRACE.ctx
        for nme, v in zip(aux_names, aux_new):
            p = self.params.get(nme)
            if tc is not None:
                # under a functionalize/jit trace the update rides out as an
                # extra jit output (state threading) — writing to .data()
                # here would only mutate the traced stand-in
                tc.state_updates.append((p, v._get()))
            else:
                with _ag.pause():
                    p.data()._set(v._get())
        return main[0] if n == 1 else list(main)
