"""Functionalize a Gluon block into a pure (params, rng, *inputs) -> outputs fn.

The reference stages Gluon models through CachedOp (SURVEY.md §4.6); here the
same trace machinery (gluon.block._TraceContext) yields a *pure pytree
function* suitable for jax transforms: jit, grad, shard_map, pjit sharding.
This is the bridge between the imperative Gluon surface and the SPMD training
paths in parallel/ — the TPU-native equivalent of handing the NNVM graph to
the GraphExecutor.
"""
from __future__ import annotations

from collections import OrderedDict

__all__ = ["functionalize"]


def functionalize(net, train_mode=False, with_state=False):
    """Return ``(apply_fn, params)`` for an initialized Gluon block.

    ``params`` is an OrderedDict name -> jax.Array (the current values).
    ``apply_fn(params_dict, rng_key, *input_arrays)`` is pure and
    jax-traceable.

    with_state=False: running-state updates (BatchNorm moving stats) are
    dropped from the trace (XLA DCEs their computation).
    with_state=True: ``apply_fn`` returns ``(outputs, state_dict)`` where
    state_dict maps the state parameter's name to its new value — thread it
    back into ``params`` between steps to keep moving stats live (the
    functional analog of the reference's stateful FCompute).
    """
    from ..gluon.block import _TRACE, _TraceContext
    from ..gluon.parameter import DeferredInitializationError
    from ..ndarray.ndarray import NDArray
    from .. import autograd as _ag
    from .. import random as _rnd

    plist = [(name, p) for name, p in sorted(net.collect_params().items())]
    try:
        params = OrderedDict((name, p.data()._get()) for name, p in plist)
    except DeferredInitializationError as e:
        raise DeferredInitializationError(
            str(e) + " — run one eager forward (net(x)) before "
            "functionalize() so deferred shapes are resolved") from e
    param_objs = [p for _, p in plist]
    names = [name for name, _ in plist]
    name_of = {id(p): name for name, p in plist}

    def apply_fn(params_dict, rng_key, *input_vals):
        pmap = {}
        for name, pobj in zip(names, param_objs):
            pmap[pobj] = NDArray._from_jax(params_dict[name], None)
        tc = _TraceContext(pmap)
        prev = _TRACE.ctx
        _TRACE.ctx = tc
        _rnd._push_trace_key(rng_key)
        prev_rec = _ag.set_recording(False)
        prev_train = _ag.set_training(train_mode)
        try:
            nd_args = [NDArray._from_jax(v, None) for v in input_vals]
            out = net.forward(*nd_args)
        finally:
            _ag.set_training(prev_train)
            _ag.set_recording(prev_rec)
            _rnd._pop_trace_key()
            _TRACE.ctx = prev
        if isinstance(out, NDArray):
            out = out._get()
        elif isinstance(out, (list, tuple)):
            out = tuple(o._get() if isinstance(o, NDArray) else o for o in out)
        if not with_state:
            return out
        state = OrderedDict(
            (name_of[id(p)], v) for p, v in tc.state_updates if id(p) in name_of)
        return out, state

    return apply_fn, params
