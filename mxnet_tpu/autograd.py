"""Imperative autograd: tape recording + backward via per-op ``jax.vjp``.

Reference semantics: ``python/mxnet/autograd.py`` (record/pause/train_mode/
predict_mode scopes, backward, mark_variables) with the C++ tape in
``src/imperative/imperative.cc`` (Imperative::RecordOp builds AGInfo nodes;
Imperative::Backward applies the nnvm "Gradient" pass) — SURVEY.md §3.5, §4.2.

TPU-native design: instead of a graph-IR Gradient pass, every recorded op
captures a *pure function* plus its input values (jax arrays are immutable,
so snapshots are free) and its ``jax.vjp`` residuals at record time.
``backward()`` walks the tape in reverse topological order accumulating
cotangents.  This supports the imperative API (per-op backward, grad_req
write/add, retain_graph) that a whole-function ``jax.grad`` cannot express —
exactly the reason the reference keeps a tape beside its symbolic executor.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

import numpy as _np

from .base import MXNetError

__all__ = [
    "record",
    "pause",
    "train_mode",
    "predict_mode",
    "is_recording",
    "is_training",
    "set_recording",
    "set_training",
    "mark_variables",
    "backward",
    "grad",
    "Function",
]


class _State(threading.local):
    def __init__(self):
        self.recording = False
        self.training = False


_STATE = _State()

# ``NDArray._grad`` of a variable marked without a gradient buffer (the data
# of a Gluon parameter): the first that asks for the buffer makes it,
# ``NDArray.grad`` zeros and ``backward`` its cotangent
UNMADE = object()


def is_recording():
    return _STATE.recording


def is_training():
    return _STATE.training


def set_recording(flag):
    prev = _STATE.recording
    _STATE.recording = bool(flag)
    return prev


def set_training(flag):
    prev = _STATE.training
    _STATE.training = bool(flag)
    return prev


@contextmanager
def _scope(recording=None, training=None):
    prev_r, prev_t = _STATE.recording, _STATE.training
    if recording is not None:
        _STATE.recording = recording
    if training is not None:
        _STATE.training = training
    try:
        yield
    finally:
        _STATE.recording, _STATE.training = prev_r, prev_t


def record(train_mode=True):
    """``with autograd.record():`` — turn on tape recording (and train mode)."""
    return _scope(recording=True, training=train_mode)


def pause(train_mode=False):
    return _scope(recording=False, training=train_mode)


def train_mode():
    return _scope(training=True)


def predict_mode():
    return _scope(training=False)


# --------------------------------------------------------------------------
# Tape structures
# --------------------------------------------------------------------------
class Entry:
    """A differentiable value on the tape: either an op output (node, oidx)
    or a marked variable (node is None)."""

    __slots__ = ("node", "oidx", "variable", "grad_req", "shape", "dtype")

    def __init__(self, node=None, oidx=0, variable=None, grad_req="write",
                 shape=None, dtype=None):
        self.node = node
        self.oidx = oidx
        self.variable = variable  # the NDArray handle for marked variables
        self.grad_req = grad_req
        self.shape = shape
        self.dtype = dtype


class Node:
    """One recorded op: pure fn + input entries + vjp residuals.

    ``fn``/``in_vals`` are kept so the tape can be *replayed* as a pure jax
    function for ``grad(create_graph=True)`` (vjp-of-vjp — the reference's
    higher-order autograd, tests/python/unittest/test_higher_order_grad.py)."""

    __slots__ = ("vjp_fn", "in_entries", "out_entries", "out_avals", "name",
                 "multi", "fn", "in_vals")

    def __init__(self, vjp_fn, in_entries, out_avals, name="", multi=False,
                 fn=None, in_vals=None):
        self.vjp_fn = vjp_fn
        self.in_entries = in_entries  # list[Entry|None], aligned with vjp cotangent outputs
        self.out_entries = []         # filled by record_op
        self.out_avals = out_avals    # list[(shape, dtype)]
        self.name = name
        self.multi = multi            # original fn returned a tuple
        self.fn = fn                  # pure forward fn (attrs closed over)
        self.in_vals = in_vals        # input snapshot for replay


def record_op(fn, in_vals, in_entries, name=""):
    """Record one op execution. Returns (out_vals, out_entries).

    ``fn`` must be a pure function of ``*in_vals`` (attrs already closed
    over).  Called only when recording AND at least one input is on the tape.
    Reference: Imperative::RecordOp (src/imperative/imperative.cc).
    """
    import jax

    out_vals, vjp_fn = jax.vjp(fn, *in_vals)
    multi = isinstance(out_vals, (tuple, list))
    outs = list(out_vals) if multi else [out_vals]
    node = Node(vjp_fn, list(in_entries),
                [(o.shape, o.dtype) for o in outs], name=name, multi=multi,
                fn=fn, in_vals=list(in_vals))
    node.out_entries = [Entry(node=node, oidx=i, shape=o.shape, dtype=o.dtype)
                        for i, o in enumerate(outs)]
    return out_vals, node.out_entries, multi


def mark_variables(variables, gradients, grad_reqs="write"):
    """Attach gradient buffers to NDArrays (reference: MXAutogradMarkVariables)."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for var, g, req in zip(variables, gradients, grad_reqs):
        var._mark_variable(g, req)


# --------------------------------------------------------------------------
# Backward
# --------------------------------------------------------------------------
def _topo_nodes(head_entries):
    """Reverse-topological order of nodes reachable from the heads."""
    order, state = [], {}  # state: 0 visiting, 1 done

    def visit(node):
        stack = [(node, False)]
        while stack:
            n, processed = stack.pop()
            if processed:
                state[id(n)] = 1
                order.append(n)
                continue
            st = state.get(id(n))
            if st is not None:
                continue
            state[id(n)] = 0
            stack.append((n, True))
            for e in n.in_entries:
                if e is not None and e.node is not None and state.get(id(e.node)) is None:
                    stack.append((e.node, False))

    for e in head_entries:
        if e is not None and e.node is not None and state.get(id(e.node)) is None:
            visit(e.node)
    order.reverse()
    return order


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """Run backward from ``heads`` (list of NDArray), accumulating into the
    ``.grad`` buffers of marked variables.

    Reference: Imperative::Backward (src/imperative/imperative.cc, SURVEY.md
    §4.2): builds grad graph from tape, executes with inplace-addto.
    Here: reverse-topo walk calling each node's stored ``vjp_fn``.
    """
    import jax.numpy as jnp

    if not isinstance(heads, (list, tuple)):
        heads = [heads]
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif not isinstance(head_grads, (list, tuple)):
        head_grads = [head_grads]

    cot = {}  # id(Entry) -> cotangent jax array
    written = set()  # variables written THIS backward (write-req semantics:
    #                  each backward overwrites; contributions within one
    #                  backward accumulate — matching the reference)

    def add_cot(entry, val):
        k = id(entry)
        if k in cot:
            cot[k] = cot[k] + val
        else:
            cot[k] = val

    head_entries = []
    for h, hg in zip(heads, head_grads):
        e = h._ag_entry
        if e is None:
            raise MXNetError(
                "cannot differentiate a head that was not computed under "
                "autograd.record() from marked variables"
            )
        head_entries.append(e)
        if hg is None:
            g = jnp.ones(h.shape, dtype=h.dtype)
        else:
            g = hg._get() if hasattr(hg, "_get") else jnp.asarray(hg)
        add_cot(e, g)

    for node in _topo_nodes(head_entries):
        outs = []
        have_any = False
        for i, (shape, dtype) in enumerate(node.out_avals):
            e = node.out_entries[i]
            c = cot.pop(id(e), None)
            if c is None:
                c = jnp.zeros(shape, dtype=dtype)
            else:
                have_any = True
            outs.append(c)
        if not have_any:
            continue
        if node.vjp_fn is None:
            raise MXNetError(
                f"backward through node {node.name!r} a second time without "
                "retain_graph=True"
            )
        cotan_in = node.vjp_fn(tuple(outs) if node.multi else outs[0])
        if not retain_graph:
            # free residuals AND the replay snapshot — both pin forward
            # activations in device memory
            node.vjp_fn = None
            node.fn = None
            node.in_vals = None
        for e, c in zip(node.in_entries, cotan_in):
            if e is None or c is None:
                continue
            if e.variable is not None:
                _accum_grad(e, c, written)
            else:
                add_cot(e, c)

    # cotangents that landed directly on variable heads (identity case)
    for e in head_entries:
        if e.variable is not None and id(e) in cot:
            _accum_grad(e, cot.pop(id(e)), written)


def _accum_grad(entry, c, written):
    var = entry.variable
    req = entry.grad_req
    if req == "null" or var is None:
        return
    grad_nd = var._grad
    if grad_nd is None:
        return
    if grad_nd is UNMADE:
        # c itself where "write" would store it, zeros plus c under "add"
        import jax.numpy as jnp

        var._make_grad(jnp.zeros((), var.dtype) + c if req == "add"
                       else c.astype(var.dtype))
        written.add(id(var))
        return
    if req == "add":
        grad_nd._set(grad_nd._get() + c)
    elif id(var) in written:  # multiple uses within ONE backward accumulate
        grad_nd._set(grad_nd._get() + c)
    else:  # 'write': first contribution of this backward overwrites
        grad_nd._set(c.astype(grad_nd.dtype) if c.dtype != grad_nd.dtype else c)
        written.add(id(var))


def _replay_fn(head_entries, var_entries, head_vals):
    """Build a pure jax function var_vals -> head_vals by replaying the tape
    (the functional rebuild of the recorded graph that makes the gradient
    itself re-differentiable — reference: the nnvm Gradient pass emits a
    symbolic grad graph that can be differentiated again)."""
    nodes = list(reversed(_topo_nodes(head_entries)))  # forward topo order
    var_ids = [id(e) for e in var_entries]

    def replay(*var_vals):
        val_of = dict(zip(var_ids, var_vals))
        for node in nodes:
            ins = []
            for e, stored in zip(node.in_entries, node.in_vals):
                if e is not None and id(e) in val_of:
                    ins.append(val_of[id(e)])
                else:
                    ins.append(stored)
            if node.fn is None:
                raise MXNetError(
                    f"tape for node {node.name!r} was freed; pass "
                    "retain_graph=True on the earlier backward")
            outs = node.fn(*ins)
            outs_l = list(outs) if node.multi else [outs]
            for oe, ov in zip(node.out_entries, outs_l):
                val_of[id(oe)] = ov
        return tuple(
            val_of.get(id(he), hv) for he, hv in zip(head_entries, head_vals))

    return replay


def grad(heads, variables, head_grads=None, retain_graph=None, create_graph=False,
         train_mode=True):
    """Functional gradient: returns grads of heads w.r.t. variables without
    touching ``.grad`` buffers (reference: mx.autograd.grad).

    ``create_graph=True`` returns gradients that are themselves on the tape,
    enabling grad-of-grad (reference: test_higher_order_grad.py): the tape is
    replayed as a pure jax function and its vjp application is recorded as
    one taped op, so a further backward() differentiates through it
    (vjp-of-vjp).
    """
    if not isinstance(variables, (list, tuple)):
        variables = [variables]
    if create_graph:
        return _grad_create_graph(heads, variables, head_grads)
    from .ndarray import ndarray as _ndm
    saved = [(v._grad, v._ag_entry) for v in variables]
    try:
        zeros = [_ndm.NDArray._from_jax(_zeros_like(v._get()), v.context) for v in variables]
        mark_variables(list(variables), zeros)
        backward(heads, head_grads, retain_graph=bool(retain_graph), train_mode=train_mode)
        return [v._grad for v in variables]
    finally:
        for v, (g, e) in zip(variables, saved):
            v._grad, v._ag_entry = g, e


def _grad_create_graph(heads, variables, head_grads=None):
    import jax
    import jax.numpy as jnp

    from .ndarray import ndarray as _ndm

    if not isinstance(heads, (list, tuple)):
        heads = [heads]
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif not isinstance(head_grads, (list, tuple)):
        head_grads = [head_grads]

    head_entries = []
    head_vals = []
    for h in heads:
        if h._ag_entry is None:
            raise MXNetError(
                "cannot differentiate a head that was not computed under "
                "autograd.record() from marked variables")
        head_entries.append(h._ag_entry)
        head_vals.append(h._get())
    var_entries = []
    for v in variables:
        if v._ag_entry is None:
            raise MXNetError(
                f"variable {v!r} is not on the tape (call .attach_grad() "
                "inside or before the record scope)")
        var_entries.append(v._ag_entry)

    replay = _replay_fn(head_entries, var_entries, head_vals)
    hg_vals = [
        jnp.ones(h.shape, dtype=h.dtype) if hg is None
        else (hg._get() if hasattr(hg, "_get") else jnp.asarray(hg))
        for h, hg in zip(heads, head_grads)]

    def grad_fn(*var_vals):
        _, vjp = jax.vjp(replay, *var_vals)
        return vjp(tuple(hg_vals))

    var_vals = [v._get() for v in variables]
    if is_recording():
        out_vals, out_entries, _ = record_op(
            grad_fn, var_vals, var_entries, name="_grad_create_graph")
    else:
        out_vals = grad_fn(*var_vals)
        out_entries = [None] * len(variables)
    results = []
    for v, g, e in zip(variables, out_vals, out_entries):
        nd = _ndm.NDArray._from_jax(g, v.context)
        nd._ag_entry = e
        results.append(nd)
    return results


def _zeros_like(x):
    import jax.numpy as jnp

    return jnp.zeros(x.shape, x.dtype)


# --------------------------------------------------------------------------
# user-defined differentiable functions
# --------------------------------------------------------------------------
def record_callback_node(in_entries, out_nds, backward_cb, name, ctx=None):
    """Attach a tape node to ``out_nds`` whose vjp is a host callback.

    Shared wiring for CustomOp and Function: ``backward_cb`` receives the
    output-gradient NDArrays and returns per-input cotangents
    (NDArray / jax array / None), aligned with ``in_entries``."""
    from .ndarray.ndarray import NDArray

    def vjp_fn(cotangents):
        import jax.numpy as jnp

        cots = cotangents if isinstance(cotangents, tuple) else (cotangents,)
        grads = backward_cb([NDArray._from_jax(jnp.asarray(c), ctx)
                             for c in cots])
        return tuple(
            None if g is None else
            (g._get() if hasattr(g, "_get") else jnp.asarray(g))
            for g in grads)

    avals = [(tuple(o.shape), _np.dtype(str(o.dtype))) for o in out_nds]
    node = Node(vjp_fn, list(in_entries), avals, name=name,
                multi=len(out_nds) > 1)
    node.out_entries = [Entry(node=node, oidx=i, shape=s, dtype=d)
                        for i, (s, d) in enumerate(avals)]
    for o, e in zip(out_nds, node.out_entries):
        o._ag_entry = e
    return node


class Function:
    """Customized differentiation (reference: ``mx.autograd.Function``,
    python/mxnet/autograd.py): subclass, implement ``forward`` and
    ``backward`` over NDArrays, stash residuals with ``save_for_backward``
    (or plain attributes on ``self``), call the instance like a function.

    Works eagerly (tape node whose vjp calls the user's ``backward`` —
    full host-Python freedom, matching reference callback semantics) and
    inside ``hybridize()``/jit traces (staged as a ``jax.custom_vjp``; user
    code must then be trace-compatible NDArray math)."""

    def __init__(self):
        self._saved = ()

    # -- user surface ------------------------------------------------------
    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def save_for_backward(self, *tensors):
        self._saved = tensors

    @property
    def saved_tensors(self):
        return self._saved

    # -- invocation --------------------------------------------------------
    def __call__(self, *inputs):
        import jax

        from .ndarray.ndarray import NDArray

        nd_in = [x if isinstance(x, NDArray)
                 else NDArray._from_jax(_as_jax(x), None)
                 for x in inputs]
        in_vals = [a._get() for a in nd_in]
        if any(isinstance(v, jax.core.Tracer) for v in in_vals):
            return self._call_traced(nd_in)
        return self._call_eager(nd_in)

    def _call_eager(self, nd_in):
        from .ndarray.ndarray import NDArray

        ctx = nd_in[0].context if nd_in else None
        with pause():
            out = self.forward(*nd_in)
        multi = isinstance(out, (tuple, list))
        outs = list(out) if multi else [out]

        if is_recording() and any(a._ag_entry is not None for a in nd_in):
            fname = type(self).__name__

            def backward_cb(out_grad_nds):
                with pause():
                    gin = self.backward(*out_grad_nds)
                gin = gin if isinstance(gin, (tuple, list)) else (gin,)
                if len(gin) != len(nd_in):
                    raise MXNetError(
                        f"{fname}.backward returned {len(gin)} grads for "
                        f"{len(nd_in)} inputs")
                return gin

            record_callback_node([a._ag_entry for a in nd_in], outs,
                                 backward_cb, f"Function:{fname}", ctx)
        return tuple(outs) if multi else outs[0]

    def _call_traced(self, nd_in):
        import jax

        from .ndarray.ndarray import NDArray

        ctx = nd_in[0].context if nd_in else None
        func = self
        multi_box = []

        @jax.custom_vjp
        def fn(*vals):
            return _fwd(*vals)[0]

        def _fwd(*vals):
            ins = [NDArray._from_jax(v, ctx) for v in vals]
            with pause():
                out = func.forward(*ins)
            multi = isinstance(out, (tuple, list))
            if not multi_box:
                multi_box.append(multi)
            outs = list(out) if multi else [out]
            saved = tuple(t._get() for t in func._saved)
            return tuple(o._get() for o in outs), (vals, saved)

        def _bwd(res, cots):
            import jax.numpy as jnp

            in_vals, saved = res
            func._saved = tuple(NDArray._from_jax(s, ctx) for s in saved)
            grad_nds = [NDArray._from_jax(c, ctx) for c in cots]
            with pause():
                gin = func.backward(*grad_nds)
            gin = gin if isinstance(gin, (tuple, list)) else (gin,)
            return tuple(
                jnp.zeros(v.shape, v.dtype) if g is None else
                (g._get() if hasattr(g, "_get") else jnp.asarray(g))
                for g, v in zip(gin, in_vals))

        fn.defvjp(_fwd, _bwd)
        out_vals = fn(*[a._get() for a in nd_in])
        outs = [NDArray._from_jax(v, ctx) for v in out_vals]
        return tuple(outs) if multi_box and multi_box[0] else outs[0]


def _as_jax(x):
    import jax.numpy as jnp

    return jnp.asarray(x)
