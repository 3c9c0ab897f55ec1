"""Typed graph IR for the compiler tier (ISSUE 11; TVM/Relay playbook).

A :class:`Graph` is the explicit, pass-amenable form of one traced
HybridBlock computation (or one ``mx.sym`` graph): nodes are registered
ops with attrs, edges are data dependencies ``(node_id, out_index)``,
and the graph-level metadata marks which variables are parameters,
which are data inputs, and which edges feed running-state write-backs
(BatchNorm moving stats).  Node order IS execution order — the trace
records creation order, and the executor replays it — so RNG-consuming
ops draw the same fold_in keys as the imperative jit path (the
bit-parity contract every pass must preserve).

Passes are pure ``Graph -> Graph`` functions (MXT070-enforced): they
never mutate the input graph's nodes or attrs — :meth:`Graph.copy`
gives a fresh, freely mutable twin.
"""
from __future__ import annotations

import hashlib

import numpy as _np

from ..base import MXNetError

__all__ = ["Node", "Graph"]


class Node:
    """One graph node: an op application, a variable (op=None, value=None)
    or an embedded constant (op=None, value=ndarray).

    ``inputs`` are ``(node_id, out_index)`` edges into earlier nodes.
    ``avals`` is the per-output ``(shape, dtype_str)`` tuple where whoever
    built the graph knew it (None when built from a shape-oblivious
    Symbol).
    """

    __slots__ = ("op", "name", "attrs", "inputs", "nout", "value", "avals")

    def __init__(self, op, name, attrs=None, inputs=(), nout=1, value=None,
                 avals=None):
        self.op = op
        self.name = name
        self.attrs = dict(attrs or {})
        self.inputs = list(inputs)
        self.nout = nout
        self.value = value
        self.avals = avals

    @property
    def is_var(self):
        return self.op is None and self.value is None

    @property
    def is_const(self):
        return self.op is None and self.value is not None

    def clone(self):
        return Node(self.op, self.name, dict(self.attrs), list(self.inputs),
                    self.nout, self.value, self.avals)

    def __repr__(self):
        kind = self.op or ("const" if self.is_const else "var")
        return f"<Node {self.name} {kind} <-{self.inputs}>"


class Graph:
    """The typed op graph one :class:`PassPipeline` run transforms.

    - ``nodes``: execution-ordered node list (ids are list positions)
    - ``inputs``: node ids of the data-input variables, in call order
    - ``params``: ``(node_id, param_name)`` in positional binding order
    - ``outputs``: the real output edges
    - ``state``: ``(param_name, edge)`` running-state write-backs,
      appended after the outputs by the executor
    - ``single``: the block returned one array (not a tuple)
    """

    __slots__ = ("nodes", "inputs", "params", "outputs", "state", "single")

    def __init__(self, nodes=None, inputs=None, params=None, outputs=None,
                 state=None, single=True):
        self.nodes = list(nodes or [])
        self.inputs = list(inputs or [])
        self.params = list(params or [])
        self.outputs = list(outputs or [])
        self.state = list(state or [])
        self.single = single

    # -- structure ---------------------------------------------------------
    def copy(self):
        """Deep-copy: fresh Node objects, same ids/edges.  Passes mutate
        the copy, never their input (the MXT070 purity contract)."""
        g = Graph([n.clone() for n in self.nodes], list(self.inputs),
                  list(self.params), list(self.outputs),
                  [(k, e) for k, e in self.state], self.single)
        return g

    @property
    def n_ops(self):
        return sum(1 for n in self.nodes if n.op is not None)

    def consumer_counts(self):
        """node_id -> number of consuming edges (heads count once each)."""
        counts = {}
        for n in self.nodes:
            for nid, _ in n.inputs:
                counts[nid] = counts.get(nid, 0) + 1
        for nid, _ in self.outputs:
            counts[nid] = counts.get(nid, 0) + 1
        for _, (nid, _) in self.state:
            counts[nid] = counts.get(nid, 0) + 1
        return counts

    def live_ids(self):
        """Ids reachable from the output/state heads, plus every declared
        input/param variable (the executor's signature is positional, so
        unused inputs must survive DCE)."""
        live = set(self.inputs) | {nid for nid, _ in self.params}
        stack = [nid for nid, _ in self.outputs]
        stack += [nid for _, (nid, _) in self.state]
        while stack:
            nid = stack.pop()
            if nid in live:
                continue
            live.add(nid)
            stack.extend(i for i, _ in self.nodes[nid].inputs)
        return live

    def compact(self, keep_ids):
        """New Graph with only ``keep_ids`` nodes (order preserved), edges
        and heads remapped.  Raises if a head or kept edge would dangle."""
        remap = {}
        nodes = []
        for nid, n in enumerate(self.nodes):
            if nid in keep_ids:
                remap[nid] = len(nodes)
                nodes.append(n.clone())
        for n in nodes:
            n.inputs = [(remap[i], idx) for i, idx in n.inputs]
        return Graph(
            nodes, [remap[i] for i in self.inputs],
            [(remap[i], nm) for i, nm in self.params],
            [(remap[i], idx) for i, idx in self.outputs],
            [(nm, (remap[i], idx)) for nm, (i, idx) in self.state],
            self.single)

    def validate(self):
        """Structural invariants: edges point to earlier nodes (execution
        order is a topological order), heads are in range, declared
        input/param ids are variables."""
        for nid, n in enumerate(self.nodes):
            for i, idx in n.inputs:
                if not 0 <= i < nid:
                    raise MXNetError(
                        f"graph node {n.name} (id {nid}) consumes id {i}: "
                        "edges must point to earlier nodes")
                if not 0 <= idx < self.nodes[i].nout:
                    raise MXNetError(
                        f"graph node {n.name} consumes out {idx} of "
                        f"{self.nodes[i].name} (nout {self.nodes[i].nout})")
        heads = list(self.outputs) + [e for _, e in self.state]
        for i, idx in heads:
            if not 0 <= i < len(self.nodes):
                raise MXNetError(f"graph head id {i} out of range")
        for i in self.inputs:
            if not self.nodes[i].is_var:
                raise MXNetError(f"graph input id {i} is not a variable")
        for i, name in self.params:
            if not self.nodes[i].is_var:
                raise MXNetError(f"graph param {name!r} is not a variable")
        return self

    def signature(self):
        """Canonical structural digest — equal graphs (same ops, attrs,
        wiring, heads) hash equal across processes; used by the
        idempotence tests and the CI smoke's cross-process pin."""
        h = hashlib.sha256()
        for n in self.nodes:
            # fused ops carry a process-local counter name; their stable
            # identity is the structural plan digest stamped at fusion
            op_key = ("__fused__", n.attrs["__fused_sig__"]) \
                if "__fused_sig__" in n.attrs else n.op
            h.update(repr((op_key, n.name if n.is_var else None,
                           sorted((k, repr(v)) for k, v in n.attrs.items()
                                  if not k.startswith("__")),
                           n.inputs, n.nout,
                           None if n.value is None else
                           (n.value.shape, str(n.value.dtype),
                            _np.asarray(n.value).tobytes()))).encode())
        h.update(repr((self.inputs, self.params, self.outputs, self.state,
                       self.single)).encode())
        return h.hexdigest()

    def fused_op_count(self):
        """Nodes produced by the fusion pass (``__fused_plan__`` attr)."""
        return sum(1 for n in self.nodes if "__fused_plan__" in n.attrs)

    # -- symbol interop ----------------------------------------------------
    @classmethod
    def from_symbol(cls, sym, input_names=None):
        """Build from an ``mx.sym`` Symbol.  Variables named in
        ``input_names`` become data inputs; every other variable is
        marked as a parameter (positional order = topo order, which is
        how the subgraph shim and tests bind them)."""
        from ..symbol.symbol import _topo

        input_names = list(input_names or [])
        snodes = _topo(sym._heads)
        nid = {id(n): i for i, n in enumerate(snodes)}
        nodes, inputs, params = [], [], []
        for n in snodes:
            node = Node(n.op, n.name, dict(n.attrs),
                        [(nid[id(i)], idx) for i, idx in n.inputs],
                        n.nout, n.value)
            nodes.append(node)
            if node.is_var:
                if n.name in input_names:
                    inputs.append(nid[id(n)])
                else:
                    params.append((nid[id(n)], n.name))
        outputs = [(nid[id(n)], idx) for n, idx in sym._heads]
        g = cls(nodes, inputs, params, outputs, [], len(outputs) == 1)
        return g.validate()

    def to_symbol(self):
        """Convert back to an ``mx.sym`` Symbol (outputs only — state
        edges are an executor concern, not part of the user graph)."""
        from ..symbol.symbol import Symbol, _Node

        snodes = []
        for n in self.nodes:
            snodes.append(_Node(n.op, n.name, dict(n.attrs),
                                [(snodes[i], idx) for i, idx in n.inputs],
                                n.nout, n.value))
        return Symbol([(snodes[i], idx) for i, idx in self.outputs])
