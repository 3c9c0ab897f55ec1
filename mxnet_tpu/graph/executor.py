"""Replay an optimized Graph as a pure jax function.

``make_block_fn(graph)`` returns a function of the cached op's contract

    fn(param_vals, rng_key, *input_vals) -> tuple(outputs + state_vals)

The replay mirrors ``ndarray.invoke`` exactly — same op fns, same attr
filtering, the same AMP cast wrap per op, and RNG keys derived with the
same ``fold_in(base, counter)`` scheme, numbered in node order — so a
pipeline with no enabled passes computes what evaluating the Symbol
would (the bit-parity floor every pass builds on; the pass tests replay
a graph before and after a pass through it).
"""
from __future__ import annotations

import functools

from ..base import MXNetError

__all__ = ["make_block_fn"]


def make_block_fn(graph):
    """Compile-free closure over ``graph``; safe to ``jax.jit``."""
    from ..ops.registry import get_op
    from ..symbol.symbol import _clean_attrs

    steps = []           # (node_id, od, attrs, input_edges)
    for nid, node in enumerate(graph.nodes):
        if node.op is None:
            continue
        od = get_op(node.op)     # raises MXNetError for unknown ops
        steps.append((nid, od, _clean_attrs(node.attrs),
                      tuple(node.inputs)))
    param_ids = [nid for nid, _ in graph.params]
    input_ids = list(graph.inputs)
    out_edges = list(graph.outputs) + [e for _, e in graph.state]
    consts = {nid: n.value for nid, n in enumerate(graph.nodes)
              if n.is_const}

    def fn(param_vals, rng_key, *input_vals):
        import jax
        import jax.numpy as jnp

        from ..ndarray.ndarray import _AMP, _call_with_attrs

        if len(param_vals) != len(param_ids) or \
                len(input_vals) != len(input_ids):
            raise MXNetError(
                f"graph executor: expected {len(param_ids)} params + "
                f"{len(input_ids)} inputs, got {len(param_vals)} + "
                f"{len(input_vals)}")
        vals = {}
        for nid, v in zip(param_ids, param_vals):
            vals[(nid, 0)] = v
        for nid, v in zip(input_ids, input_vals):
            vals[(nid, 0)] = v
        for nid, v in consts.items():
            vals[(nid, 0)] = jnp.asarray(v)
        amp_wrap = _AMP["wrap"] if _AMP["on"] else None
        rng_ops = 0
        for nid, od, attrs, in_edges in steps:
            f = functools.partial(_call_with_attrs, od.fn, attrs)
            if amp_wrap is not None:
                f = amp_wrap(od, f)
            args = [vals[e] for e in in_edges]
            if od.needs_rng:
                rng_ops += 1      # keys numbered in node order
                args = [jax.random.fold_in(rng_key, rng_ops)] + args
            out = f(*args)
            outs = out if isinstance(out, (tuple, list)) else (out,)
            for i, v in enumerate(outs):
                vals[(nid, i)] = v
        return tuple(vals[e] for e in out_edges)

    return fn
