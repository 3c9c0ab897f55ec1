"""Pass manager: registry, selection knobs, ordering, fixed-point.

``PassPipeline`` runs an ordered list of registered passes over a
:class:`Graph`, optionally iterating the sweep to a fixed point
(structure digest stable).  Every pass run is measured — a
``kind="graph_pass"`` compile event with duration and nodes
before/after — so pipeline wins are read off telemetry, not asserted.

Knobs (env.py / README "Graph compiler"):

- ``MXNET_GRAPH_PASSES``: comma-separated pass selection.  Plain names
  replace the default list; ``-name`` entries subtract from it.
- ``MXNET_GRAPH_FUSE_CAP``: max ops per fused elementwise chain
  (default 16; < 2 disables fusion).
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict

from .. import env as _env
from ..base import MXNetError

__all__ = ["graph_pass", "list_passes", "PassPipeline", "default_pipeline",
           "selected_pass_names", "DEFAULT_PASSES", "stats_snapshot",
           "reset_stats", "record_fallback"]

PASS_REGISTRY: "OrderedDict[str, object]" = OrderedDict()

# default order: fold + CSE shrink the graph, the AMP pass canonicalizes
# casts (so a second CSE round — via fixed point — merges the hoisted
# ones), fusion collapses the surviving chains, DCE sweeps the husks
DEFAULT_PASSES = ("fold_constants", "eliminate_common_subexpr",
                  "place_amp_casts", "fuse_elemwise_chains",
                  "eliminate_dead_nodes")


def graph_pass(name, default=True):
    """Decorator registering a pure ``Graph -> Graph`` pass under
    ``name``.  Every pass a :class:`PassPipeline` can reach MUST be
    registered (MXT071) — anonymous callables don't ride the pipeline."""

    def _do(fn):
        if name in PASS_REGISTRY and PASS_REGISTRY[name] is not fn:
            raise MXNetError(f"graph pass {name!r} already registered")
        PASS_REGISTRY[name] = fn
        fn.graph_pass_name = name
        fn.graph_pass_default = bool(default)
        return fn

    return _do


def _ensure_builtins():
    from . import passes  # noqa: F401  (import registers the builtins)


def list_passes():
    """Registered pass names, registration order."""
    _ensure_builtins()
    return list(PASS_REGISTRY)


def selected_pass_names():
    """Resolve ``MXNET_GRAPH_PASSES`` against the default list."""
    _ensure_builtins()
    spec = (_env.graph_passes() or "").strip()
    if not spec:
        return list(DEFAULT_PASSES)
    removed = {p[1:].strip() for p in spec.split(",")
               if p.strip().startswith("-")}
    picked = [p.strip() for p in spec.split(",")
              if p.strip() and not p.strip().startswith("-")]
    names = picked if picked else list(DEFAULT_PASSES)
    names = [n for n in names if n not in removed]
    unknown = [n for n in names if n not in PASS_REGISTRY]
    if unknown:
        raise MXNetError(
            f"MXNET_GRAPH_PASSES names unregistered passes {unknown}; "
            f"registered: {list(PASS_REGISTRY)}")
    return names


# --------------------------------------------------------------------------
# stats (snapshot()'s "graph" section; bench extra.graph reads this too)
# --------------------------------------------------------------------------
_SLOCK = threading.Lock()
_STATS = {
    "pipeline_runs": 0,
    "fallbacks": 0,
    "fused_ops_created": 0,
    "passes": {},       # name -> {runs, nodes_in, nodes_out, seconds}
    "last_run": None,   # [{pass, nodes_before, nodes_after, seconds}]
}


def _record_pass(name, before, after, dt):
    with _SLOCK:
        rec = _STATS["passes"].setdefault(
            name, {"runs": 0, "nodes_in": 0, "nodes_out": 0, "seconds": 0.0})
        rec["runs"] += 1
        rec["nodes_in"] += before
        rec["nodes_out"] += after
        rec["seconds"] += dt


def record_fallback():
    """A consumer handed its Symbol to the pipeline and kept the raw one
    (counted so a pipeline that silently never runs is visible in the
    snapshot)."""
    with _SLOCK:
        _STATS["fallbacks"] += 1


def stats_snapshot():
    with _SLOCK:
        return {
            "pipeline_runs": _STATS["pipeline_runs"],
            "fallbacks": _STATS["fallbacks"],
            "fused_ops_created": _STATS["fused_ops_created"],
            "passes": {k: dict(v) for k, v in _STATS["passes"].items()},
            "last_run": [dict(r) for r in _STATS["last_run"]]
            if _STATS["last_run"] else None,
        }


def reset_stats():
    with _SLOCK:
        _STATS["pipeline_runs"] = 0
        _STATS["fallbacks"] = 0
        _STATS["fused_ops_created"] = 0
        _STATS["passes"].clear()
        _STATS["last_run"] = None


# --------------------------------------------------------------------------
class PassPipeline:
    """An ordered, knob-selectable pass schedule.

    ``passes``: registered pass names (strings).  ``fixed_point=True``
    repeats the sweep until the structure digest stabilizes (bounded by
    ``max_iters``) — fusion after cast-hoisting after CSE converges in
    2 sweeps on real graphs.
    """

    def __init__(self, passes=None, fixed_point=True, max_iters=3):
        _ensure_builtins()
        names = list(passes) if passes is not None else \
            selected_pass_names()
        for n in names:
            if n not in PASS_REGISTRY:
                raise MXNetError(
                    f"unknown graph pass {n!r}; registered: "
                    f"{list(PASS_REGISTRY)}")
        self.pass_names = names
        self.fixed_point = bool(fixed_point)
        self.max_iters = max(1, int(max_iters))

    def run(self, graph):
        """Apply the schedule; returns the optimized graph (input graph
        untouched — each pass is pure)."""
        from .. import telemetry as _telemetry

        out = graph
        run_log = []
        fused_before = graph.fused_op_count()
        sig_before = out.signature() if self.fixed_point else None
        for _ in range(self.max_iters if self.fixed_point else 1):
            for name in self.pass_names:
                fn = PASS_REGISTRY[name]
                before = len(out.nodes)
                t0 = time.perf_counter()
                nxt = fn(out)
                dt = time.perf_counter() - t0
                if nxt is None or nxt is out:
                    raise MXNetError(
                        f"graph pass {name!r} must return a NEW graph "
                        "(pure Graph -> Graph)")
                out = nxt
                after = len(out.nodes)
                _record_pass(name, before, after, dt)
                run_log.append({"pass": name, "nodes_before": before,
                                "nodes_after": after,
                                "seconds": round(dt, 6)})
                _telemetry.compile_event(
                    "graph_pass", name, dt, "pipeline",
                    nodes_before=before, nodes_after=after)
            if not self.fixed_point:
                break
            sig_after = out.signature()
            if sig_after == sig_before:
                break
            sig_before = sig_after   # one hash per sweep, not two
        with _SLOCK:
            _STATS["pipeline_runs"] += 1
            _STATS["fused_ops_created"] += max(
                0, out.fused_op_count() - fused_before)
            _STATS["last_run"] = run_log
        return out

    def run_symbol(self, sym, input_names=None):
        """Symbol-level sugar (the ``subgraph.optimize_for`` shim):
        Symbol -> Graph -> passes -> Symbol."""
        from .ir import Graph

        g = Graph.from_symbol(sym, input_names=input_names)
        return self.run(g).to_symbol()


def default_pipeline():
    """The knob-configured pipeline every consumer uses."""
    return PassPipeline(selected_pass_names())
