"""Graph compiler tier (ISSUE 11): Relay/TVM-style optimization passes
over a Symbol's graph, on request.

``SymbolBlock`` (a loaded export), ``Symbol.optimize_for`` and the
``subgraph`` backends hand their Symbol to :func:`default_pipeline`:
constant folding, CSE, AMP-cast placement, elementwise-chain fusion and
DCE run over the typed :class:`Graph` IR and the optimized Symbol comes
back.  A Gluon net (``hybridize()``, ``parallel.functionalize``) is
traced by JAX alone and does not pass through here.  Every pass is pure
(MXT070) and bit-parity-preserving on fp32 paths.
"""
from .ir import Graph, Node
from .pipeline import (DEFAULT_PASSES, PassPipeline, default_pipeline,
                       graph_pass, list_passes, record_fallback,
                       reset_stats, selected_pass_names, stats_snapshot)
from . import passes as _passes  # noqa: F401  (registers the builtins)
from .executor import make_block_fn

__all__ = ["Graph", "Node", "PassPipeline", "default_pipeline",
           "graph_pass", "list_passes", "selected_pass_names",
           "DEFAULT_PASSES", "stats_snapshot", "reset_stats",
           "record_fallback", "make_block_fn"]
