"""Graph compiler tier (ISSUE 11): IR, passes, pipeline, Symbol surface.

Every pass ships with a seeded fixture graph built from Symbols + a
BIT-parity assertion (optimized output ``np.array_equal`` unoptimized —
the fp32 contract), plus the pins of the surface the tier serves: a
loaded ``SymbolBlock`` runs the optimized heads and ``optimize_for``
rides the pipeline; and a served artifact traces nothing in steady state.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu import graph as G
from mxnet_tpu.gluon import HybridBlock, nn


@pytest.fixture(autouse=True)
def _reset_graph_stats():
    G.reset_stats()
    yield


def _exec(g, feed):
    """Run a graph via the block executor with params fed by name."""
    import jax

    fn = G.make_block_fn(g)
    pvals = [feed[nm] for _, nm in g.params]
    ivals = [feed[g.nodes[i].name] for i in g.inputs]
    return [np.asarray(v)
            for v in fn(pvals, jax.random.PRNGKey(0), *ivals)]


def _stamp_avals(g, *inputs):
    """Give every node its ``(shape, dtype)`` a port: what a front end
    that knows its types stamps, and what place_amp_casts reads."""
    import itertools

    import jax

    every = g.copy()
    every.outputs = [(nid, i) for nid, n in enumerate(g.nodes)
                     for i in range(n.nout)]
    avals = iter(jax.eval_shape(G.make_block_fn(every), [],
                                jax.random.PRNGKey(0), *inputs))
    for n in g.nodes:
        n.avals = tuple((tuple(a.shape), str(a.dtype))
                        for a in itertools.islice(avals, n.nout))
    return g


# -- IR ---------------------------------------------------------------------
def test_from_symbol_round_trip_and_copy_purity():
    x = mx.sym.var("data")
    y = mx.sym.tanh(mx.sym.FullyConnected(x, num_hidden=4, name="fc"))
    g = G.Graph.from_symbol(y, input_names=["data"])
    assert len(g.inputs) == 1 and len(g.params) == 2  # weight + bias
    sym2 = g.to_symbol()
    assert sym2.list_arguments() == y.list_arguments()
    sig = g.signature()
    g2 = g.copy()
    g2.nodes[0].attrs["mutated"] = 1
    g2.outputs = []
    assert g.signature() == sig  # the copy is fully detached


def test_validate_rejects_forward_edges():
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.graph.ir import Graph, Node

    a = Node(None, "x")
    b = Node("tanh", "t", inputs=[(1, 0)])   # self-reference
    with pytest.raises(MXNetError):
        Graph([a, b], inputs=[0], outputs=[(1, 0)]).validate()


# -- per-pass parity fixtures -----------------------------------------------
def test_fold_constants_parity_and_shrink():
    from mxnet_tpu.graph.passes import fold_constants
    from mxnet_tpu.symbol.symbol import constant

    x = mx.sym.var("data")
    c = mx.sym.sqrt(constant(np.full((4,), 2.0, "f")) * 3.0)  # const chain
    y = mx.sym.broadcast_add(mx.sym.tanh(x), c)
    g = G.Graph.from_symbol(y, input_names=["data"])
    feed = {"data": np.random.RandomState(0).randn(3, 4).astype("f")}
    ref = _exec(g, feed)
    opt = fold_constants(g)
    assert opt.n_ops < g.n_ops          # sqrt + scalar-mul folded away
    assert len(G.Graph.from_symbol(y, input_names=["data"]).nodes) == \
        len(g.nodes)                     # input graph untouched
    out = _exec(opt, feed)
    assert all(np.array_equal(a, b) for a, b in zip(out, ref))


def test_cse_merges_duplicates_parity():
    from mxnet_tpu.graph.passes import eliminate_common_subexpr

    x = mx.sym.var("data")
    y = mx.sym.broadcast_add(mx.sym.tanh(x), mx.sym.tanh(x))  # two tanh
    g = G.Graph.from_symbol(y, input_names=["data"])
    assert sum(1 for n in g.nodes if n.op == "tanh") == 2
    feed = {"data": np.random.RandomState(1).randn(2, 5).astype("f")}
    ref = _exec(g, feed)
    opt = eliminate_common_subexpr(g)
    # the duplicate is re-routed; DCE removes the husk
    from mxnet_tpu.graph.passes import eliminate_dead_nodes

    opt = eliminate_dead_nodes(opt)
    assert sum(1 for n in opt.nodes if n.op == "tanh") == 1
    out = _exec(opt, feed)
    assert all(np.array_equal(a, b) for a, b in zip(out, ref))


def test_cse_never_merges_rng_ops():
    from mxnet_tpu.graph.passes import eliminate_common_subexpr

    import jax

    x = mx.sym.var("data")
    y = mx.sym.Dropout(x, p=0.5, training=True) + \
        mx.sym.Dropout(x, p=0.5, training=True)
    g = G.Graph.from_symbol(y, input_names=["data"])
    n_drop = sum(1 for n in g.nodes if n.op == "Dropout")
    assert n_drop == 2
    opt = eliminate_common_subexpr(g)
    assert sum(1 for n in opt.nodes if n.op == "Dropout") == 2
    # and the two draws stay distinct at execution
    fn = G.make_block_fn(opt)
    out = np.asarray(fn([], jax.random.PRNGKey(3),
                        np.ones((4, 4), "f"))[0])
    assert not np.array_equal(out, 2 * np.ones((4, 4)) * 2)


def test_dead_node_elimination_keeps_signature():
    from mxnet_tpu.graph.passes import eliminate_dead_nodes

    x = mx.sym.var("data")
    live = mx.sym.tanh(x)
    dead = mx.sym.sigmoid(mx.sym.exp(x))
    both = mx.sym.Group([live, dead])
    g = G.Graph.from_symbol(both, input_names=["data"])
    g.outputs = [g.outputs[0]]           # only the tanh head is live
    feed = {"data": np.random.RandomState(2).randn(2, 3).astype("f")}
    ref = _exec(g, feed)
    opt = eliminate_dead_nodes(g)
    assert opt.n_ops == 1 and len(opt.inputs) == 1
    assert np.array_equal(_exec(opt, feed)[0], ref[0])


def test_fuse_elemwise_chains_parity_and_cap(monkeypatch):
    from mxnet_tpu.graph.passes import fuse_elemwise_chains

    h = mx.sym.var("data")
    for _ in range(4):
        h = mx.sym.tanh(h * 0.5 + 1.0)
    g = G.Graph.from_symbol(h, input_names=["data"])
    assert g.n_ops == 12
    feed = {"data": np.random.RandomState(3).randn(3, 4).astype("f")}
    ref = _exec(g, feed)[0]
    opt = fuse_elemwise_chains(g)
    assert opt.fused_op_count() == 1 and opt.n_ops == 1
    assert np.array_equal(_exec(opt, feed)[0], ref)
    # the chain cap splits long chains into bounded fused segments
    monkeypatch.setenv("MXNET_GRAPH_FUSE_CAP", "4")
    capped = fuse_elemwise_chains(g)
    assert capped.fused_op_count() > 1
    assert all(n.attrs.get("__n_fused__", 0) <= 4 for n in capped.nodes)
    assert np.array_equal(_exec(capped, feed)[0], ref)
    monkeypatch.setenv("MXNET_GRAPH_FUSE_CAP", "0")
    assert fuse_elemwise_chains(g).fused_op_count() == 0


def test_amp_cast_placement_parity():
    from mxnet_tpu.graph.passes import eliminate_dead_nodes, place_amp_casts

    def cast(s, dtype):
        return mx.sym.cast(s, dtype=dtype)

    # identity cast + widen->narrow round trip + cast after movement
    # (hoistable) — all bit-exact removals/moves
    x = mx.sym.var("data")
    h = cast(x, "float32")                              # identity (x is f32)
    h = cast(cast(h, "float16"), "float32")             # NOT collapsible
    w = cast(x, "float16")
    w = cast(cast(w, "float32"), "float16")             # collapses to w
    r = cast(mx.sym.reshape(x, shape=(4, 3)), "float16")  # hoists above move
    y = mx.sym.sum(h) + mx.sym.sum(cast(w, "float32")) + \
        mx.sym.sum(cast(r, "float32"))
    feed = {"data": np.random.RandomState(4).randn(3, 4).astype("f")}
    g = _stamp_avals(G.Graph.from_symbol(y, input_names=["data"]),
                     feed["data"])
    ref = _exec(g, feed)
    n_casts = sum(1 for n in g.nodes if n.op == "cast")
    assert n_casts >= 7
    opt = eliminate_dead_nodes(place_amp_casts(g))
    assert sum(1 for n in opt.nodes if n.op == "cast") < n_casts
    out = _exec(opt, feed)
    assert all(np.array_equal(a, b) for a, b in zip(out, ref))


# -- pipeline ---------------------------------------------------------------
def test_pipeline_idempotent_and_telemetry():
    h = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=8, name="fc")
    for _ in range(4):
        h = mx.sym.sigmoid(h + 0.25)
    g = G.Graph.from_symbol(h, input_names=["data"])
    pipe = G.default_pipeline()
    opt1 = pipe.run(g)
    opt2 = G.default_pipeline().run(opt1)
    assert opt1.signature() == opt2.signature()   # fixed point reached
    assert opt1.fused_op_count() >= 1
    events = [e for e in telemetry.compile_events()
              if e["kind"] == "graph_pass"]
    assert events and all("nodes_before" in e and "nodes_after" in e
                          for e in events)
    snap = telemetry.snapshot()["graph"]
    assert snap["pipeline_runs"] >= 2
    assert snap["fused_ops_created"] >= 1
    assert "fuse_elemwise_chains" in snap["passes"]


def test_pass_selection_knob(monkeypatch):
    monkeypatch.setenv("MXNET_GRAPH_PASSES", "-fuse_elemwise_chains")
    names = G.selected_pass_names()
    assert "fuse_elemwise_chains" not in names
    assert "eliminate_dead_nodes" in names
    monkeypatch.setenv("MXNET_GRAPH_PASSES",
                       "fold_constants,eliminate_dead_nodes")
    assert G.selected_pass_names() == ["fold_constants",
                                       "eliminate_dead_nodes"]
    monkeypatch.setenv("MXNET_GRAPH_PASSES", "no_such_pass")
    from mxnet_tpu.base import MXNetError

    with pytest.raises(MXNetError):
        G.selected_pass_names()


def test_registering_duplicate_pass_name_raises():
    from mxnet_tpu.base import MXNetError

    @G.graph_pass("test_dup_pass_name")
    def p1(graph):
        return graph.copy()

    with pytest.raises(MXNetError):
        @G.graph_pass("test_dup_pass_name")
        def p2(graph):
            return graph.copy()


# -- serving / export integration --------------------------------------------
def test_serving_artifact_optimized_zero_fresh_traces(tmp_path):
    """Export -> load_artifact: the artifact bit-matches the hybridized
    forward, and steady state performs ZERO fresh traces (the ISSUE 11
    serving pin)."""
    from mxnet_tpu import serving

    class Deep(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.fc1 = nn.Dense(32, in_units=16)
                self.fc2 = nn.Dense(8, in_units=32)

        def hybrid_forward(self, F, x):
            h = self.fc1(x)
            for _ in range(4):
                h = F.tanh(h * 0.5 + 0.1)
            return self.fc2(h)

    net = Deep()
    net.initialize()
    net.hybridize()
    x = nd.array(np.random.RandomState(0).randn(4, 16).astype("f"))
    y_raw = net(x).asnumpy()
    path = str(tmp_path / "deep")
    net.export(path)
    art = serving.load_artifact(path)
    assert np.array_equal(art(x).asnumpy(), y_raw)
    # steady state: repeat calls at a warmed signature trace nothing
    before = telemetry.snapshot()["compile"]["count"]
    for _ in range(3):
        art(x)
    assert telemetry.snapshot()["compile"]["count"] == before


def test_symbol_block_runs_optimized_heads(tmp_path):
    """SymbolBlock (the load_artifact reconstruction path) runs the
    optimized heads: fused chain present, outputs bit-match ``evaluate``
    of the raw heads."""
    from mxnet_tpu.gluon import SymbolBlock
    from mxnet_tpu.symbol.symbol import _topo, evaluate

    class Deep(HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.fc = nn.Dense(8, in_units=6)

        def hybrid_forward(self, F, x):
            h = self.fc(x)
            for _ in range(4):
                h = F.tanh(h * 0.25)
            return h

    net = Deep()
    net.initialize()
    xv = nd.array(np.random.RandomState(1).randn(2, 6).astype("f"))
    prefix = str(tmp_path / "deep")
    net.export(prefix, 0, xv, manifest=False)
    blk = SymbolBlock.imports(f"{prefix}-symbol.json", ["data"],
                              f"{prefix}-0000.params")
    feed = {"data": xv._get()}
    feed.update({n: blk.params.get(n).data()._get()
                 for n in blk._sym_param_names})
    y_raw = np.asarray(evaluate(blk._sym._heads, feed)[0][0])
    y_opt = blk(xv).asnumpy()
    heads = blk._optimized_heads()
    assert np.array_equal(y_opt, y_raw)
    ops = [n.op for n in _topo(heads) if n.op is not None]
    assert any(op.startswith("_gfused_chain") for op in ops), ops


def test_subgraph_backends_ride_the_pipeline():
    """optimize_for is PassPipeline sugar: backend passes emit
    kind=graph_pass compile events like any other pass."""
    before = len([e for e in telemetry.compile_events()
                  if e["kind"] == "graph_pass"])
    sym = mx.sym.Activation(
        mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=4,
                              name="fc"), act_type="relu")
    fused = sym.optimize_for("default")
    from mxnet_tpu.symbol.symbol import _topo

    assert any(n.op == "_sg_fused_dense_act" for n in _topo(fused._heads))
    events = [e for e in telemetry.compile_events()
              if e["kind"] == "graph_pass"]
    assert len(events) > before
    assert any(e["name"].startswith("subgraph:default:") for e in events)
