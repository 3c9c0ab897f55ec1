"""The readers of the program's scopes, spans and counters: CPU, on events
and a table recorded on the chip, on hand-made events, and on a hand-filled
registry.

    python -m pytest chipbench/tests -q
"""
from __future__ import annotations

import gzip
import json
import os

import pytest

import toy

REPO = toy.REPO
CELL = "bert_base.seq512.fused"
DEVICE = ("fwd_device_ms", "bwd_device_ms", "optimizer_device_ms",
          "attn_bwd_device_ms", "scope_unsplit_device_pct")
HOST = ("step_prepare_host_ms", "step_execute_host_ms", "prefetch_wait_ms",
        "prefetch_miss_pct")


def _recorded(name):
    path = os.path.join(REPO, "chipbench", "testdata",
                        f"bert_step_scoped.{name}.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cell():
    from chipbench.harness.cell import Cell

    return Cell(CELL, root=REPO)


@pytest.fixture
def tables(monkeypatch):
    """The program's registry of tables, emptied for the test."""
    from mxnet_tpu import profiler

    monkeypatch.setattr(profiler, "_OP_SCOPES", type(profiler._OP_SCOPES)())
    return profiler._OP_SCOPES


def _ctx(events, steps):
    from chipbench.harness import trace

    return {"trace": events, "window": trace.window_of(events),
            "steps": steps, "summary": trace.summary(events, steps)}


def _read(cell, names, ctx):
    return {n: cell.read_layer_metric(n, ctx) for n in names}


# -- on what the chip recorded ------------------------------------------------

def test_the_split_of_the_recorded_step_adds_up(cell, tables, capsys):
    """Two steps of the 2-layer step, with the table the program built for
    that very executable."""
    tables["train_step:Other"] = {"fusion.1": {"scope": "", "classes": []}}
    tables["train_step:BertForPretraining"] = _recorded("op_scopes")
    ctx = _ctx(_recorded("trace_events"), steps=2)
    got = _read(cell, DEVICE + ("step_device_ms",), ctx)
    assert all(v is not None and v >= 0 for v in got.values())
    busy = got["step_device_ms"]
    parts = sum(got[n] for n in DEVICE[:3])
    assert parts + got["scope_unsplit_device_pct"] / 100 * busy \
        == pytest.approx(busy, rel=1e-9)
    # each part is there, the forward holds the Pallas kernel, and the
    # attention's backward is a part of what follows the forward
    assert min(got[n] for n in DEVICE[:4]) > 0
    assert got["attn_bwd_device_ms"] < busy - got["fwd_device_ms"]
    assert 0 < got["scope_unsplit_device_pct"] < 100
    said = capsys.readouterr().out
    assert said.count("scopes: table of") == 1      # read once a run
    # every op of the cut is the step's own: the table is that executable's
    assert "not in the table 0.000" in said


def test_attention_backward_is_found_by_its_scope(cell, tables):
    from chipbench.harness import trace
    from chipbench.layer_metrics import _scopes

    table = _recorded("op_scopes")
    tables["train_step:BertForPretraining"] = table
    events = _recorded("trace_events")
    ctx = _ctx(events, steps=2)
    named = {n for n, row in table.items()
             if _scopes.ATTENTION_BWD in row["scope"]}
    assert named
    on_line = {trace.op_name(e[0]) for e in events["devices"]["0"]["ops"]}
    assert named & on_line
    by_hand = sum(s for n, s in _scopes.self_seconds(trace.clip(
        events["devices"]["0"]["ops"], *ctx["window"])).items()
        if n in named)
    assert cell.read_layer_metric("attn_bwd_device_ms", ctx) \
        == pytest.approx(by_hand / 2 * 1e3)


# -- the union rule, on hand-made events --------------------------------------

def test_a_loop_and_the_ops_of_its_body_are_counted_once(cell, tables):
    """A ``while`` op lies on the line over the ops of its body: every
    instant goes to the innermost event that covers it."""
    from chipbench.harness import trace
    from chipbench.layer_metrics import _scopes

    ops = [["%fusion.1 = f32[8] fusion(...)", 0.0, 1.0],
           ["%while.2 = (s32[], f32[8]) while(...)", 1.0, 4.0],
           ["%fusion.3 = f32[8] fusion(...)", 1.5, 1.0],     # body, trip 1
           ["%copy.4 = f32[8] copy(...)", 2.0, 0.25],        # inside it
           ["%fusion.3 = f32[8] fusion(...)", 3.0, 1.0],     # body, trip 2
           ["%fusion.5 = f32[8] fusion(...)", 4.5, 1.0],     # straddles
           ["%fusion.6 = f32[8] fusion(...)", 7.0, 1.0]]
    own = _scopes.self_seconds(ops)
    assert own == pytest.approx({
        "fusion.1": 1.0, "while.2": 1.5, "fusion.3": 1.75, "copy.4": 0.25,
        "fusion.5": 1.0, "fusion.6": 1.0})
    assert sum(own.values()) == pytest.approx(
        trace.length(trace.union(ops))) == pytest.approx(6.5)
    row = lambda scope, *classes: {"scope": scope, "classes": list(classes)}
    bwd = "jit(train_step)/transpose(jvp(mx_forward))/"
    tables["train_step:Hand"] = {
        "fusion.1": row("jit(train_step)/jvp(mx_forward)/tanh", "forward"),
        "while.2": row(bwd + "mxnet_flash_attention_bwd/while", "backward"),
        "fusion.3": row(bwd + "mxnet_flash_attention_bwd/while/body/exp",
                        "backward", "forward"),
        "copy.4": row(""),
        "fusion.5": row(bwd + "dot_general", "backward", "optimizer"),
        "fusion.6": row("jit(train_step)/mx_optimizer/sub", "optimizer")}
    events = {"devices": {"0": {"ops": ops}, "1": {"ops": ops}},
              "host": [["dispatch_step", 0.0, 8.0]]}
    got = _read(cell, DEVICE, _ctx(events, steps=2))
    # seconds over two steps, a chip: milliseconds a step are x 500
    assert got == pytest.approx({
        "fwd_device_ms": 500.0, "bwd_device_ms": 750.0,
        "optimizer_device_ms": 500.0, "attn_bwd_device_ms": 1625.0,
        "scope_unsplit_device_pct": 3.0 / 6.5 * 100})


# -- nothing to read ----------------------------------------------------------

@pytest.mark.parametrize("table", [
    None, {}, {"fusion.1": {"scope": "jit(step)/jvp(tanh)", "classes": []}}],
    ids=["no-table", "empty-table", "no-class"])
def test_with_no_table_the_device_readers_find_nothing(cell, tables, table):
    if table is not None:
        tables["train_step:BertForPretraining"] = table
    events = {"devices": {"0": {"ops": [["%fusion.1 = f32[8]", 0.0, 1.0]]}},
              "host": [["dispatch_step", 0.0, 1.0]]}
    assert set(_read(cell, DEVICE, _ctx(events, 1)).values()) == {None}


def test_a_program_without_the_registry_gives_nothing(cell, monkeypatch):
    """The parent commit's program: no ``op_scopes``, no phases."""
    from mxnet_tpu import profiler, telemetry

    monkeypatch.delattr(profiler, "op_scopes")
    telemetry.reset()
    events = {"devices": {"0": {"ops": [["%fusion.1 = f32[8]", 0.0, 1.0]]}},
              "host": [["dispatch_step", 0.0, 1.0]]}
    got = _read(cell, DEVICE + HOST, _ctx(events, 1))
    assert set(got.values()) == {None}


# -- the program's counters, on a hand-filled registry ------------------------

def test_host_readers_on_a_hand_filled_registry(cell):
    from mxnet_tpu import telemetry

    telemetry.reset()
    phases = telemetry.histogram("mxnet_step_phase_seconds",
                                 labelnames=("phase",))
    for seconds in (0.001, 0.003):
        phases.labels(phase="train_step.prepare").observe(seconds)
    for seconds in (0.002, 0.002, 0.005):
        phases.labels(phase="train_step.execute").observe(seconds)
    phases.labels(phase="train_step.compile").observe(60.0)
    wait = telemetry.histogram("mxnet_prefetch_wait_seconds")
    for seconds in (0.0001, 0.0003, 0.0002, 0.0002):
        wait.observe(seconds)
    telemetry.counter("mxnet_prefetch_hits_total").inc(3)
    telemetry.counter("mxnet_prefetch_misses_total").inc(1)
    try:
        got = _read(cell, HOST, {})
    finally:
        telemetry.reset()
    assert got == pytest.approx({
        "step_prepare_host_ms": 2.0, "step_execute_host_ms": 3.0,
        "prefetch_wait_ms": 0.2, "prefetch_miss_pct": 25.0})


def test_the_new_entries_list_their_cell_and_have_their_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    layers = {"attn_bwd_device_ms": "attention kernel",
              "prefetch_wait_ms": "input staging",
              "prefetch_miss_pct": "input staging"}
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in DEVICE + HOST:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "samples_per_s_per_chip"
        assert entries[name]["layer"] == layers.get(name, "fused step")
        assert entries[name]["source"] == (
            "device_trace" if name in DEVICE else "program_counter")
