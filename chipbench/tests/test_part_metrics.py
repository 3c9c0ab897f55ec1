"""The readers of the step's split by part of the model: CPU, on two steps
of a two-layer decoder recorded on the chip with the table the program
built for that executable, on hand-made events, and on a table from before
the parts.

    python -m pytest chipbench/tests -q
"""
from __future__ import annotations

import gzip
import json
import os

import pytest

import toy

REPO = toy.REPO
CELL = "trinity_mini.causal_seq8192.fused"
MS = ("attn_proj_device_ms", "ffn_dense_device_ms", "head_loss_device_ms",
      "norm_rope_device_ms", "embed_device_ms")
NEW = MS + ("step_unnamed_device_pct",)


def _recorded(name):
    with gzip.open(os.path.join(REPO, "chipbench", "testdata",
                                name + ".json.gz"), "rt") as f:
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

def test_the_parts_of_the_recorded_decoder_step_add_up(cell, tables, capsys):
    """A dense window layer and a full layer of routed experts beside a
    shared one, under AMP with per-layer recomputation: every part the
    decoder cells have between them ran on the chip."""
    from chipbench.layer_metrics import _parts

    table = _recorded("decoder_step_parts.op_scopes")
    tables["train_step:LlamaForCausalLM"] = table
    ctx = _ctx(_recorded("decoder_step_parts.trace_events"), steps=2)
    got = _read(cell, NEW + ("step_device_ms",), ctx)
    assert all(v is not None and v > 0 for v in got.values()), got
    busy = got["step_device_ms"]
    split = _parts.split(ctx)
    assert split["busy"] == pytest.approx(busy, rel=1e-9)
    # every instant once: the parts, ``mixed`` and ``""`` are the busy time
    assert sum(split["parts"].values()) == pytest.approx(busy, rel=1e-9)
    unnamed = sum(split["parts"].get(p, 0.0) for p in _parts.UNNAMED)
    assert got["step_unnamed_device_pct"] == pytest.approx(
        unnamed / busy * 100)
    assert got["step_unnamed_device_pct"] < 50
    # the readers are sums of parts; what they leave is the kernels', the
    # expert layer's, the optimizer's and the unnamed
    assert got["head_loss_device_ms"] == pytest.approx(
        split["parts"]["mx_head"] + split["parts"]["mx_loss"])
    assert got["norm_rope_device_ms"] == pytest.approx(
        split["parts"]["mx_norm"] + split["parts"]["mx_rope"])
    others = {"mxnet_flash_attention_fwd", "mxnet_flash_attention_bwd",
              "mx_moe_route", "mx_moe_experts", "mx_moe_shared", "optimizer"}
    assert others <= set(split["parts"])
    assert sum(got[n] for n in MS) + unnamed + sum(
        split["parts"][p] for p in others) == pytest.approx(busy, rel=1e-9)
    # the existing readers read the same recording as before
    assert cell.read_layer_metric("moe_shared_device_ms", ctx) \
        == pytest.approx(split["parts"]["mx_moe_shared"])
    said = capsys.readouterr().out
    assert said.count("parts: they sum to") == 1        # read once a run
    assert "largest of no part:" in said and "largest of mixed:" in said


def test_every_row_of_the_recorded_table_has_one_part(tables):
    from mxnet_tpu import profiler

    table = _recorded("decoder_step_parts.op_scopes")
    named = {v for k, v in vars(profiler).items() if k.startswith("SCOPE_")
             and v not in (profiler.SCOPE_FORWARD, profiler.SCOPE_OPTIMIZER)}
    allowed = named | {profiler.KERNEL_ATTENTION_FWD, profiler.PART_MIXED,
                       profiler.PART_OPTIMIZER, ""}
    assert {row["part"] for row in table.values()} <= allowed
    # a weight gradient's matmul with Adam's update as epilogue is its
    # matmul's part, though XLA names it after the update or fuses both
    # (where what it fused of the backward has no part, the optimizer's)
    fused = [row["part"] for row in table.values()
             if {"backward", "optimizer"} <= set(row["classes"])]
    assert set(fused) <= named | {profiler.PART_OPTIMIZER}
    assert {profiler.SCOPE_HEAD, profiler.SCOPE_ATTENTION_PROJ,
            profiler.SCOPE_FFN, profiler.SCOPE_MOE_SHARED} <= set(fused)


# -- a program before the parts -----------------------------------------------

def test_a_table_without_parts_gives_nothing(cell, tables, capsys):
    """The parent's program, or its executable found in a shared compile
    cache: ``bert_step_scoped`` was recorded before rows had a part."""
    table = _recorded("bert_step_scoped.op_scopes")
    assert not any("part" in row for row in table.values())
    tables["train_step:BertForPretraining"] = table
    ctx = _ctx(_recorded("bert_step_scoped.trace_events"), steps=2)
    assert set(_read(cell, NEW, ctx).values()) == {None}
    # the readers that were there read it as before
    assert cell.read_layer_metric("fwd_device_ms", ctx) > 0
    assert "parts:" not in capsys.readouterr().out


@pytest.mark.parametrize("table", [
    None, {}, {"fusion.1": {"scope": "jit(step)/jvp(mx_forward)/tanh",
                            "classes": ["forward"], "part": ""}}],
    ids=["no-table", "empty-table", "no-part"])
def test_with_no_table_or_no_part_the_readers_find_nothing(cell, tables,
                                                           table):
    if table is not None:
        tables["train_step:Net"] = table
    events = {"devices": {"0": {"ops": [["%fusion.1 = f32[8]", 0.0, 1.0]]}},
              "host": [["dispatch_step", 0.0, 1.0]]}
    assert set(_read(cell, NEW, _ctx(events, 1)).values()) == {None}


# -- the rule of the sum, on hand-made events ---------------------------------

def test_parts_on_hand_made_events(cell, tables):
    """Every instant to the innermost event; an op the table does not hold
    (another program's) and an op of no part are unnamed; a net without a
    dense layer has no ``ffn_dense_device_ms``."""
    ops = [["%fusion.1 = bf16[8] fusion(...)", 0.0, 1.0],
           ["%while.2 = (s32[], f32[8]) while(...)", 1.0, 4.0],
           ["%fusion.3 = f32[8] fusion(...)", 1.5, 1.0],     # body, trip 1
           ["%copy.4 = f32[8] copy(...)", 2.0, 0.25],        # inside it
           ["%fusion.3 = f32[8] fusion(...)", 3.0, 1.0],     # body, trip 2
           ["%fusion.5 = f32[8] fusion(...)", 5.0, 1.0],
           ["%fusion.6 = f32[8] fusion(...)", 6.0, 0.5],
           ["%fusion.7 = f32[8] fusion(...)", 6.5, 0.5],
           ["%elsewhere.8 = f32[8] fusion(...)", 7.0, 1.0]]
    row = lambda part: {"scope": "jit(train_step)/jvp(mx_forward)/x",
                        "classes": ["forward"], "part": part}
    tables["train_step:Hand"] = {
        "fusion.1": row("mx_attn_proj"), "while.2": row(""),
        "fusion.3": row("mx_moe_route"), "copy.4": row(""),
        "fusion.5": row("mx_head"), "fusion.6": row("mx_loss"),
        "fusion.7": row("mixed"), "unused.9": row("mx_rope")}
    events = {"devices": {"0": {"ops": ops}, "1": {"ops": ops}},
              "host": [["dispatch_step", 0.0, 8.0]]}
    got = _read(cell, NEW, _ctx(events, steps=2))
    # seconds over two steps, a chip: milliseconds a step are x 500
    assert got == pytest.approx({
        "attn_proj_device_ms": 500.0, "ffn_dense_device_ms": None,
        "head_loss_device_ms": 750.0,
        "norm_rope_device_ms": 0.0,        # in the table, not on the line
        "embed_device_ms": None,
        # the loop's own 2.0, the copy 0.25, the mixed 0.5, the stranger 1.0
        "step_unnamed_device_pct": 3.75 / 8.0 * 100})


# -- the entries --------------------------------------------------------------

def test_the_new_entries_have_their_files_and_list_cells_that_exist():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert len(entries) == 32
    assert list(entries)[-6:] == list(NEW)      # appended, in this order
    for name in NEW:
        entry = entries[name]
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "layer_metrics", name + ".py"))
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert entry["source"] == "device_trace"
        assert entry["better"] == "lower"
        assert entry["moves"] == "samples_per_s_per_chip"
        assert entry["unit"] == ("%" if name.endswith("_pct") else "ms")
        assert entry["layer"] == ("fused step" if name.endswith("_pct")
                                  else "transformer block")
        assert entry["workloads"] and set(entry["workloads"]) <= set(cells)
    # a dense layer: the encoder's cells and the window cell's layer 0
    assert entries["ffn_dense_device_ms"]["workloads"] == [
        c for c in cells if c.startswith(("bert_base.", "trinity_mini."))]
    for name in set(NEW) - {"ffn_dense_device_ms"}:
        assert entries[name]["workloads"] == cells
