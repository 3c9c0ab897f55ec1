"""The ``phi4_mini_flash`` configuration at a toy width through the harness,
on the CPU: the real cell is found with its files and its arithmetic, a toy
run is correct and counts its scan and what its layers hand on, the check
separates the lower precision and every fault of ``faults.py``, and the four
readers this configuration brings read a made-up trace and give a program
without the mixers nothing."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

import toy
import toy_phi4

sys.path.insert(0, os.path.join(toy.REPO, "chipbench"))

V5E = "TPU v5 lite"
NEW = {"ssm_scan_device_ms", "ssm_scan_fwd_roofline", "ssm_scan_bwd_roofline",
       "diff_attn_fwd_roofline"}


@pytest.fixture(autouse=True)
def chunks_of_16(monkeypatch):
    """The toy rows are 32 long: two chunks of the scan a row."""
    from mxnet_tpu.gluon.model_zoo.language import llama

    monkeypatch.setattr(llama, "SSM_CHUNK", 16)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy_phi4.make_root(tmp_path_factory.mktemp("chipbench_phi4"))


def _cell(root):
    from chipbench.harness.cell import Cell

    return Cell(toy_phi4.CELL, root=root)


def test_the_real_cell_is_found_with_its_files():
    from chipbench.harness.cell import Cell

    cell = Cell(toy_phi4.LIKE)
    assert cell.chips == 1 and cell.spec["batch"] * cell.spec["seq"] == 8192
    shapes = cell.reference.param_shapes(cell.cfg)
    n = sum(int(np.prod(shape)) for shape, _ in shapes.values())
    # the issue's reckoning: 577.1M parameters, 8.60 GiB at 16 bytes
    assert n == pytest.approx(577.1e6, rel=1e-3)
    assert 8.59 < n * 16 / 2 ** 30 < 8.62
    # every key of the catalog's row as published, but the two `reduced` names
    source = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
              "intermediate_size": 10240, "layer_norm_eps": 1e-05,
              "max_position_embeddings": 262144, "mb_per_layer": 2,
              "model_type": "phi4flash", "num_attention_heads": 40,
              "num_key_value_heads": 20, "resid_pdrop": 0,
              "sliding_window": 512, "tie_word_embeddings": True,
              "mlp_bias": False, "lm_head_bias": False}
    assert {k: cell.cfg[k] for k in source} == source
    assert cell.cfg["published"] == {"num_hidden_layers": 32,
                                     "vocab_size": 200064}
    assert sorted(cell.cfg["reduced"]) == sorted(cell.cfg["published"])
    assert cell.cfg["vocab_size"] * 8 == 200064
    assert (cell.cfg["num_hidden_layers"], cell.cfg["layers_first"]) == (5, 15)
    assert cell.build.counts.ssm_sizes(cell.cfg) == (5120, 16, 4, 160)
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert NEW | {"mfu", "window_attn_fwd_roofline", "mixer_gate_device_ms",
                  "ffn_dense_device_ms", "attn_bwd_device_ms",
                  "step_unnamed_device_pct"} <= names
    assert not names & {"flash_fwd_roofline", "kda_device_ms",
                        "mla_attn_fwd_roofline", "moe_experts_device_ms",
                        "allreduce_exposed_ms"}
    # the new metrics are this cell's alone
    other = Cell("ling3_flash_vl.causal_seq8192.fused")
    assert not {m["name"] for m in other.metrics("per_layer")} & NEW


def test_a_toy_run_is_correct_and_counts_its_scan_and_hand_overs(root):
    import jax
    import run
    from chipbench.layer_metrics import _scopes
    from chipbench.harness.peaks import peaks_of
    from mxnet_tpu import telemetry

    telemetry.reset()
    cell = _cell(root)
    result = run.run_cell(cell, jax.devices()[:1], peaks_of(V5E), 2147483651,
                          1.0, False)
    assert result["correct"] is True
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["metrics"]) == {"samples_per_s_per_chip", "step_ms_p95",
                                      "peak_hbm_gib", "setup_s"}
    assert _scopes.sample("mxnet_selective_scan_fwd_calls_total",
                          path="scan")["value"] == 1
    assert _scopes.sample("mxnet_selective_scan_chunks_total")["value"] == 2
    handed = telemetry.LAYER_HANDED_ON_BYTES
    assert handed.labels(name="memory").value == 2 * 32 * 128 * 4
    assert handed.labels(name="kv").value == 2 * (2 * 2 * 32 * 16) * 4


@pytest.mark.parametrize("seed", range(1, 4))
def test_check_separates_the_lower_precision(root, seed):
    import limits
    from chipbench.harness import check

    cell = _cell(root)
    row = limits.read_seed(cell, seed)
    lines = []
    assert check.verdict(row["sound"], cell.spec["limits"],
                         lines.append), lines
    assert not check.verdict(row["control"], cell.spec["limits"],
                             lines.append), lines
    assert row["control"]["first_gradient_error"][0] \
        > 3 * row["sound"]["first_gradient_error"][0]


def test_every_planted_fault_is_not_correct(root):
    """Each of ``faults.py``'s five planted in the program's own path, the
    reference left whole: the cell's check says NOT CORRECT, where the
    program as it is passes on the same seed."""
    from chipbench.harness import check, loop
    from chipbench.harness.cell import _module

    cell = _cell(root)
    faults = _module(root, "configs", "toy_phi4", "faults")
    spec, cfg, seed = cell.spec, cell.cfg, 3
    pool = loop.make_pool(cell.build, cfg, spec, seed)
    ref = check.follow(cell.reference, cfg, "float32",
                       cell.reference.init_params(cfg, seed),
                       pool[:spec["check_steps"]], spec)

    def correct(fault):
        lines = []
        return check.verdict(check.compare(faults.first_step(
            cell, seed, pool, fault), ref), spec["limits"],
            lines.append), lines

    assert correct(None)[0]
    assert len(faults.FAULTS) == 5
    # the decay is no part of it here: over 32 rows at the seed's steps of
    # 0.001 to 0.1 the state hardly decays, and leaving it out moves nothing
    # past a limit (tests/test_ssm_diff_decoder.py sees it at larger steps,
    # the chip at 8,192 rows: PERF.md section 2)
    for fault in faults.FAULTS[1:]:
        ok, lines = correct(fault)
        assert not ok, (fault, lines)
    with pytest.raises(ValueError, match="no fault"):
        with faults.planted("gate"):
            pass


def test_the_new_readers_on_a_made_up_trace(root, monkeypatch):
    """``ssm_scan_device_ms`` takes the ops the table resolves to its part;
    the two scan rooflines the ops under the walks' scopes against the
    walks' least times; ``diff_attn_fwd_roofline`` the causal kernel's
    events and not the window's.  A program with none of them (the parent)
    gives None."""
    from chipbench.layer_metrics import (_scopes, diff_attn_fwd_roofline,
                                         ssm_scan_bwd_roofline,
                                         ssm_scan_device_ms,
                                         ssm_scan_fwd_roofline)
    from chipbench.harness.peaks import peaks_of

    cell = _cell(root)
    counts, cfg, peaks = cell.build.counts, cell.cfg, peaks_of(V5E)
    seq = cell.spec["seq"]

    def least(flops, nbytes):
        return 2 * max(flops / peaks["flops_bf16"],
                       nbytes / peaks["hbm_bytes_per_s"])

    fwd_walk = least(counts.ssm_scan_fwd_flops(cfg, seq),
                     counts.ssm_scan_fwd_bytes(cfg, seq, 2))
    bwd_walk = least(counts.ssm_scan_bwd_flops(cfg, seq),
                     counts.ssm_scan_bwd_bytes(cfg, seq, 2))
    a_call = least(counts.diff_attention_fwd_flops(cfg, seq),
                   counts.diff_attention_fwd_bytes(cfg, seq, 2))
    fwd = ("jit(train_step)/mx_forward/checkpoint/mx_ssm_scan/"
           "mxnet_selective_scan_fwd/pallas_call")
    bwd = ("jit(train_step)/transpose(jvp(mx_forward))/checkpoint/"
           "mx_ssm_scan/mxnet_selective_scan_bwd/pallas_call")
    kernel = "%mxnet_flash_attention_fwd{} = bf16[] custom-call()"
    # 2 steps of one state-space layer: two forward walks at 4 times their
    # least time, two backward walks at 10 times; two causal calls at twice
    # theirs, and a window call that does not count
    ops = [["%mxnet_selective_scan_fwd.1 = f32[] custom-call()", 0.000,
            2 * 4 * fwd_walk],
           ["%mxnet_selective_scan_bwd.1 = f32[] custom-call()", 0.010,
            2 * 10 * bwd_walk],
           [kernel.format(".2"), 0.020, 2 * a_call],
           [kernel.format(".3"), 0.025, 2 * a_call],
           [kernel.format("_window.1"), 0.030, 0.001]]
    table = {"mxnet_selective_scan_fwd.1": {
        "scope": fwd, "classes": ["forward"], "part": "mx_ssm_scan"},
        "mxnet_selective_scan_bwd.1": {
        "scope": bwd, "classes": ["backward"], "part": "mx_ssm_scan"}}
    ctx = {"cfg": cfg, "cell": cell.spec, "build": cell.build, "chips": 1,
           "peaks": peaks, "trace": {"devices": {"0": {"ops": ops}}},
           "window": (0.0, 0.04), "steps": 2}
    monkeypatch.setattr(_scopes, "step_table", lambda: table)
    assert ssm_scan_fwd_roofline.read(ctx) == pytest.approx(25.0)
    assert ssm_scan_bwd_roofline.read(ctx) == pytest.approx(10.0)
    assert diff_attn_fwd_roofline.read(ctx) == pytest.approx(50.0)
    assert ssm_scan_device_ms.read(ctx) == pytest.approx(
        (2 * 4 * fwd_walk + 2 * 10 * bwd_walk) / 2 * 1e3)
    # the parent's program: no op of those scopes, no kernel in the trace
    ctx = dict(ctx, trace={"devices": {"0": {"ops": [
        ["%fusion.9 = f32[] fusion()", 0.0, 0.01]]}}})
    ctx.pop("_part_split", None)
    table.clear()
    table["fusion.9"] = {"scope": "jit(train_step)/mx_forward/mx_ffn/dot",
                         "classes": ["forward"], "part": "mx_ffn"}
    for reader in (ssm_scan_fwd_roofline, ssm_scan_bwd_roofline,
                   diff_attn_fwd_roofline, ssm_scan_device_ms):
        assert reader.read(ctx) is None
    # and a configuration without the mixers reads no roofline of them,
    # whatever its trace holds
    from chipbench.harness.cell import Cell

    other = Cell("trinity_mini.causal_seq8192.fused")
    ctx = dict(ctx, cfg=other.cfg, cell=other.spec, build=other.build,
               trace={"devices": {"0": {"ops": ops}}})
    for reader in (ssm_scan_fwd_roofline, ssm_scan_bwd_roofline,
                   diff_attn_fwd_roofline):
        assert reader.read(ctx) is None
