"""The ``ling3_flash_vl`` configuration at a toy width through the harness,
on the CPU: the real cell is found with its files and its arithmetic, a toy
run is correct and counts its pairs and chunks, the check separates the
lower precision and a fault planted in the program, and the four readers this configuration brings read a
made-up trace and give a program without the mixers nothing."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

import toy
import toy_ling3

sys.path.insert(0, os.path.join(toy.REPO, "chipbench"))

V5E = "TPU v5 lite"


@pytest.fixture(autouse=True)
def chunks_of_16(monkeypatch):
    """The toy rows are 64 long: four chunks of the delta rule a row."""
    from mxnet_tpu.gluon.model_zoo.language import llama

    monkeypatch.setattr(llama, "KDA_CHUNK", 16)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy_ling3.make_root(tmp_path_factory.mktemp("chipbench_ling3"))


def _cell(root):
    from chipbench.harness.cell import Cell

    return Cell(toy_ling3.CELL, root=root)


def test_the_real_cell_is_found_with_its_files():
    from chipbench.harness.cell import Cell

    cell = Cell(toy_ling3.LIKE)
    assert cell.chips == 1 and cell.spec["batch"] * cell.spec["seq"] == 8192
    shapes = cell.reference.param_shapes(cell.cfg)
    n = sum(int(np.prod(shape)) for shape, _ in shapes.values())
    # the issue's reckoning: 577.9M parameters, 8.61 GiB at 16 bytes
    assert n == pytest.approx(577.9e6, rel=1e-3)
    assert 8.60 < n * 16 / 2 ** 30 < 8.63
    # every width as published, the cut in the five keys `reduced` names
    source = {"hidden_size": 2560, "head_dim": 128, "intermediate_size": 6144,
              "moe_intermediate_size": 768, "num_experts_per_tok": 8,
              "moe_shared_expert_intermediate_size": 768,
              "kv_lora_rank": 512, "qk_nope_head_dim": 128,
              "qk_rope_head_dim": 64, "v_head_dim": 128,
              "short_conv_kernel_size": 4, "n_group": 8, "topk_group": 4,
              "routed_scaling_factor": 2.5, "rope_theta": 6000000,
              "kda_lower_bound": -5, "layer_group_size": 6,
              "num_key_value_heads": 32}
    assert {k: cell.cfg[k] for k in source} == source
    assert cell.cfg["published"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts": 512, "num_attention_heads": 32, "vocab_size": 157184}
    assert sorted(cell.cfg["reduced"]) == sorted(cell.cfg["published"])
    assert cell.cfg["router_width"] == 512
    assert len(cell.cfg["expert_swiglu_limit_list"]) == 42
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"kda_device_ms", "kda_fwd_roofline", "mla_attn_fwd_roofline",
            "mixer_gate_device_ms", "moe_experts_device_ms",
            "moe_route_device_ms", "moe_load_max_over_mean",
            "moe_shared_device_ms", "ffn_dense_device_ms", "mfu",
            "moe_gmm_sparse_roofline",
            "attn_bwd_device_ms", "step_unnamed_device_pct"} <= names
    assert not names & {"flash_fwd_roofline", "window_attn_fwd_roofline",
                        "moe_gmm_roofline", "allreduce_exposed_ms"}
    # the new metrics are this cell's alone
    other = Cell("trinity_mini.causal_seq8192.fused")
    assert not {m["name"] for m in other.metrics("per_layer")} & {
        "kda_device_ms", "kda_fwd_roofline", "mla_attn_fwd_roofline",
        "mixer_gate_device_ms", "moe_gmm_sparse_roofline"}
    # the issue's load: 1,024 pairs a layer a step in the mean
    assert cell.build.counts.pairs_per_token(cell.cfg) * 8192 == 1024


def test_a_toy_run_is_correct_and_counts_its_pairs_and_chunks(root):
    import jax
    import run
    from chipbench.layer_metrics import _scopes, moe_load_max_over_mean
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
    # the assumed routers: independent columns, so of a token's 4 choices
    # among 32 outputs the 4 held here get half a pair in the mean, in
    # each of the 6 sparse layers (128 tokens a step: 384 pairs a step)
    pairs = _scopes.sample("mxnet_moe_routed_pairs_total")["value"]
    load = _scopes.sample("mxnet_moe_expert_load_max_over_mean")
    assert pairs / (load["count"] / 6) == pytest.approx(
        6 * 128 * cell.build.counts.pairs_per_token(cell.cfg), rel=0.25)
    assert 1.0 <= moe_load_max_over_mean.read({"cfg": cell.cfg}) <= 4.0
    assert _scopes.sample("mxnet_kda_calls_total", path="scan")["value"] == 6


@pytest.mark.parametrize("seed", range(1, 7))
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


@pytest.mark.parametrize("fault", ["no_decay", "no_delta"])
def test_a_fault_planted_in_the_program_is_not_correct(root, fault):
    """The decay dropped (``alpha = 1``) or the delta term dropped (``beta k
    k^T``) in the program's own chunked op, the reference left whole: the
    cell's check says NOT CORRECT, by ``loss_gap`` among others, where the
    program as it is passes on the same seed."""
    from chipbench.harness import check, loop

    cell = _cell(root)
    from chipbench.harness.cell import _module

    faults = _module(root, "configs", "toy_ling3", "faults")
    spec, cfg, seed = cell.spec, cell.cfg, 3
    pool = loop.make_pool(cell.build, cfg, spec, seed)
    ref = check.follow(cell.reference, cfg, "float32",
                       cell.reference.init_params(cfg, seed),
                       pool[:spec["check_steps"]], spec)
    lines = []
    sound = check.compare(faults.first_step(cell, seed), ref)
    assert check.verdict(sound, spec["limits"], lines.append), lines
    broken = check.compare(faults.first_step(cell, seed, fault), ref)
    assert not check.verdict(broken, spec["limits"], lines.append), lines
    assert broken["loss_gap"][0] > 3 * spec["limits"]["loss_gap"], lines
    assert broken["loss_gap"][0] > 10 * sound["loss_gap"][0]


def test_the_new_readers_on_a_made_up_trace(root, monkeypatch):
    """``kda_device_ms`` and ``mixer_gate_device_ms`` take the ops the
    table resolves to their parts; ``kda_fwd_roofline`` the ops under
    ``mxnet_kda_fwd`` (the checkpoints' too, so two passes a layer) against
    the passes' least time; ``mla_attn_fwd_roofline`` the causal kernel's
    events.  A program with none of them (the parent) gives None."""
    from chipbench.layer_metrics import (_scopes, kda_device_ms,
                                         kda_fwd_roofline,
                                         mixer_gate_device_ms,
                                         mla_attn_fwd_roofline)
    from chipbench.harness.peaks import peaks_of

    cell = _cell(root)
    counts, cfg, peaks = cell.build.counts, cell.cfg, peaks_of(V5E)
    seq = cell.spec["seq"]
    a_pass = 2 * max(counts.kda_fwd_flops(cfg, seq) / peaks["flops_bf16"],
                     counts.kda_fwd_bytes(cfg, seq, 2)
                     / peaks["hbm_bytes_per_s"])
    a_call = 2 * max(counts.mla_attention_fwd_flops(cfg, seq)
                     / peaks["flops_bf16"],
                     counts.mla_attention_fwd_bytes(cfg, seq, 2)
                     / peaks["hbm_bytes_per_s"])
    fwd = "jit(train_step)/mx_forward/mx_kda/mxnet_kda_fwd/dot"
    again = ("jit(train_step)/transpose(jvp(mx_forward))/checkpoint/"
             "rematted_computation/mx_kda/mxnet_kda_fwd/dot")
    bwd = ("jit(train_step)/transpose(jvp(mx_forward))/checkpoint/mx_kda/"
           "mxnet_kda_bwd/dot")
    gate = "jit(train_step)/mx_forward/mx_mixer_gate/mul"
    kernel = "%mxnet_flash_attention_fwd.{} = bf16[] custom-call()"
    # 2 steps, 6 delta-rule layers, two passes each: 24 passes in all, here
    # at 5 times their least time, in two ops
    ops = [["%fusion.1 = f32[] fusion()", 0.000, 12 * 5 * a_pass],
           ["%fusion.2 = f32[] fusion()", 0.010, 12 * 5 * a_pass],
           ["%fusion.3 = f32[] fusion()", 0.020, 0.004],
           ["%fusion.4 = f32[] fusion()", 0.025, 0.003],
           [kernel.format(5), 0.030, 2 * a_call],
           [kernel.format(6), 0.032, 2 * a_call]]
    table = {"fusion.1": {"scope": fwd, "classes": ["forward"],
                          "part": "mx_kda"},
             "fusion.2": {"scope": again, "classes": ["backward"],
                          "part": "mx_kda"},
             "fusion.3": {"scope": bwd, "classes": ["backward"],
                          "part": "mx_kda"},
             "fusion.4": {"scope": gate, "classes": ["forward"],
                          "part": "mx_mixer_gate"}}
    ctx = {"cfg": cfg, "cell": cell.spec, "build": cell.build, "chips": 1,
           "peaks": peaks, "trace": {"devices": {"0": {"ops": ops}}},
           "window": (0.0, 0.04), "steps": 2}
    monkeypatch.setattr(_scopes, "step_table", lambda: table)
    assert kda_fwd_roofline.read(ctx) == pytest.approx(20.0)
    assert mla_attn_fwd_roofline.read(ctx) == pytest.approx(50.0)
    assert kda_device_ms.read(ctx) == pytest.approx(
        (24 * 5 * a_pass + 0.004) / 2 * 1e3)
    assert mixer_gate_device_ms.read(ctx) == pytest.approx(1.5)
    # the parent's program: no op of those scopes, no kernel in the trace
    ctx = dict(ctx, trace={"devices": {"0": {"ops": [
        ["%fusion.9 = f32[] fusion()", 0.0, 0.01]]}}})
    ctx.pop("_part_split", None)
    table.clear()
    table["fusion.9"] = {"scope": "jit(train_step)/mx_forward/mx_ffn/dot",
                         "classes": ["forward"], "part": "mx_ffn"}
    for reader in (kda_fwd_roofline, mla_attn_fwd_roofline, kda_device_ms,
                   mixer_gate_device_ms):
        assert reader.read(ctx) is None
    # the grouped products over the sparse layers alone: 6 of the 7, each
    # with 64 pairs a step here, bound by the held experts' weights
    from chipbench.layer_metrics import _moe, moe_gmm_sparse_roofline

    least = 3 * 6 * (counts.expert_weight_bytes(cfg, 2)
                     + 64 * counts.routed_pair_bytes(cfg, 2)) \
        / peaks["hbm_bytes_per_s"] * 1e3
    monkeypatch.setattr(_moe, "pairs_per_step", lambda ctx: 6 * 64)
    monkeypatch.setattr(_moe, "scope_ms",
                        lambda ctx, scope, grouped=False: 4 * least)
    assert moe_gmm_sparse_roofline.read(ctx) == pytest.approx(25.0)
    monkeypatch.setattr(_moe, "scope_ms", lambda *a, **kw: None)
    assert moe_gmm_sparse_roofline.read(ctx) is None
    # and a configuration without the mixers reads no roofline of them
    from chipbench.harness.cell import Cell

    other = Cell("trinity_mini.causal_seq8192.fused")
    ctx = dict(ctx, cfg=other.cfg, cell=other.spec, build=other.build)
    assert kda_fwd_roofline.read(ctx) is None
    assert mla_attn_fwd_roofline.read(ctx) is None
    assert moe_gmm_sparse_roofline.read(ctx) is None
