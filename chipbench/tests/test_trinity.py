"""The ``trinity_mini`` configuration at a toy width through the harness, on
the CPU: a run is correct and reports what a cell reports, the check
separates the lower precision, the counts are those of brute force, and the
two readers this configuration brings read a made-up trace."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

import toy
import toy_trinity

sys.path.insert(0, os.path.join(toy.REPO, "chipbench"))

V5E = "TPU v5 lite"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy_trinity.make_root(tmp_path_factory.mktemp("chipbench_trinity"))


def _cell(root):
    from chipbench.harness.cell import Cell

    return Cell(toy_trinity.CELL, root=root)


def test_the_real_cell_is_found_with_its_files():
    from chipbench.harness.cell import Cell

    cell = Cell(toy_trinity.LIKE)
    assert cell.chips == 1 and cell.spec["batch"] * cell.spec["seq"] == 8192
    shapes = cell.reference.param_shapes(cell.cfg)
    n = sum(int(np.prod(shape)) for shape, _ in shapes.values())
    # the issue's reckoning: 504.1M parameters, 7.51 GiB at 16 bytes
    assert n == 504_147_200 and 7.50 < n * 16 / 2 ** 30 < 7.52
    assert cell.build.train_flops_per_sample(cell.cfg, cell.spec) \
        == pytest.approx(18.1e12, rel=5e-3)
    # every width as published, the cut in the four keys `reduced` names,
    # the published list of layer kinds whole
    source = {"hidden_size": 2048, "head_dim": 128, "intermediate_size": 6144,
              "moe_intermediate_size": 1024, "num_attention_heads": 32,
              "num_key_value_heads": 4, "num_experts_per_tok": 8,
              "num_shared_experts": 1, "sliding_window": 2048,
              "route_scale": 2.826, "rope_theta": 10000}
    assert {k: cell.cfg[k] for k in source} == source
    assert cell.cfg["published"] == {
        "num_hidden_layers": 32, "num_dense_layers": 2, "num_experts": 128,
        "vocab_size": 200192}
    assert len(cell.cfg["layer_types"]) == 32
    assert sorted(cell.cfg["reduced"]) == sorted(cell.cfg["published"])
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"window_attn_fwd_roofline", "moe_shared_device_ms",
            "moe_experts_device_ms", "moe_route_device_ms",
            "moe_load_max_over_mean", "mfu", "attn_bwd_device_ms"} <= names
    assert not names & {"flash_fwd_roofline", "blockdiff_attn_fwd_roofline",
                        "moe_gmm_roofline", "allreduce_exposed_ms"}


def test_the_four_chip_cell_is_found_with_its_driver():
    from chipbench.harness.cell import Cell

    cell = Cell("bert_base.seq512.dp4")
    one = Cell("bert_base.seq512.fused")
    assert cell.chips == 4 and cell.spec["batch"] == 4 * one.spec["batch"]
    assert cell.spec["limits"] == one.spec["limits"]
    assert cell.driver.Runner.__mro__[1].__name__ == "Runner"
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert "allreduce_exposed_ms" in names
    assert {m["name"] for m in one.metrics("per_layer")} \
        == names - {"allreduce_exposed_ms"}


def test_the_mesh_driver_runs_a_toy_cell_correct(root):
    """``fused_step_mesh`` over as many of four devices as JAX has here
    (``XLA_FLAGS=--xla_force_host_platform_device_count=4`` gives the CPU
    four): the toy BERT cell's file with the driver and the batch changed,
    under the same limits, as the real four-chip cell is made."""
    import json

    import jax
    import run
    from chipbench.harness.cell import Cell
    from chipbench.harness.peaks import peaks_of

    chips = min(4, len(jax.devices()))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(root, "chipbench", "workloads",
                           "toy_bert.fused.json")) as f:
        spec = json.load(f)
    spec.update(driver="fused_step_mesh", chips=chips, batch=2 * chips)
    with open(os.path.join(root, "chipbench", "workloads",
                           "toy_bert.dp.json"), "w") as f:
        json.dump(spec, f)
    bench["workloads"].append({"name": "toy_bert.dp", "config": "toy_bert",
                               "traffic": "dp", "chips": chips,
                               "why": "toy width"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = Cell("toy_bert.dp", root=root)
    result = run.run_cell(cell, jax.devices()[:chips], peaks_of(V5E),
                          2147483652, 0.5, False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] == chips


def test_a_toy_run_is_correct_and_counts_its_pairs(root):
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
    # the assumed routers: each of the 64 tokens sends this share one pair
    # in each of the 4 sparse layers; Adam then moves a column's copies
    # apart by 1e-6 a step, and a near-tie in a thousand falls otherwise
    pairs = _scopes.sample("mxnet_moe_routed_pairs_total")["value"]
    load = _scopes.sample("mxnet_moe_expert_load_max_over_mean")
    assert pairs / (load["count"] / 4) == pytest.approx(4 * 64, rel=5e-3)
    assert 1.0 <= moe_load_max_over_mean.read({"cfg": cell.cfg}) <= 2.0


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


def test_counts_against_brute_force(root):
    cell = _cell(root)
    counts, cfg = cell.build.counts, cell.cfg
    for length, window in ((32, 8), (8, 8), (5, 8), (96, 40)):
        pairs = sum(1 for i in range(length) for j in range(length)
                    if i - window < j <= i)
        assert counts.window_pairs(length, window) == pairs
        assert counts.causal_pairs(length) == sum(
            1 for i in range(length) for j in range(i + 1))
    assert counts.layer_types(cfg) == ["sliding_attention"] * 3 + [
        "full_attention", "sliding_attention"]
    assert counts.sparse_layers(cfg) == 4
    # a toy sample's forward pass, product by product
    h, hd, length = 64, 16, 32
    layer = 2 * length * h * hd * (4 + 2 + 2 + 4 + 4)
    attention = 4 * 4 * hd * (4 * counts.window_pairs(32, 8)
                              + counts.causal_pairs(32))
    dense = 3 * 2 * length * h * 96
    sparse = length * (2 * h * 8 + 2 * 3 * 2 * h * 32)
    head = 2 * length * h * 96
    assert counts.forward_flops_per_sample(cfg, length) \
        == 5 * layer + attention + dense + 4 * sparse + head
    assert counts.attention_fwd_bytes(cfg, length, 2) \
        == 4 * length * (4 * hd * 2 + 4)
    # a share the bias does not favour is routed nothing
    assert counts.pairs_per_token(dict(cfg, experts_first=4)) == 0.0


def test_the_new_readers_on_a_made_up_trace(root, monkeypatch):
    """``window_attn_fwd_roofline`` takes the window kernel's events alone
    (the full layer's kernel has another name) against the band's least
    time; ``moe_shared_device_ms`` the ops under ``mx_moe_shared``, forward
    and backward.  A program with neither (the parent) gives None."""
    from chipbench.layer_metrics import (_scopes, moe_shared_device_ms,
                                         window_attn_fwd_roofline)
    from chipbench.harness.peaks import peaks_of

    cell = _cell(root)
    counts, cfg, peaks = cell.build.counts, cell.cfg, peaks_of(V5E)
    least = max(2 * counts.attention_fwd_flops(cfg, 32, "sliding_attention")
                / peaks["flops_bf16"],
                2 * counts.attention_fwd_bytes(cfg, 32, 2)
                / peaks["hbm_bytes_per_s"])
    kernel = "%mxnet_flash_attention_fwd{}.{} = bf16[] custom-call()"
    ops = [[kernel.format("_window", 1), 0.000, 4 * least],
           [kernel.format("_window", 2), 0.010, 4 * least],
           [kernel.format("", 3), 0.020, 0.005],
           ["%fusion.4 = bf16[] fusion()", 0.030, 0.002],
           ["%fusion.5 = bf16[] fusion()", 0.033, 0.001],
           ["%fusion.6 = f32[] fusion()", 0.035, 0.004]]
    table = {
        "fusion.4": {"scope": "jit(train_step)/mx_forward/mx_moe_shared/dot",
                     "classes": ["forward"]},
        "fusion.5": {"scope": "jit(train_step)/transpose(jvp(mx_forward))/"
                     "mx_moe_shared/dot", "classes": ["backward"]},
        "fusion.6": {"scope": "jit(train_step)/mx_forward/mx_moe_route/dot",
                     "classes": ["forward"]}}
    ctx = {"cfg": cfg, "cell": cell.spec, "build": cell.build, "chips": 1,
           "peaks": peaks, "trace": {"devices": {"0": {"ops": ops}}},
           "window": (0.0, 0.04), "steps": 2}
    monkeypatch.setattr(_scopes, "step_table", lambda: table)
    assert window_attn_fwd_roofline.read(ctx) == pytest.approx(25.0)
    assert moe_shared_device_ms.read(ctx) == pytest.approx(1.5)
    # the parent's program: no kernel of that name, no such scope
    ctx["trace"]["devices"]["0"]["ops"] = ops[2:3] + ops[5:]
    del table["fusion.4"], table["fusion.5"]
    assert window_attn_fwd_roofline.read(ctx) is None
    assert moe_shared_device_ms.read(ctx) is None


def test_exposed_all_reduce_counts_start_and_done_once():
    """``allreduce_exposed_ms``: the all-reduce ops of the ``XLA Ops`` line,
    ``-start`` and ``-done`` alike, every instant once, a step a chip; a
    one-chip trace holds none and reads None."""
    from chipbench.layer_metrics import allreduce_exposed_ms

    ops = [["%fusion.1 = f32[] fusion()", 0.000, 0.010],
           ["%all-reduce-start.2 = f32[] all-reduce-start()", 0.010, 0.001],
           ["%fusion.3 = f32[] fusion()", 0.011, 0.004],
           ["%all-reduce-done.2 = f32[] all-reduce-done()", 0.015, 0.003],
           ["%all-reduce.7 = f32[] all-reduce()", 0.020, 0.002]]
    ctx = {"trace": {"devices": {"0": {"ops": ops}, "1": {"ops": ops}}},
           "window": (0.0, 0.03), "steps": 2}
    assert allreduce_exposed_ms.read(ctx) == pytest.approx(3.0)
    ctx["trace"]["devices"] = {"0": {"ops": ops[:1] + ops[2:3]}}
    assert allreduce_exposed_ms.read(ctx) is None
