"""The ``mellum2_12b_a2p5b`` configuration at a toy width through the
harness, on the CPU: the cell's files load, a run is correct and counts its
pairs, the check separates the lower precision, the counts are those of
brute force, the batches are the cell's documents in a seeded order, the
reference in blocks is itself unblocked, and the readers this PR brings
read a made-up trace."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

import toy
import toy_mellum2

sys.path.insert(0, os.path.join(toy.REPO, "chipbench"))

V5E = "TPU v5 lite"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy_mellum2.make_root(tmp_path_factory.mktemp("chipbench_mellum2"))


def _cell(root):
    from chipbench.harness.cell import Cell

    return Cell(toy_mellum2.CELL, root=root)


def test_the_real_cell_is_found_with_its_files():
    from chipbench.harness.cell import Cell

    cell = Cell(toy_mellum2.LIKE)
    spec, cfg = cell.spec, cell.cfg
    assert cell.chips == 1 and spec["batch"] * spec["seq"] == 16384
    assert sum(spec["documents"]) == spec["seq"]
    assert len(spec["documents"]) == 13
    assert not any(n % 128 == 0 for n in spec["documents"])
    shapes = cell.reference.param_shapes(cfg)
    n = sum(int(np.prod(shape)) for shape, _ in shapes.values())
    # the issue's reckoning: 340.4M parameters, 5.07 GiB at 16 bytes
    assert n == 340_350_208 and 5.06 < n * 16 / 2 ** 30 < 5.08
    assert cell.build.train_flops_per_sample(cfg, spec) \
        == pytest.approx(16.5e12, rel=5e-3)
    counts = cell.build.counts
    assert counts.visible_pairs(cfg, spec["documents"], "full_attention") \
        == 22_975_232
    assert counts.visible_pairs(cfg, spec["documents"], "sliding_attention") \
        == 11_849_883
    # every width as published, the cut in the three keys `reduced` names,
    # the published lists of layer kinds and the RoPE parameters whole
    source = {"hidden_size": 2304, "head_dim": 128, "intermediate_size": 7168,
              "moe_intermediate_size": 896, "num_attention_heads": 32,
              "num_key_value_heads": 4, "num_experts_per_tok": 8,
              "sliding_window": 1024, "max_position_embeddings": 131072,
              "norm_topk_prob": True, "rms_norm_eps": 1e-06}
    assert {k: cfg[k] for k in source} == source
    assert cfg["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                                "vocab_size": 98304}
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == 28
    assert cfg["rope_parameters"]["full_attention"] == {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
    assert cfg["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 500000}
    assert sorted(cfg["reduced"]) == sorted(cfg["published"])
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"packed_full_attn_fwd_roofline", "attn_visible_over_walked",
            "packed_window_attn_fwd_roofline", "moe_gmm_roofline",
            "moe_experts_device_ms", "moe_route_device_ms",
            "moe_load_max_over_mean", "mfu", "attn_bwd_device_ms"} <= names
    assert not names & {"flash_fwd_roofline", "blockdiff_attn_fwd_roofline",
                        "window_attn_fwd_roofline", "moe_shared_device_ms",
                        "attn_plain_fwd_device_ms", "allreduce_exposed_ms"}


def test_the_short_bert_cell_is_found_with_its_reader():
    from chipbench.harness.cell import Cell

    cell, long = Cell("bert_base.seq128.fused"), Cell("bert_base.seq512.fused")
    assert cell.chips == 1 and cell.cfg == long.cfg
    assert cell.spec["batch"] * cell.spec["seq"] \
        == long.spec["batch"] * long.spec["seq"] == 8192
    assert cell.spec["seq"] == 128 and cell.spec["check_steps"] == 3
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {m["name"] for m in long.metrics("per_layer")} \
        - {"flash_fwd_roofline"} == names - {"attn_plain_fwd_device_ms"}
    assert "attn_plain_fwd_device_ms" in names


def test_a_toy_run_is_correct_and_counts_its_pairs(root):
    import jax
    import run
    from chipbench.layer_metrics import _scopes, attn_visible_over_walked
    from chipbench.harness.peaks import peaks_of
    from mxnet_tpu import telemetry

    telemetry.reset()
    cell = _cell(root)
    result = run.run_cell(cell, jax.devices()[:1], peaks_of(V5E), 2147483653,
                          1.0, False)
    assert result["correct"] is True
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["metrics"]) == {"samples_per_s_per_chip", "step_ms_p95",
                                      "peak_hbm_gib", "setup_s"}
    # the assumed routers: each of the 64 tokens sends this share one pair
    # in each of the 4 layers
    pairs = _scopes.sample("mxnet_moe_routed_pairs_total")["value"]
    load = _scopes.sample("mxnet_moe_expert_load_max_over_mean")
    assert pairs / (load["count"] / 4) == pytest.approx(4 * 64, rel=5e-3)
    # the pairs the masks show inside the documents, over the whole 32 x 32
    # of the plain path, three window layers and a full one a sample
    counts, docs = cell.build.counts, cell.spec["documents"]
    shown = 3 * counts.visible_pairs(cell.cfg, docs, "sliding_attention") \
        + counts.visible_pairs(cell.cfg, docs, "full_attention")
    assert attn_visible_over_walked.read({}) \
        == pytest.approx(shown / (4 * 32 * 32))
    # (one program for every order of the documents: ``correct`` holds
    # "nothing compiled inside the window", whose batches differ in order)


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


def test_counts_against_a_brute_force_count_of_the_mask(root):
    """``counts.py`` never sees the order; the reference's own mask over a
    shuffled row shows as many pairs."""
    cell = _cell(root)
    counts, cfg, reference = cell.build.counts, cell.cfg, cell.reference
    rng = np.random.default_rng(5)
    for docs, window in (([13, 9, 5, 3, 2], 8), ([1, 30, 1], 8),
                         ([40, 7, 17], 16)):
        order = rng.permutation(len(docs))
        seg = np.repeat(np.arange(len(docs)), np.asarray(docs)[order])
        rows = np.arange(len(seg))
        for kind, w in (("full_attention", 0), ("sliding_attention", window)):
            seen = np.asarray(reference.visible(rows, rows, seg, seg, w))
            assert counts.visible_pairs(dict(cfg, sliding_window=window),
                                        docs, kind) == seen.sum()
    assert counts.layer_types(cfg) == ["sliding_attention"] * 3 + [
        "full_attention"]
    # a toy sample's forward pass, product by product
    h, hd, length, docs = 64, 16, 32, cell.spec["documents"]
    layer = 2 * length * h * hd * (4 + 2 + 2 + 4)
    attention = 4 * 4 * hd * (
        3 * sum(counts.window_pairs(n, 8) for n in docs)
        + sum(counts.causal_pairs(n) for n in docs))
    sparse = length * (2 * h * 8 + 2 * 3 * h * 32)
    head = 2 * length * h * 96
    assert counts.forward_flops_per_sample(cfg, docs) \
        == 4 * (layer + sparse) + attention + head
    assert counts.attention_fwd_bytes(cfg, length, 2) \
        == 4 * length * (4 * hd * 2 + 4) + 4 * length
    assert counts.pairs_per_token(cfg) == 1.0


def test_batches_are_the_documents_in_a_seeded_order(root):
    from chipbench.harness import loop

    cell = _cell(root)
    pool = loop.make_pool(cell.build, cell.cfg, cell.spec, 2147483659)
    again = loop.make_pool(cell.build, cell.cfg, cell.spec, 2147483659)
    orders = set()
    for ((ids, seg), labels), ((ids2, seg2), labels2) in zip(pool, again):
        assert ids.shape == seg.shape == labels.shape == (2, 32)
        assert ids.dtype == seg.dtype == labels.dtype == np.int32
        assert (ids == ids2).all() and (seg == seg2).all()
        assert 0 <= ids.min() and ids.max() < cell.cfg["vocab_size"]
        assert (ids[:, 1:] == labels[:, :-1]).all()
        for row in seg:
            runs = np.flatnonzero(np.diff(row)) + 1
            lengths = np.diff(np.r_[0, runs, len(row)])
            assert sorted(lengths) == sorted(cell.spec["documents"])
            assert (row == np.repeat(np.arange(len(lengths)), lengths)).all()
            orders.add(tuple(lengths))
    assert len(orders) > 8      # the boundaries move from batch to batch


def test_the_reference_in_blocks_is_itself_unblocked(root):
    import jax

    cell = _cell(root)
    params = cell.reference.init_params(cell.cfg, 7)
    batch = cell.build.make_batch(cell.cfg, cell.spec,
                                  np.random.default_rng(7))
    whole = cell.reference.loss_and_grads(cell.cfg, "float32", params, batch,
                                          0)
    blocks = cell.reference.loss_and_grads(cell.cfg, "float32", params, batch,
                                           8)
    assert float(whole[0]) == pytest.approx(float(blocks[0]), rel=1e-6)
    for leaf, got in jax.device_get(blocks[1]).items():
        np.testing.assert_allclose(got, whole[1][leaf], rtol=1e-4, atol=1e-7,
                                   err_msg=leaf)


def test_the_new_readers_on_a_made_up_trace(root, monkeypatch):
    """The two rooflines take their own kernel's events alone (the full
    layers' under ids, the window layers' under ids; neither a kernel
    without ids) against the least time of the pairs inside the documents;
    ``attn_plain_fwd_device_ms`` the ops under ``mxnet_attention_plain_fwd``.
    A program with none of it (the parent) gives None everywhere."""
    from chipbench.layer_metrics import (_scopes, attn_plain_fwd_device_ms,
                                         attn_visible_over_walked,
                                         packed_full_attn_fwd_roofline,
                                         packed_window_attn_fwd_roofline)
    from chipbench.harness.peaks import peaks_of
    from mxnet_tpu import telemetry

    cell = _cell(root)
    counts, cfg, peaks = cell.build.counts, cell.cfg, peaks_of(V5E)
    docs = cell.spec["documents"]

    def least(kind):
        return max(2 * counts.attention_fwd_flops(cfg, docs, kind)
                   / peaks["flops_bf16"],
                   2 * counts.attention_fwd_bytes(cfg, 32, 2)
                   / peaks["hbm_bytes_per_s"])

    kernel = "%mxnet_flash_attention_fwd{}.{} = bf16[] custom-call()"
    full, band = least("full_attention"), least("sliding_attention")
    ops = [[kernel.format("_segments", 1), 0.000, 10 * full],
           [kernel.format("_window_segments", 2), 0.010, 4 * band],
           [kernel.format("_window_segments", 3), 0.015, 4 * band],
           [kernel.format("_window", 4), 0.020, 0.003],
           [kernel.format("", 5), 0.025, 0.003],
           ["%fusion.6 = f32[] fusion()", 0.030, 0.002],
           ["%fusion.7 = f32[] fusion()", 0.033, 0.004]]
    table = {
        "fusion.6": {"scope": "jit(train_step)/mx_forward/"
                     "mxnet_attention_plain_fwd/dot", "classes": ["forward"]},
        "fusion.7": {"scope": "jit(train_step)/mx_forward/dot",
                     "classes": ["forward"]}}
    ctx = {"cfg": cfg, "cell": cell.spec, "build": cell.build, "chips": 1,
           "peaks": peaks, "trace": {"devices": {"0": {"ops": ops}}},
           "window": (0.0, 0.04), "steps": 2}
    monkeypatch.setattr(_scopes, "step_table", lambda: table)
    assert packed_full_attn_fwd_roofline.read(ctx) == pytest.approx(10.0)
    assert packed_window_attn_fwd_roofline.read(ctx) == pytest.approx(25.0)
    assert attn_plain_fwd_device_ms.read(ctx) == pytest.approx(1.0)
    telemetry.reset()
    telemetry.ATTENTION_VISIBLE_PAIRS.inc(30.0)
    telemetry.ATTENTION_WALKED_PAIRS.inc(120.0)
    assert attn_visible_over_walked.read(ctx) == pytest.approx(0.25)
    # the parent's program: no kernel under ids, no such scope, no counters
    ctx["trace"]["devices"]["0"]["ops"] = ops[3:5] + ops[6:]
    del table["fusion.6"]
    telemetry.reset()
    assert packed_full_attn_fwd_roofline.read(ctx) is None
    assert packed_window_attn_fwd_roofline.read(ctx) is None
    assert attn_plain_fwd_device_ms.read(ctx) is None
    assert attn_visible_over_walked.read(ctx) is None
    monkeypatch.delitem(telemetry._FAMILIES,
                        "mxnet_attention_walked_pairs_total")
    assert attn_visible_over_walked.read(ctx) is None
    # a cell that packs no documents reads nothing either
    ctx["trace"]["devices"]["0"]["ops"] = ops
    ctx["cell"] = {k: v for k, v in cell.spec.items() if k != "documents"}
    assert packed_full_attn_fwd_roofline.read(ctx) is None
