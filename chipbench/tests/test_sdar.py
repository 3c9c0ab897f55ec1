"""The ``sdar_30b_a3b`` configuration at a toy width through the harness, on
the CPU: a run is correct and reports what a cell reports, the check
separates the lower precision, and the expert layer's readers read the
program's counters and scopes."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

import toy
import toy_sdar

sys.path.insert(0, os.path.join(toy.REPO, "chipbench"))

V5E = "TPU v5 lite"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy_sdar.make_root(tmp_path_factory.mktemp("chipbench_sdar"))


def _cell(root):
    from chipbench.harness.cell import Cell

    return Cell(toy_sdar.CELL, root=root)


def test_the_real_cell_is_found_with_its_files():
    from chipbench.harness.cell import Cell

    cell = Cell(toy_sdar.LIKE)
    assert cell.chips == 1 and cell.spec["batch"] * cell.spec["seq"] == 8192
    shapes = cell.reference.param_shapes(cell.cfg)
    n = sum(int(np.prod(shape)) for shape, _ in shapes.values())
    # the issue's reckoning: 456.4M parameters, 6.80 GiB at 16 bytes
    assert n == 456_346_624 and 6.79 < n * 16 / 2 ** 30 < 6.81
    assert cell.build.train_flops_per_sample(cell.cfg, cell.spec) * 2 \
        == pytest.approx(17.9e12, rel=3e-3)
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"moe_experts_device_ms", "moe_route_device_ms",
            "moe_gmm_roofline", "blockdiff_attn_fwd_roofline",
            "moe_load_max_over_mean", "mfu", "attn_bwd_device_ms"} <= names
    assert "flash_fwd_roofline" not in names


def test_a_toy_run_is_correct_and_counts_its_pairs(root):
    import jax
    import run
    from chipbench.harness.peaks import peaks_of
    from chipbench.layer_metrics import _moe, moe_load_max_over_mean
    from mxnet_tpu import telemetry

    telemetry.reset()
    cell = _cell(root)
    result = run.run_cell(cell, jax.devices()[:1], peaks_of(V5E), 3, 1.0,
                          False)
    assert result["correct"] is True
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["metrics"]) == {"samples_per_s_per_chip", "step_ms_p95",
                                      "peak_hbm_gib", "setup_s"}
    # the counters of the expert layer, as the readers see them: the
    # routers' columns are one share's for every share, so each of the 128
    # tokens sends this share one pair a layer (2 layers); Adam then moves
    # the copies apart by 1e-6 a step, and a near-tie in a hundred falls
    # otherwise
    ctx = {"cfg": cell.cfg}
    per_step = _moe.pairs_per_step(ctx)
    assert per_step == pytest.approx(2 * 128, rel=5e-3)
    assert 1.0 <= moe_load_max_over_mean.read(ctx) <= 4.0


@pytest.mark.parametrize("seed", range(1, 7))
def test_check_separates_the_lower_precision(root, seed):
    import limits
    from chipbench.harness import check

    cell = _cell(root)
    row = limits.read_seed(cell, seed)
    lines = []
    assert check.verdict(row["sound"], cell.spec["limits"], lines.append), lines
    assert not check.verdict(row["control"], cell.spec["limits"],
                             lines.append), lines
    assert row["control"]["first_gradient_error"][0] \
        > 3 * row["sound"]["first_gradient_error"][0]


def test_scope_reader_gives_the_innermost_op_its_time(monkeypatch):
    """``_moe.scope_ms`` on a made-up trace: a ``while`` op covers the
    grouped product inside it, whose time is the experts' and not counted
    twice; an op outside the scope is left out; no table, no number."""
    from chipbench.layer_metrics import _moe, _scopes

    table = {
        "while.1": {"scope": "jit(train_step)/mx_forward/while", "classes": []},
        "ragged-dot.2": {"scope": "jit(train_step)/mx_forward/while/body/"
                         "mx_moe_experts/ragged_dot", "classes": []},
        "fusion.3": {"scope": "jit(train_step)/transpose(jvp(mx_forward))/"
                     "mx_moe_route/gather", "classes": []},
        "fusion.4": {"scope": "jit(train_step)/mx_optimizer/add",
                     "classes": []}}
    ops = [["%while.1 = f32[] while()", 0.0, 0.010],
           ["%ragged-dot.2 = bf16[] custom-call()", 0.002, 0.004],
           ["%fusion.3 = f32[] fusion()", 0.012, 0.003],
           ["%fusion.4 = f32[] fusion()", 0.016, 0.002]]
    ctx = {"trace": {"devices": {"0": {"ops": ops}}}, "window": (0.0, 0.02),
           "steps": 2}
    monkeypatch.setattr(_scopes, "step_table", lambda: table)
    assert _moe.scope_ms(ctx, "mx_moe_experts") == pytest.approx(2.0)
    assert _moe.scope_ms(ctx, "mx_moe_experts", grouped=True) \
        == pytest.approx(2.0)        # a product that kept the scope
    # XLA's own name for the grouped product counts through the table
    table["ragged-dot-none.7"] = {"scope": "ragged-dot-none", "classes": []}
    ops.append(["%ragged-dot-none.7 = bf16[] custom-call()", 0.0185, 0.001])
    assert _moe.scope_ms(ctx, "mx_moe_experts", grouped=True) \
        == pytest.approx(2.5)
    assert _moe.scope_ms(ctx, "mx_moe_experts") == pytest.approx(2.0)
    assert _moe.scope_ms(ctx, "mx_moe_route") == pytest.approx(1.5)
    assert _moe.scope_ms(ctx, "mx_no_such_scope") is None
    # no grouped product in the table, or none in the trace: no number,
    # not the scope's other ops alone
    renamed = {name.replace("ragged-dot", "grouped-mm"): dict(
        row, scope=row["scope"].replace("ragged", "grouped"))
        for name, row in table.items()}
    monkeypatch.setattr(_scopes, "step_table", lambda: renamed)
    assert _moe.scope_ms(ctx, "mx_moe_experts") is not None
    assert _moe.scope_ms(ctx, "mx_moe_experts", grouped=True) is None
    monkeypatch.setattr(_scopes, "step_table", lambda: table)
    ctx["trace"]["devices"]["0"]["ops"] = [ops[0], ops[2], ops[3]]
    assert _moe.scope_ms(ctx, "mx_moe_experts", grouped=True) is None
    monkeypatch.setattr(_scopes, "step_table", lambda: None)
    assert _moe.scope_ms(ctx, "mx_moe_experts") is None


def test_grouped_products_are_found_in_what_the_chip_recorded(monkeypatch):
    """One forward and backward of the expert layer on the v5e (16 experts
    held of 128, 8 a token; ``chipbench/testdata/moe_step.*``: the table
    the program built for that executable and the trace's ``XLA Ops``): the
    grouped products carry XLA's own name in both, the reader finds them
    through the table, and they are most of what it reads."""
    import gzip
    import json

    from chipbench.harness import trace
    from chipbench.layer_metrics import _moe, _scopes

    def recorded(name):
        path = os.path.join(toy.REPO, "chipbench", "testdata",
                            f"moe_step.{name}.json.gz")
        with gzip.open(path, "rt") as f:
            return json.load(f)

    table, events = recorded("op_scopes"), recorded("trace_events")
    products = {name for name, row in table.items()
                if row["scope"].startswith(_moe.GROUPED_XLA)}
    assert products and all(name.startswith(_moe.GROUPED_XLA)
                            for name in products)
    shown = {trace.op_name(e[0]) for e in events["devices"]["0"]["ops"]}
    assert products & shown
    monkeypatch.setattr(_scopes, "step_table", lambda: table)
    t0 = min(e[1] for e in events["devices"]["0"]["ops"])
    t1 = max(e[1] + e[2] for e in events["devices"]["0"]["ops"])
    ctx = {"trace": events, "window": (t0, t1), "steps": 1}
    with_products = _moe.scope_ms(ctx, "mx_moe_experts", grouped=True)
    without = _moe.scope_ms(ctx, "mx_moe_experts")
    assert with_products > 2 * without > 0
    assert _moe.scope_ms(ctx, "mx_moe_route") > 0


def test_flipped_share_counts_tokens_whose_experts_change(root):
    """``flips.py`` at the toy width: a share in [0, 1) a layer, no flip
    between a precision and itself."""
    import jax.numpy as jnp

    from chipbench.configs.sdar_30b_a3b import flips

    cell = _cell(root)
    params = cell.reference.init_params(cell.cfg, 2)
    ids = cell.build.make_batch(cell.cfg, cell.spec,
                                np.random.default_rng(2))[0]
    shares = flips.flipped_share(cell.reference, cell.cfg, params, ids)
    assert len(shares) == 2 and all(0.0 <= s < 0.5 for s in shares)
    same = [cell.reference.routes(cell.cfg, "float32", params, ids)
            for _ in range(2)]
    assert bool(jnp.all(same[0] == same[1]))
    assert same[0].shape == (2, 2, 64, 2)
