"""The benchmark's own tests: CPU, toy widths.

    python -m pytest chipbench/tests -q

They import the harness's loop, check and drivers and call them directly:
``run.py`` has no option that lifts the chip requirement, and its refusal
off the chip is tested.  No test describes a TPU topology.
"""
from __future__ import annotations

import gzip
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import toy

REPO = toy.REPO
sys.path.insert(0, os.path.join(REPO, "chipbench"))

TOY = "toy_bert.fused"
V5E = "TPU v5 lite"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy.make_root(tmp_path_factory.mktemp("chipbench"))


def _cell(root, name):
    from chipbench.harness.cell import Cell

    return Cell(name, root=root)


def _run(root, name=TOY, seed=3, seconds=1.0):
    import jax
    import run
    from chipbench.harness.peaks import peaks_of

    cell = _cell(root, name)
    return run.run_cell(cell, jax.devices()[:cell.chips], peaks_of(V5E), seed,
                        seconds, False)


# -- a run, with the look for a chip skipped ---------------------------------

def test_a_run_is_correct_and_counts_all_its_work(root):
    result = _run(root)
    assert result["correct"] is True
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["metrics"]) == {"samples_per_s_per_chip", "step_ms_p95",
                                      "peak_hbm_gib", "setup_s"}
    assert result["metrics"]["samples_per_s_per_chip"]["value"] > 0
    assert result["device"]["count"] == 1


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(
        root, monkeypatch):
    """The timed path broken underneath: the loss is computed and the
    update is thrown away."""
    from mxnet_tpu.parallel.data_parallel import TrainStep

    real = TrainStep.__call__

    def frozen(self, x, y):
        import jax.numpy as jnp

        keep = [{k: jnp.copy(v) for k, v in t.items()} for t in
                (self.train_params, self.rest_params)]
        loss = real(self, x, y)
        self.train_params, self.rest_params = keep
        return loss

    monkeypatch.setattr(TrainStep, "__call__", frozen)
    assert _run(root)["correct"] is False
    assert _cell(root, TOY).spec["limits"]["change_gap"] < 1.0


def test_part_of_the_batch_left_out_is_not_correct(root, monkeypatch):
    """The timed path broken underneath: the step sees the first half of
    its rows twice."""
    from mxnet_tpu.parallel.data_parallel import TrainStep

    real = TrainStep.__call__

    def half(self, x, y):
        import jax.numpy as jnp

        x, y = (getattr(v, "_get", lambda v=v: v)() for v in (x, y))
        n = x.shape[0] // 2
        return real(self, jnp.concatenate([x[:n], x[:n]]),
                    jnp.concatenate([y[:n], y[:n]]))

    monkeypatch.setattr(TrainStep, "__call__", half)
    assert _run(root)["correct"] is False


# -- the check: sound runs pass, the lower precision fails -------------------

@pytest.mark.parametrize("seed", range(1, 13))
def test_check_separates_the_lower_precision(root, seed):
    import limits
    from chipbench.harness import check

    cell = _cell(root, TOY)
    row = limits.read_seed(cell, seed)
    lines = []
    assert check.verdict(row["sound"], cell.spec["limits"], lines.append), lines
    assert not check.verdict(row["control"], cell.spec["limits"],
                             lines.append), lines
    # the number that separates them does so by three times or more
    assert row["control"]["first_gradient_error"][0] \
        > 3 * row["sound"]["first_gradient_error"][0]


def test_worst_leaf_is_floored_by_the_median_leaf():
    from chipbench.harness import check

    zeros = {"change_sq": {"a": 1.0, "b": 1.0, "c": 9.0}, "losses": [1.0]}
    ref = dict(zeros, first_gradient={"a": np.ones(4), "b": np.ones(4),
                                      "c": np.full(4, 1e-9)})
    got = dict(zeros, first_gradient={"a": np.ones(4), "b": np.ones(4) * 1.01,
                                      "c": np.full(4, 3e-9)})
    stats = check.compare(got, ref)
    # leaf c is all but zero: its threefold gap counts against the median leaf
    assert stats["first_gradient_gap"] == (pytest.approx(0.01), "b")
    # and its change, which is rounding noise through Adam, is not compared
    assert stats["change_gap"][0] == 0.0
    got["losses"] = [float("nan")]
    assert check.compare(got, ref)["loss_gap"][0] == math.inf


# -- the chip requirement ----------------------------------------------------

def test_run_refuses_off_the_chip():
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "bert_base.seq512.fused", "--seed", "1", "--seconds", "1"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 3
    assert "FAIL" in out.stderr and "{" not in out.stdout


def test_unknown_device_kind_is_an_error():
    from chipbench.harness.peaks import peaks_of

    assert peaks_of(V5E)["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks_of("TPU v5")


# -- driven by data ----------------------------------------------------------

def test_new_files_are_found_with_no_edit(root):
    """The toy configuration and its two cells are already new files in a
    copy.  A per-layer metric is one more file and one more entry."""
    with open(os.path.join(root, "chipbench", "layer_metrics",
                           "steps_seen.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx['steps']) or None\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["per_layer"].append({
        "name": "steps_seen", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "fused step",
        "moves": "samples_per_s_per_chip", "workloads": [TOY]})
    with open(path, "w") as f:
        json.dump(bench, f)
    cell = _cell(root, TOY)
    assert cell.cfg["hidden_size"] == 32
    assert "steps_seen" in [m["name"] for m in cell.metrics("per_layer")]
    assert cell.read_layer_metric("steps_seen", {"steps": 7}) == 7.0
    assert cell.read_layer_metric("steps_seen", {"steps": 0}) is None
    other = _cell(root, "toy_bert.short")
    assert (other.spec["seq"], other.driver is cell.driver) == (8, True)
    names = [m["name"] for m in other.metrics("per_layer")]
    # a metric with no list is every cell's; one with a list is the listed
    # cells' alone
    assert names == ["input_wait_ms", "step_device_ms", "device_idle_pct"]
    assert {"mfu", "flash_fwd_roofline"} <= {
        m["name"] for m in cell.metrics("per_layer")}


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for config in bench["configs"]:
        assert os.path.isfile(os.path.join(REPO, config["file"]))
    for cell in bench["workloads"]:
        spec = _cell(REPO, cell["name"]).spec
        assert spec["chips"] == cell["chips"]
        assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
        assert all(math.isfinite(v) and v < 1 for v in spec["limits"].values())
    for metric in bench["per_layer"]:
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "layer_metrics", metric["name"] + ".py"))
    four = [c for c in bench["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


# -- the loop's arithmetic ---------------------------------------------------

def test_the_window_counts_every_step_it_dispatched():
    import itertools

    from chipbench.harness import loop

    class Runner:
        def step(self, batch, spans):
            return 0.5

    win = loop.window(Runner(), itertools.repeat(None), 0.02, loop.Spans())
    # the steps in flight when the time is up are waited for and counted
    assert len(win["stamps"]) == win["attempted"] > loop.AHEAD
    assert win["stamps"] == sorted(win["stamps"]) and win["failed"] == 0


def test_step_times_and_percentile():
    from chipbench.harness import loop

    stamps = [0.1 * i for i in range(1, 31)]       # a step every 100 ms
    stamps[20:] = [t + 0.4 for t in stamps[20:]]   # and one stall
    times = loop.step_times_ms(stamps)
    # every gap is a reading, the first from the window's opening, and the
    # stall is one of them
    assert len(times) == 30 and sum(times) == pytest.approx(3400.0)
    assert sorted(times)[-2:] == pytest.approx([100.0, 500.0])
    assert loop.percentile(list(range(1, 101)), 0.95) == 95
    assert loop.percentile([5.0], 0.95) == 5.0


# -- operations and bytes -----------------------------------------------------

def _config(name):
    with open(os.path.join(REPO, "chipbench", "configs", name,
                           "config.json")) as f:
        return json.load(f)


def _xla_flops(fn, *shapes):
    import jax

    cost = jax.jit(fn).lower(*shapes).compile().cost_analysis()
    return (cost[0] if isinstance(cost, (list, tuple)) else cost)["flops"]


def test_bert_count_against_hand_value_and_xla():
    import jax
    import jax.numpy as jnp
    from chipbench.harness import counts, precision

    cfg = _config("bert_base")
    macs = counts.bert_forward_macs_per_token(cfg, 512)
    # by hand: 12 x (4 x 768^2 + 2 x 768 x 3072 + 2 x 512 x 768)
    #          + 768^2 + 768 x 30522 = 118,402,560
    assert macs == 118_402_560
    assert counts.bert_train_flops_per_sequence(cfg, 512) == 6 * macs * 512
    ref = _cell(REPO, "bert_base.seq512.fused").reference
    params = {k: jax.ShapeDtypeStruct(shape, jnp.float32)
              for k, (shape, _) in ref.param_shapes(cfg).items()}
    flops = _xla_flops(
        lambda p, ids: ref.forward(cfg, precision.ops("float32"), p, ids),
        params, jax.ShapeDtypeStruct((1, 512), jnp.int32))
    assert flops == pytest.approx(2 * macs * 512, rel=0.05)


def test_flash_count_against_hand_value():
    from chipbench.harness import counts

    # 16 rows x 12 heads, 512 x 512, head size 64, bfloat16
    assert counts.flash_fwd_flops(192, 512, 512, 64) == 12_884_901_888
    assert counts.flash_fwd_bytes(192, 512, 512, 64, 2) \
        == 4 * 192 * 512 * 64 * 2 + 192 * 512 * 4 == 50_724_864


# -- the trace reduction, on events recorded on the chip ----------------------

@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(REPO, "chipbench", "testdata",
                        "bert_step.trace_events.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _brute_busy(events, t0, t1, tick=1e-6):
    """Busy seconds by marking microsecond ticks: slow and plain."""
    n = int(round((t1 - t0) / tick))
    busy = np.zeros(n, bool)
    for _, start, dur in events:
        a = max(0, int(math.floor((start - t0) / tick)))
        b = min(n, int(math.ceil((start + dur - t0) / tick)))
        busy[a:b] = True
    return busy.sum() * tick


def test_busy_union_and_idle_share_on_the_recorded_trace(recorded):
    from chipbench.harness import trace

    window = trace.window_of(recorded)
    summary = trace.summary(recorded, steps=2)
    ops = recorded["devices"]["0"]["ops"]
    assert summary["window_s"] == pytest.approx(window[1] - window[0])
    # the union never exceeds the sum, and agrees with a brute-force count
    clipped = trace.clip(ops, *window)
    assert summary["busy_s"] <= sum(d for _, _, d in clipped) + 1e-12
    assert summary["busy_s"] == pytest.approx(
        _brute_busy(clipped, *window), rel=0.02)
    assert 0 < summary["busy_s"] < summary["window_s"]
    gaps = sum(s for _, s in summary["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx(summary["window_s"] - summary["busy_s"],
                                 rel=1e-6)
    assert len(summary["breakdown"]["device_ops"]) == 10


def test_named_kernel_time_on_the_recorded_trace(recorded):
    from chipbench.harness import trace

    window = trace.window_of(recorded)
    found = trace.kernel_events(recorded, window,
                                "mxnet_flash_attention_fwd")["0"]
    by_hand = [e for e in recorded["devices"]["0"]["ops"]
               if "mxnet_flash_attention_fwd" in e[0]
               and e[1] >= window[0] and e[1] + e[2] <= window[1]]
    assert len(found) >= len(by_hand) > 0
    assert sum(e[2] for e in found) >= sum(e[2] for e in by_hand)
    assert trace.op_name(found[0][0]).startswith("jvp_mxnet_flash")


def test_gap_attribution():
    """Hand-made events, in the recorded trace's form."""
    from chipbench.harness import trace

    dev = {"ops": [["%fusion.1 = f32[8]", 1.0, 1.0],
                   ["%fusion.2 = f32[8]", 3.0, 1.0],
                   ["%fusion.3 = f32[8]", 4.5, 0.5]]}
    host = [["dispatch_step", 0.0, 0.5], ["wait_loss", 0.5, 5.0],
            ["next_batch", 2.0, 0.2]]
    window = (0.0, 5.5)
    gaps = trace.idle_gaps(dev, host, window)
    # idle 0-1, 2-3, 4-4.5, 5-5.5, each named by the innermost span open
    assert gaps == [["dispatch_step", pytest.approx(1.0)],
                    ["next_batch", pytest.approx(1.0)],
                    ["wait_loss", pytest.approx(0.5)],
                    ["wait_loss", pytest.approx(0.5)]]
    two = {"devices": {"0": dev, "1": dev}, "host": host}
    s = trace.summary(two, steps=1)
    assert s["chips"] == 2 and s["busy_s"] == pytest.approx(2.5)
    assert s["breakdown"]["idle_gaps"][0] == ["dispatch_step",
                                              pytest.approx(1.0)]


def test_a_trace_with_no_device_plane_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp
    from chipbench.harness import trace

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    import glob

    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))[0]
    with pytest.raises(ValueError):
        trace.read_xplane(path, ("wait_loss",))
