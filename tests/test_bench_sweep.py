"""MXNET_BENCH_FORCE_SWEEP (VERDICT r5 Weak #1): the TPU-gated sweep and
headline-selection branches in bench.py must be executable on CPU, so first
chip contact cannot be the first time that code runs.

Fast tests drive the sweep/selection plumbing with stubbed measurement
fns; the real full-path runs (actual models, actual TrainStep) execute the
llama proxy's one run in tier-1 and the resnet config sweep under the
``slow`` marker.
"""
import sys

import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/", 2)[0])
import bench


@pytest.fixture
def force_sweep(monkeypatch):
    monkeypatch.setenv("MXNET_BENCH_FORCE_SWEEP", "1")


def test_resnet_sweep_selection(force_sweep, monkeypatch):
    """All three resnet configs execute on CPU under force-sweep and the
    best throughput is headlined."""
    calls = []

    def fake_layout(on_tpu, layout, batch=None, remat=False, stem="conv7"):
        calls.append((layout, batch, remat, stem))
        return {"conv7": 100.0, "s2d": 140.0}[stem] + (5.0 if remat else 0.0), 0.3

    monkeypatch.setattr(bench, "_bench_resnet50_layout", fake_layout)
    img_s, mfu, cfgs = bench.bench_resnet50(on_tpu=False)
    assert [c[3] for c in calls] == ["conv7", "conv7", "s2d"]
    assert all(c[2] for c in calls[1:])          # sweep configs use remat
    assert all(c[1] is None for c in calls)      # CPU keeps default batch
    assert cfgs["best"] == "b512_remat_s2d"
    assert img_s == 145.0
    assert set(cfgs["configs"]) == {"base", "b512_remat", "b512_remat_s2d"}


def test_resnet_sweep_survives_config_failure(force_sweep, monkeypatch):
    def fake_layout(on_tpu, layout, batch=None, remat=False, stem="conv7"):
        if stem == "s2d":
            raise RuntimeError("boom")
        return 100.0, 0.3

    monkeypatch.setattr(bench, "_bench_resnet50_layout", fake_layout)
    img_s, mfu, cfgs = bench.bench_resnet50(on_tpu=False)
    assert img_s == 100.0
    assert "boom" in cfgs["configs"]["b512_remat_s2d"]["error"]


def test_llama_full_sweep_path_on_cpu():
    """The REAL full path: model build + TrainStep + the timed steps, end
    to end on CPU (the whole point is that this cannot traceback only on
    the chip)."""
    tok, mfu = bench.bench_llama(False)
    assert tok > 0


@pytest.mark.slow
def test_resnet_full_sweep_path_on_cpu(force_sweep):
    """Real resnet config sweep (base + b512_remat + b512_remat_s2d at CPU
    batch) — long; excluded from tier-1."""
    img_s, mfu, cfgs = bench.bench_resnet50(on_tpu=False)
    assert img_s > 0
    assert set(cfgs["configs"]) == {"base", "b512_remat", "b512_remat_s2d"}


def test_eager_op_overhead_microbench():
    """The dispatch-cache microbench emits both modes and a speedup; the
    ≥3x acceptance number is asserted on the full bench run, not here
    (short runs are noise-prone) — this guards the plumbing."""
    r = bench.bench_eager_op_overhead(iters=30, warmup=5)
    assert r["us_per_op_jit"] > 0 and r["us_per_op_eager"] > 0
    assert r["speedup"] > 0
    assert r["cache"]["hits"] > r["cache"]["misses"]
