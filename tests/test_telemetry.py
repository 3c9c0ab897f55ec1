"""Unified runtime telemetry (mxnet_tpu/telemetry.py — ISSUE 3): metrics
registry (concurrency, histogram bucketing, label families), step timeline
phases, compile-event tracing, and Prometheus/JSON exporter shape, plus
the end-to-end smoke train loop the acceptance criteria name."""
import json
import re
import threading
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, profiler, telemetry
from mxnet_tpu.gluon import nn


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


# --------------------------------------------------------------------------
# registry primitives
# --------------------------------------------------------------------------
def test_counter_gauge_basics():
    c = telemetry.counter("t_requests_total", "help text")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = telemetry.gauge("t_depth", "queue depth")
    g.set(7)
    g.dec(2)
    assert g.value == 5
    # get-or-create returns the SAME family (process-wide registry)
    assert telemetry.counter("t_requests_total") is c


def test_type_conflict_rejected():
    telemetry.counter("t_conflict_total")
    with pytest.raises(ValueError):
        telemetry.gauge("t_conflict_total")
    with pytest.raises(ValueError):
        telemetry.counter("t_conflict_total", labelnames=("x",))


def test_label_families():
    fam = telemetry.counter("t_rpc_total", "by method", labelnames=("method",))
    fam.labels(method="push").inc(3)
    fam.labels("pull").inc()
    fam.labels(method="push").inc()          # same child
    snap = telemetry.snapshot()["metrics"]["t_rpc_total"]
    by = {s["labels"]["method"]: s["value"] for s in snap["samples"]}
    assert by == {"push": 4.0, "pull": 1.0}
    with pytest.raises(ValueError):
        fam.labels("a", "b")                 # wrong label arity


def test_histogram_bucketing():
    h = telemetry.histogram("t_lat_seconds", "latency",
                            buckets=[0.01, 0.1, 1.0])
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    cum = dict(h.cumulative())
    assert cum[0.01] == 1 and cum[0.1] == 2 and cum[1.0] == 3
    assert cum[float("inf")] == 4
    assert h.count == 4
    assert abs(h.sum - 5.555) < 1e-9


def test_exponential_buckets():
    bs = telemetry.exponential_buckets(1e-4, 2.0, 4)
    assert bs == [1e-4, 2e-4, 4e-4, 8e-4]


def test_registry_concurrency():
    c = telemetry.counter("t_threads_total")
    h = telemetry.histogram("t_threads_seconds", buckets=[1.0])
    fam = telemetry.counter("t_threads_labeled_total", labelnames=("w",))

    def work(i):
        for _ in range(500):
            c.inc()
            h.observe(0.5)
            fam.labels(w=str(i % 4)).inc()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 4000
    assert h.count == 4000
    total = sum(s["value"] for s in
                telemetry.snapshot()["metrics"]["t_threads_labeled_total"]
                ["samples"])
    assert total == 4000


# --------------------------------------------------------------------------
# step timeline
# --------------------------------------------------------------------------
def test_step_phases_sum_to_wall():
    telemetry.step_begin(10)
    with telemetry.phase("data"):
        pass
    with telemetry.phase("forward_backward"):
        nd.ones((8, 8)).asnumpy()
    rec = telemetry.step_end()
    assert rec["step"] == 10
    assert set(rec["phases"]) >= {"data", "forward_backward"}
    assert abs(sum(rec["phases"].values()) - rec["wall_s"]) < 1e-9
    assert telemetry.timeline()[-1]["step"] == 10


def test_nested_phase_attribution_is_exclusive():
    """Inner phases pause the outer clock: optimizer-with-collectives-
    inside must not double-count."""
    import time

    telemetry.step_begin()
    with telemetry.phase("optimizer"):
        with telemetry.phase("collectives"):
            time.sleep(0.05)
    rec = telemetry.step_end()
    assert rec["phases"]["collectives"] >= 0.045
    # outer got only its own (tiny) exclusive time, not the inner 50ms
    assert rec["phases"]["optimizer"] < 0.02
    assert abs(sum(rec["phases"].values()) - rec["wall_s"]) < 1e-9


def test_step_abort_and_auto_finalize():
    telemetry.step_begin(1)
    telemetry.step_abort()
    assert telemetry.timeline() == []
    telemetry.step_begin(2)   # left open...
    telemetry.step_begin(3)   # ...auto-finalized by the next begin
    telemetry.step_end()
    assert [r["step"] for r in telemetry.timeline()] == [2, 3]


def test_phase_outside_step_records_histogram():
    with telemetry.phase("checkpoint"):
        pass
    snap = telemetry.snapshot()["metrics"]["mxnet_step_phase_seconds"]
    assert any(s["labels"].get("phase") == "checkpoint" and s["count"] >= 1
               for s in snap["samples"])


def test_timeline_ring_is_bounded():
    from mxnet_tpu.telemetry import _TIMELINE_CAP

    for i in range(_TIMELINE_CAP + 5):
        telemetry.step_begin(i)
        telemetry.step_end()
    steps = telemetry.timeline()
    assert len(steps) == _TIMELINE_CAP
    assert steps[-1]["step"] == _TIMELINE_CAP + 4


# --------------------------------------------------------------------------
# compile-event tracing
# --------------------------------------------------------------------------
def test_op_compile_events_with_causes():
    from mxnet_tpu.ops.registry import register, OP_TABLE

    name = "_tel_compile_probe"
    if name not in OP_TABLE:
        @register(name, differentiable=False)
        def _probe(x, k=1.0):
            return x * k

    x32 = nd.array(np.ones((3,), "f"))
    nd.invoke(name, [x32], {"k": 1.0})               # new_op
    nd.invoke(name, [nd.array(np.ones((5,), "f"))], {"k": 1.0})  # new_shape
    nd.invoke(name, [x32], {"k": 2.0})               # new_attrs
    nd.invoke(name, [x32.astype("float16")], {"k": 1.0})         # new_dtype
    causes = {e["cause"] for e in telemetry.compile_events()
              if e["name"] == name}
    assert {"new_op", "new_shape", "new_attrs", "new_dtype"} <= causes
    ev = [e for e in telemetry.compile_events() if e["name"] == name][0]
    assert ev["kind"] == "op" and ev["elapsed_s"] > 0
    # cache hits do NOT append events
    n = len(telemetry.compile_events())
    nd.invoke(name, [x32], {"k": 1.0})
    assert len(telemetry.compile_events()) == n


def test_block_compile_event():
    net = nn.Dense(4)
    net.initialize()
    net.hybridize()
    x = nd.ones((2, 3))
    net(x)
    net(x)          # cached: no second event
    net(nd.ones((5, 3)))   # new signature
    evs = [e for e in telemetry.compile_events() if e["kind"] == "block"]
    assert len(evs) == 2
    assert evs[0]["cause"] == "new_block"
    assert evs[1]["cause"] == "new_signature"


def test_trace_failure_compile_event():
    from mxnet_tpu.ops.registry import register, OP_TABLE

    name = "_tel_trace_fail_probe"
    if name not in OP_TABLE:
        @register(name, differentiable=False)
        def _bad(x):
            return x + float(np.asarray(x).sum())    # concretizes under jit

    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        nd.invoke(name, [nd.array(np.ones((3,), "f"))], {})
    assert any(e["cause"] == "trace_failure" and e["name"] == name
               for e in telemetry.compile_events())


# --------------------------------------------------------------------------
# exporters
# --------------------------------------------------------------------------
_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? (-?[0-9.eE+-]+|\+Inf|NaN)$")


def _assert_prometheus_parses(text):
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert re.match(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]*", line)
        else:
            assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"


def test_render_prometheus_shape():
    telemetry.counter("t_x_total", "a counter").inc(2)
    fam = telemetry.histogram("t_h_seconds", "a hist", buckets=[0.1, 1.0],
                              labelnames=("op",))
    fam.labels(op='we"ird\nname').observe(0.05)
    text = telemetry.render_prometheus()
    _assert_prometheus_parses(text)
    assert "# TYPE t_x_total counter" in text
    assert "t_x_total 2" in text
    assert 't_h_seconds_bucket{le="0.1",op="we\\"ird\\nname"} 1' in text
    assert re.search(r't_h_seconds_count\{op=.*\} 1', text)
    # collector-backed families are present with no prior traffic needed
    assert "mxnet_dispatch_cache_hits_total" in text
    assert 'mxnet_fault_seam_calls_total{seam="kvstore.push"}' in text


def test_snapshot_is_json_serializable():
    telemetry.step_begin()
    with telemetry.phase("data"):
        pass
    telemetry.step_end()
    snap = json.loads(json.dumps(telemetry.snapshot()))
    assert "metrics" in snap and "steps" in snap and "compile_events" in snap
    assert snap["steps"][0]["phases"]


def test_http_endpoint():
    srv = telemetry.start_http_server(port=0)
    try:
        port = srv.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        _assert_prometheus_parses(body)
        snap = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/snapshot", timeout=5).read())
        assert "metrics" in snap
        ok = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5).read()
        assert ok == b"ok\n"
        with pytest.raises(Exception):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=5)
    finally:
        telemetry.stop_http_server()


# --------------------------------------------------------------------------
# layer instrumentation
# --------------------------------------------------------------------------
def test_kvstore_traffic_counters():
    kv = mx.kv.create("local")
    shape = (16, 4)
    kv.init(0, nd.zeros(shape))
    before = telemetry.snapshot()["metrics"]
    p0 = before["mxnet_kvstore_push_bytes_total"]["samples"][0]["value"]
    kv.push(0, [nd.ones(shape)])
    out = nd.zeros(shape)
    kv.pull(0, out=[out])
    after = telemetry.snapshot()["metrics"]
    nbytes = int(np.prod(shape)) * 4
    assert after["mxnet_kvstore_push_bytes_total"]["samples"][0]["value"] \
        == p0 + nbytes
    assert after["mxnet_kvstore_pull_bytes_total"]["samples"][0]["value"] \
        >= nbytes


def test_dataloader_batch_wait_histogram():
    ds = gluon.data.ArrayDataset(np.arange(32, dtype="f").reshape(16, 2),
                                 np.arange(16, dtype="f"))
    dl = gluon.data.DataLoader(ds, batch_size=4)
    n = sum(1 for _ in dl)
    assert n == 4
    snap = telemetry.snapshot()["metrics"]
    hist = snap["mxnet_dataloader_batch_wait_seconds"]["samples"][0]
    assert hist["count"] >= 4
    assert snap["mxnet_dataloader_batches_total"]["samples"][0]["value"] >= 4


def test_checkpoint_save_restore_metrics(tmp_path):
    from mxnet_tpu.checkpoint import CheckpointManager

    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, extra={"k": 1})
    assert mgr.restore() == 1
    snap = telemetry.snapshot()["metrics"]
    assert snap["mxnet_checkpoint_saves_total"]["samples"][0]["value"] == 1
    assert snap["mxnet_checkpoint_restores_total"]["samples"][0]["value"] == 1
    assert snap["mxnet_checkpoint_save_seconds"]["samples"][0]["count"] == 1


def test_recovery_restart_counter(tmp_path):
    from mxnet_tpu.checkpoint import CheckpointManager, run_with_recovery

    mgr = CheckpointManager(str(tmp_path))
    boom = [True]

    def train(start, manager):
        manager.save(start + 1)
        if boom[0]:
            boom[0] = False
            raise OSError("synthetic preemption")
        return "ok"

    assert run_with_recovery(train, mgr, max_restarts=2, backoff_ms=0) == "ok"
    snap = telemetry.snapshot()["metrics"]
    assert snap["mxnet_recovery_restarts_total"]["samples"][0]["value"] == 1


def test_trainer_step_phases():
    net = nn.Dense(2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, telemetry=True)
    from mxnet_tpu import autograd

    telemetry.step_begin()
    with autograd.record():
        loss = net(nd.ones((4, 3))).sum()
    loss.backward()
    trainer.step(4)
    rec = telemetry.step_end()
    assert "collectives" in rec["phases"] and "optimizer" in rec["phases"]
    snap = telemetry.snapshot()["metrics"]
    assert snap["mxnet_trainer_steps_total"]["samples"][0]["value"] == 1


def test_speedometer_telemetry_gauge():
    from mxnet_tpu.callback import Speedometer

    class P:
        def __init__(self, nbatch):
            self.nbatch = nbatch
            self.epoch = 0
            self.eval_metric = None

    sp = Speedometer(batch_size=8, frequent=2, telemetry=True)
    for i in range(5):
        sp(P(i))
    snap = telemetry.snapshot()["metrics"]
    assert snap["mxnet_speedometer_samples_per_sec"]["samples"][0]["value"] > 0
    assert snap["mxnet_speedometer_batches_total"]["samples"][0]["value"] >= 2


# --------------------------------------------------------------------------
# acceptance smoke: tiny train loop, telemetry + profiler on
# --------------------------------------------------------------------------
def test_smoke_train_loop_acceptance(tmp_path):
    from mxnet_tpu import fault

    trace = str(tmp_path / "profile.json")
    profiler.set_config(profile_imperative=True, filename=trace,
                        jax_trace=False)
    profiler.start()
    try:
        net = nn.Dense(2)
        net.initialize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.01}, telemetry=True)
        from mxnet_tpu import autograd

        X = np.random.RandomState(0).randn(32, 3).astype("f")
        Y = np.random.RandomState(1).randn(32, 2).astype("f")
        ds = gluon.data.ArrayDataset(X, Y)
        dl = gluon.data.DataLoader(ds, batch_size=8)
        for _ in range(2):   # 2 epochs: second is all cache hits
            it = iter(dl)
            while True:
                telemetry.step_begin()
                with telemetry.phase("data"):
                    batch = next(it, None)
                if batch is None:
                    telemetry.step_abort()
                    break
                x, y = batch
                with telemetry.phase("forward_backward"):
                    with autograd.record():
                        out = net(x)
                        loss = ((out - y) * (out - y)).sum()
                    loss.backward()
                trainer.step(x.shape[0])
                telemetry.step_end()
    finally:
        profiler.stop()

    # 1) Prometheus rendering parses and carries the core families
    text = telemetry.render_prometheus()
    _assert_prometheus_parses(text)
    for fam in ("mxnet_dispatch_cache_hits_total",
                "mxnet_fault_seam_calls_total",
                "mxnet_step_phase_seconds",
                "mxnet_compile_events_total"):
        assert fam in text, fam

    # 2) snapshot: per-step phase durations sum to ~step wall time
    snap = telemetry.snapshot()
    assert len(snap["steps"]) == 8
    for rec in snap["steps"]:
        assert abs(sum(rec["phases"].values()) - rec["wall_s"]) < 1e-9
        assert {"data", "forward_backward", "collectives",
                "optimizer"} <= set(rec["phases"])

    # 3) >=1 compile event with a cause
    assert snap["compile"]["count"] >= 1
    assert all(e["cause"] for e in snap["compile_events"])

    # the kvstore seam saw the trainer's pushes (fault family has traffic)
    assert fault.stats()["kvstore.push"]["calls"] > 0

    # step-phase spans + telemetry snapshot merged into the Chrome trace
    path = profiler.dump()
    data = json.load(open(path))
    cats = {e.get("cat") for e in data["traceEvents"]}
    assert "step_phase" in cats and "step" in cats
    assert "telemetry" in data["otherData"]
    assert data["otherData"]["telemetry"]["steps"]


# --------------------------------------------------------------------------
# ISSUE 14: runtime introspection plane
# --------------------------------------------------------------------------
def test_request_trace_span_tree_nesting():
    from mxnet_tpu.serving.tracing import RequestTrace

    tr = RequestTrace(7)
    q = tr.add_span("queue_wait", tr.t0, tr.t0 + 0.01)
    p = tr.add_span("prefill", tr.t0 + 0.01, tr.t0 + 0.05, tokens=3)
    tr.add_span("sample", tr.t0 + 0.04, tr.t0 + 0.05, parent=p)
    d = tr.add_span("decode_step", tr.t0 + 0.05, tr.t0 + 0.06, step=1)
    tr.add_span("sample", tr.t0 + 0.055, tr.t0 + 0.06, parent=d)
    tr.event("evicted", cache_len=9)
    tr.finish("length")
    doc = tr.to_dict()
    root = doc["tree"]
    assert [c["name"] for c in root["children"]] == \
        ["queue_wait", "prefill", "decode_step"]
    prefill = root["children"][1]
    assert [c["name"] for c in prefill["children"]] == ["sample"]
    assert prefill["attrs"] == {"tokens": 3}
    decode = root["children"][2]
    assert [c["name"] for c in decode["children"]] == ["sample"]
    assert doc["evicted"] is True
    assert doc["outcome"] == "length"
    assert doc["events"][0]["name"] == "evicted"
    assert q == 1  # span ids are stable, root is 0
    json.dumps(doc)  # JSON-able end to end


def test_request_trace_span_cap_counts_overflow():
    from mxnet_tpu.serving import tracing
    from mxnet_tpu.serving.tracing import RequestTrace

    tr = RequestTrace(1)
    for i in range(tracing._MAX_SPANS + 5):
        tr.add_span("decode_step", 0.0, 0.1)
    assert len(tr.spans) == tracing._MAX_SPANS
    assert tr.dropped_spans == 5


def test_trace_store_tail_retention_keeps_slowest_and_errors():
    from mxnet_tpu.serving.tracing import RequestTrace, TraceStore

    store = TraceStore(keep_slowest=2, keep_recent=3, keep_errors=4)

    def finished(i, dur, outcome="length", error=None, evicted=False):
        tr = RequestTrace(i)
        tr.t_end = tr.t0 + dur  # fix duration deterministically
        tr.outcome = outcome
        tr.error = error
        tr.evicted = evicted
        store.add(tr)
        return tr

    slow = finished(1, 9.0)                      # the p99 outlier, early
    err = finished(2, 0.1, outcome="error",
                   error=RuntimeError("boom"))
    ev = finished(3, 0.2, evicted=True)
    for i in range(4, 30):                       # healthy fast traffic
        finished(i, 0.01)
    kept = {tr.trace_id: tags for tr, tags in store.traces()}
    # the slowest trace survived 26 later completions
    assert 1 in kept and "slowest" in kept[1]
    # error + evicted traces are always retained
    assert 2 in kept and "errors" in kept[2]
    assert 3 in kept and "errors" in kept[3]
    # the recent ring holds only the newest 3
    assert all("recent" not in tags for tid, tags in kept.items()
               if tid < 27)
    snap = store.snapshot()
    assert snap["traced_requests"] == 29
    assert snap["requests"][0]["trace_id"] == 1  # slowest-first
    assert snap["retention"]["keep_slowest"] == 2
    json.dumps(snap)
    assert slow.duration_s == pytest.approx(9.0)
    assert err.error is not None and ev.evicted


def _tiny_train_step():
    from mxnet_tpu.parallel.data_parallel import TrainStep

    net = nn.Dense(2)
    net.initialize()
    net(nd.ones((1, 3)))   # resolve deferred shapes before functionalize

    def loss_fn(out, y):
        import jax.numpy as jnp

        return jnp.square(out - y).mean()

    return TrainStep(net, loss_fn, optimizer="sgd",
                     optimizer_params={"learning_rate": 0.01})


def _drop_mfu_gauge():
    from mxnet_tpu import introspection

    telemetry._FAMILIES.pop("mxnet_model_flops_utilization", None)
    introspection._MFU_GAUGE = None
    introspection.reset()


def test_online_mfu_gauge_present_with_peak_override(monkeypatch):
    from mxnet_tpu import introspection

    _drop_mfu_gauge()
    monkeypatch.setenv("MXNET_DEVICE_PEAK_FLOPS", "1e12")
    step = _tiny_train_step()
    x = np.ones((4, 3), "f")
    y = np.zeros((4, 2), "f")
    for _ in range(3):
        np.asarray(step(x, y))
    ws = introspection.window_stats()
    assert ws["events"] == 3 and ws["flops"] > 0
    snap = telemetry.snapshot()
    assert "mxnet_model_flops_utilization" in snap["metrics"]
    util = snap["metrics"]["mxnet_model_flops_utilization"][
        "samples"][0]["value"]
    assert util > 0
    fl = snap["metrics"]["mxnet_executable_flops_total"]["samples"]
    assert {"kind": "train_step"} in [s["labels"] for s in fl]
    # exactly ONE train_step compile event: the AOT path traced once
    kinds = [e["kind"] for e in snap["compile_events"]]
    assert kinds.count("train_step") == 1


def test_mfu_gauge_absent_when_cost_analysis_unavailable(monkeypatch):
    """The graceful-fallback contract: no FLOPs source -> the MFU gauge
    does not exist (absent, not wrong) — and the step still runs."""
    from mxnet_tpu import introspection

    _drop_mfu_gauge()
    monkeypatch.setenv("MXNET_DEVICE_PEAK_FLOPS", "1e12")
    monkeypatch.setattr(introspection, "flops_of", lambda compiled: None)
    step = _tiny_train_step()
    losses = [np.asarray(step(np.ones((4, 3), "f"),
                              np.zeros((4, 2), "f")))
              for _ in range(2)]
    assert all(np.isfinite(v) for v in losses)
    assert introspection.window_stats()["events"] == 0
    assert "mxnet_model_flops_utilization" not in \
        telemetry.snapshot()["metrics"]


def test_mfu_gauge_absent_when_peak_unknown(monkeypatch):
    from mxnet_tpu import introspection

    _drop_mfu_gauge()
    monkeypatch.delenv("MXNET_DEVICE_PEAK_FLOPS", raising=False)
    monkeypatch.setattr(introspection, "device_peak_flops", lambda: None)
    introspection.account_flops(1e9)
    introspection.account_flops(1e9)
    assert introspection.utilization() is None
    assert "mxnet_model_flops_utilization" not in \
        telemetry.snapshot()["metrics"]


def test_aot_flops_match_cost_analysis_source():
    """Online accounting uses the SAME FLOPs source as an offline
    lower().compile().cost_analysis() of the identical step — the
    bench extra.observability MFU pin relies on this equivalence."""
    from mxnet_tpu import introspection

    introspection.reset()
    step = _tiny_train_step()
    x = np.ones((4, 3), "f")
    y = np.zeros((4, 2), "f")
    np.asarray(step(x, y))
    per_step = telemetry.snapshot()["metrics"][
        "mxnet_executable_flops_total"]["samples"][0]["value"]
    compiled, flops = step._compiled[next(iter(step._compiled))]
    assert flops == pytest.approx(per_step)
    assert introspection.flops_of(compiled) == pytest.approx(per_step)


def test_each_signature_accounts_its_own_executables_flops():
    """Two batch shapes are two executables; a step accounts the count
    captured when its own was compiled, at every dispatch."""
    from mxnet_tpu import introspection

    introspection.reset()
    step = _tiny_train_step()

    def total():
        return telemetry.snapshot()["metrics"][
            "mxnet_executable_flops_total"]["samples"][0]["value"]

    seen = 0.0
    for rows in (4, 16, 4, 16):
        np.asarray(step(np.ones((rows, 3), "f"), np.zeros((rows, 2), "f")))
        sig = ((rows, 3), "float32", (rows, 2), "float32")
        flops = step._compiled[sig][1]
        assert total() - seen == pytest.approx(flops)
        seen = total()
    small, large = (step._compiled[s][1] for s in sorted(step._compiled))
    assert large > small


def test_goodput_ledger_preempt_resume_and_reshard(tmp_path):
    """Goodput classification across a restarting run and a live
    reshard: productive accrues from steps, checkpoint from save,
    restart from the failure->re-attempt window, reshard from the
    transfer seam; the ratio reflects all of them."""
    import time as _time

    from mxnet_tpu.checkpoint import CheckpointManager, run_with_recovery
    from mxnet_tpu.parallel import resharding

    manager = CheckpointManager(str(tmp_path))
    state = {"fails": 0}

    def train_fn(start, mgr):
        for s in range(start, 3):
            with telemetry.step_scope(s):
                _time.sleep(0.002)
            mgr.save(s)
        if state["fails"] < 1:
            state["fails"] += 1
            raise RuntimeError("injected failure")
        return "done"

    assert run_with_recovery(train_fn, manager, max_restarts=3,
                             backoff_ms=1) == "done"
    # a live transfer (trivial 1-device plans) charges the reshard bucket
    resharding.transfer_params({"w": np.ones((4, 4), "f")})
    good = telemetry.goodput_summary()
    for bucket in ("productive", "checkpoint", "restart", "reshard"):
        assert good["buckets"].get(bucket, 0) > 0, (bucket, good)
    assert 0 < good["productive_ratio"] < 1
    snap = telemetry.snapshot()
    assert snap["goodput"]["buckets"] == good["buckets"]
    ratio = snap["metrics"]["mxnet_goodput_ratio"]["samples"][0]["value"]
    assert ratio == pytest.approx(good["productive_ratio"])


def test_goodput_stall_bucket_from_watchdog(tmp_path):
    from mxnet_tpu import lifecycle

    telemetry.heartbeat()
    wd = lifecycle.Watchdog(timeout_s=0.01, abort=False,
                            dump_dir=str(tmp_path), poll_s=0.005)
    wd._fire(1.25, None)   # a REAL stall fire charges the ledger
    assert telemetry.goodput_summary()["buckets"]["stall"] == \
        pytest.approx(1.25)
    wd._fire(9.9, RuntimeError("chaos"))  # injected fires charge nothing
    assert telemetry.goodput_summary()["buckets"]["stall"] == \
        pytest.approx(1.25)


def _synthetic_snapshot(step, phases, steps_total):
    return {
        "time": 100.0 + steps_total,
        "metrics": {
            "mxnet_steps_total": {
                "type": "counter", "help": "h",
                "samples": [{"labels": {}, "value": steps_total}]},
        },
        "steps": [{"step": step, "time": 100.0, "wall_s": sum(
            phases.values()), "phases": dict(phases)}],
        "compile": {"count": 2},
        "goodput": {"productive_ratio": 0.5},
    }


def test_rank_merge_is_deterministic_and_rank_labeled():
    from mxnet_tpu import telemetry_agg

    s0 = _synthetic_snapshot(5, {"data": 0.010, "forward_backward": 0.02},
                             6)
    s1 = _synthetic_snapshot(5, {"data": 0.025, "forward_backward": 0.02},
                             6)
    m1 = telemetry_agg.merge_snapshots({0: s0, 1: s1})
    m2 = telemetry_agg.merge_snapshots({1: s1, 0: s0})
    assert json.dumps(m1, sort_keys=True) == json.dumps(m2,
                                                        sort_keys=True)
    assert m1["ranks"] == [0, 1]
    labels = [s["labels"] for s in
              m1["metrics"]["mxnet_steps_total"]["samples"]]
    assert labels == [{"rank": "0"}, {"rank": "1"}]
    assert m1["skew"]["step"] == 5
    assert m1["skew"]["phases"]["data"] == pytest.approx(0.015)
    assert m1["skew"]["phases"]["forward_backward"] == pytest.approx(0.0)
    assert m1["per_rank"][0]["last_step"] == 5
    assert m1["per_rank"][1]["compile_count"] == 2
    # no common step -> no skew, never a crash
    s2 = _synthetic_snapshot(9, {"data": 0.01}, 1)
    m3 = telemetry_agg.merge_snapshots({0: s0, 1: s2})
    assert m3["skew"]["step"] is None and m3["skew"]["phases"] == {}


def test_aggregator_dir_roundtrip_and_skew_histogram(tmp_path):
    from mxnet_tpu import telemetry_agg

    telemetry_agg.reset()
    try:
        with telemetry.step_scope(3):
            pass
        assert telemetry_agg.publish(str(tmp_path), 0)
        # fabricate a slower peer at the same step
        peer = telemetry.snapshot()
        peer["steps"][-1]["phases"]["other"] = \
            peer["steps"][-1]["phases"].get("other", 0.0) + 0.5
        with open(tmp_path / "rank1.json", "w") as f:
            json.dump(peer, f)
        (tmp_path / "rank9.json").write_text("{torn")  # skipped, not fatal
        doc = telemetry_agg.merge_dir(str(tmp_path))
        assert doc["ranks"] == [0, 1]
        assert doc["skew"]["step"] == 3
        hist = telemetry.snapshot()["metrics"][
            "mxnet_rank_step_skew_seconds"]
        assert any(s["count"] for s in hist["samples"])
    finally:
        telemetry_agg.reset()


def test_aggregator_tick_stride(tmp_path, monkeypatch):
    from mxnet_tpu import telemetry_agg

    telemetry_agg.reset()
    try:
        telemetry_agg.configure(directory=str(tmp_path), every=2, rank=0,
                                world=1)
        for i in range(4):
            with telemetry.step_scope(i):   # step_end ticks the stride
                pass
        merged = telemetry_agg.merged()
        assert merged is not None and merged["ranks"] == [0]
        assert (tmp_path / "rank0.json").exists()
    finally:
        telemetry_agg.reset()


def test_read_dir_drops_stale_departed_ranks(tmp_path):
    """A rank that left an elastic job stops publishing; its file must
    not pin a frozen rank into every merge forever.  Staleness is
    judged against the NEWEST file, not the wall clock, so offline
    re-merges of old directories stay deterministic and complete."""
    from mxnet_tpu import telemetry_agg

    fresh = _synthetic_snapshot(5, {"data": 0.01}, 6)
    fresh["time"] = 10_000.0
    stale = _synthetic_snapshot(2, {"data": 0.01}, 3)
    stale["time"] = 10_000.0 - 3600.0      # an hour behind the newest
    with open(tmp_path / "rank0.json", "w") as f:
        json.dump(fresh, f)
    with open(tmp_path / "rank3.json", "w") as f:
        json.dump(stale, f)
    assert sorted(telemetry_agg.read_dir(str(tmp_path))) == [0]
    # filter disabled / both within the window -> both merge
    assert sorted(telemetry_agg.read_dir(str(tmp_path),
                                         max_age_s=0)) == [0, 3]
    assert sorted(telemetry_agg.read_dir(str(tmp_path),
                                         max_age_s=7200)) == [0, 3]


def test_request_trace_event_cap_keeps_flags():
    from mxnet_tpu.serving import tracing
    from mxnet_tpu.serving.tracing import RequestTrace

    tr = RequestTrace(2)
    for _ in range(tracing._MAX_EVENTS + 3):
        tr.event("requeued", reason="pool_full")
    tr.event("evicted")   # past the cap: dropped but the flag still set
    assert len(tr.events) == tracing._MAX_EVENTS
    assert tr.dropped_events == 4
    assert tr.evicted is True
    assert tr.to_dict()["dropped_events"] == 4
