"""Step-overlap engine (ISSUE 4): device prefetch, bucketed fused
allreduce, and async checkpoint writes.

Acceptance anchors: bucketing assignment is deterministic (part of the
collective contract), gradients are BIT-identical bucketed vs per-key,
kvstore byte telemetry counts bucket flat buffers once, the prefetch
pipeline preserves order/values and fails fast on a dead source, and an
async save round-trips bit-exact while a failed background write costs one
step, never the job.
"""
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.data import DataLoader, PrefetchIterator
from mxnet_tpu.gluon.data.dataset import ArrayDataset
from mxnet_tpu.parallel import bucketing


# ---------------------------------------------------------------------------
# bucket assignment
# ---------------------------------------------------------------------------
def test_bucket_assignment_deterministic_across_instances():
    """Same ordered entries -> identical plan from independent Bucketer
    instances (what separate SPMD processes / restarted jobs compute)."""
    entries = [(f"p{i}", (64, 64), "float32") for i in range(10)] + \
        [("q0", (8,), "int32"), ("q1", (128, 128), "float32")]
    a = bucketing.Bucketer(cap_bytes=40_000).plan_for(entries)
    b = bucketing.Bucketer(cap_bytes=40_000).plan_for(entries)
    assert a.signature == b.signature
    assert [(x.dtype, x.keys, x.offsets, x.sizes) for x in a.buckets] == \
        [(x.dtype, x.keys, x.offsets, x.sizes) for x in b.buckets]
    # and it is a pure function: assign_buckets agrees too
    c = bucketing.assign_buckets(entries, 40_000)
    assert [x.keys for x in c.buckets] == [x.keys for x in a.buckets]


def test_bucket_assignment_dtype_segregated_and_capped():
    entries = [("a", (10,), "float32"), ("i", (10,), "int32"),
               ("b", (10,), "float32")]
    plan = bucketing.assign_buckets(entries, cap_bytes=1 << 20)
    by_dtype = {b.dtype: b.keys for b in plan.buckets}
    assert by_dtype == {"float32": ["a", "b"], "int32": ["i"]}
    # cap: 40B values with a 64B cap never share a bucket
    plan = bucketing.assign_buckets(
        [("a", (10,), "float32"), ("b", (10,), "float32")], cap_bytes=64)
    assert [b.keys for b in plan.buckets] == [["a"], ["b"]]


def test_bucket_oversized_value_gets_own_bucket():
    plan = bucketing.assign_buckets(
        [("small", (4,), "float32"), ("huge", (1 << 16,), "float32"),
         ("small2", (4,), "float32")], cap_bytes=1024)
    huge = [b for b in plan.buckets if "huge" in b.keys]
    assert len(huge) == 1 and huge[0].keys == ["huge"]
    # the oversized value must NOT close the open small bucket: the two
    # smalls bracketing it still share one bucket
    smalls = [b for b in plan.buckets if "small" in b.keys]
    assert smalls[0].keys == ["small", "small2"]


def test_bucket_pack_unpack_roundtrip_bit_exact():
    rng = np.random.RandomState(3)
    vals = [rng.randn(7, 3).astype("f"), rng.randn(11).astype("f"),
            rng.randn(2, 2, 2).astype("f")]
    plan = bucketing.assign_buckets(
        [(i, v.shape, str(v.dtype)) for i, v in enumerate(vals)])
    (b,) = plan.buckets
    flat = bucketing.pack(vals)
    out = bucketing.unpack(b, flat)
    for v, o in zip(vals, out):
        assert np.array_equal(v, np.asarray(o))


def test_bucketer_replans_on_signature_change():
    bk = bucketing.Bucketer(cap_bytes=1 << 20)
    p1 = bk.plan_for([("a", (4,), "float32")])
    assert bk.plan_for([("a", (4,), "float32")]) is p1  # cached
    p2 = bk.plan_for([("a", (8,), "float32")])
    assert p2 is not p1


# ---------------------------------------------------------------------------
# trainer: bucketed allreduce
# ---------------------------------------------------------------------------
def _make_net(seed=0):
    np.random.seed(seed)
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    net(nd.zeros((2, 8)))
    return net


def _train(net, steps=5, bucket_mb=None, kvstore="device"):
    prev = os.environ.get("MXNET_ALLREDUCE_BUCKET_MB")
    if bucket_mb is not None:
        os.environ["MXNET_ALLREDUCE_BUCKET_MB"] = str(bucket_mb)
    try:
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9},
                           kvstore=kvstore)
        rng = np.random.RandomState(7)
        for _ in range(steps):
            x = nd.array(rng.randn(8, 8).astype("f"))
            y = nd.array((rng.randn(8, 4) > 0).astype("f"))
            with autograd.record():
                loss = ((net(x) - y) ** 2).mean()
            loss.backward()
            tr.step(8)
        return {k: v.data().asnumpy()
                for k, v in net.collect_params().items()}
    finally:
        if prev is None:
            os.environ.pop("MXNET_ALLREDUCE_BUCKET_MB", None)
        else:
            os.environ["MXNET_ALLREDUCE_BUCKET_MB"] = prev


def test_trainer_bucketed_trajectory_bit_identical_to_per_key():
    """Acceptance: 5-step fp32 CPU trajectory with bucketing is
    bit-identical to the serial per-key path."""
    serial = _train(_make_net(), bucket_mb=0)      # per-key
    bucketed = _train(_make_net(), bucket_mb=32)   # fused
    assert len(serial) == len(bucketed)
    # gluon auto-names differ between net instances; sorted order aligns
    for (ks, vs), (kb, vb) in zip(sorted(serial.items()),
                                  sorted(bucketed.items())):
        assert np.array_equal(vs, vb), (ks, kb)


def test_trainer_bucketing_issues_expected_fused_collectives():
    net = _make_net()
    before = telemetry.counter("mxnet_allreduce_buckets_total").value
    _train(net, steps=3, bucket_mb=32)
    after = telemetry.counter("mxnet_allreduce_buckets_total").value
    # 4 small fp32 params -> exactly one fused bucket per step
    assert after - before == 3


def test_trainer_bucketing_push_bytes_counted_once():
    """kvstore_push_bytes must equal the actual payload exactly once under
    bucketing — the same total the per-key path reports (satellite:
    no double-report of bucket members)."""
    fam = telemetry.counter("mxnet_kvstore_push_bytes_total")
    b0 = fam.value
    _train(_make_net(), steps=2, bucket_mb=0)
    per_key_bytes = fam.value - b0
    b1 = fam.value
    _train(_make_net(), steps=2, bucket_mb=32)
    bucketed_bytes = fam.value - b1
    assert per_key_bytes > 0
    assert bucketed_bytes == per_key_bytes
    # and the bucket-byte family counted each bucket exactly once: the
    # fused flat buffers carry the same bytes the per-key path pushed
    snap = telemetry.snapshot()
    fused = snap["metrics"]["mxnet_allreduce_bucket_bytes_total"]
    assert fused["samples"][0]["value"] > 0


def test_trainer_bucketing_sparse_and_host_keys_bypass():
    """Row-sparse grads and host-promoted keys never enter a bucket."""
    from mxnet_tpu.ndarray.sparse import row_sparse_array

    net = _make_net()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore="device")
    x = nd.array(np.random.randn(4, 8).astype("f"))
    with autograd.record():
        loss = (net(x) ** 2).mean()
    loss.backward()

    class RecordingKV:
        def __init__(self, kv):
            self._kv = kv
            self.pushed = []

        def push(self, key, value, priority=0):
            self.pushed.append(str(key))
            self._kv.push(key, value, priority)

        def __getattr__(self, name):
            return getattr(self._kv, name)

    tr._init_kvstore()
    rec = RecordingKV(tr._kvstore)
    tr._kvstore = rec
    # make param 1's grad row-sparse and mark param 2 host-promoted
    params = tr._params
    ctx = params[1].list_ctx()[0]
    rsp = row_sparse_array((np.ones((1,) + params[1].shape[1:], "f"), [0]),
                           shape=params[1].shape)
    params[1].data(ctx)._grad = rsp
    from mxnet_tpu.kvstore import _HostRowSparseTable

    rec._kv._store["2"] = _HostRowSparseTable(
        params[2].data().asnumpy())

    class StopAfterPush(Exception):
        pass

    # only the partition matters here: record pushes, skip real pulls
    rec._kv.pull = lambda *a, **k: None
    tr._allreduce_grads()
    assert "1" in rec.pushed and "2" in rec.pushed  # per-key bypass
    bucket_keys = [k for k in rec.pushed if k.startswith("__grad_bucket")]
    assert bucket_keys  # the remaining dense params still fused


def test_trainer_bucket_buffers_not_retained_and_replan_rekeys():
    """Review fixes: (a) pulled flat buckets must not stay resident in the
    kvstore (they would duplicate the dense-grad footprint in HBM);
    (b) a replan bumps the key generation so per-key compression
    residuals never cross plans with different bucket composition."""
    net = _make_net()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore="device")
    os.environ["MXNET_ALLREDUCE_BUCKET_MB"] = "32"
    try:
        x = nd.array(np.random.randn(4, 8).astype("f"))
        with autograd.record():
            loss = (net(x) ** 2).mean()
        loss.backward()
        tr.step(4)
        kv = tr._kvstore
        stale = [k for k in kv._store if k.startswith("__grad_bucket")]
        assert not stale, stale
        gen1 = tr._bucketer.generation
        tr.step(4)  # same plan: no regeneration
        assert tr._bucketer.generation == gen1
        # cap change -> new signature -> replan -> new generation
        os.environ["MXNET_ALLREDUCE_BUCKET_MB"] = "1"
        tr.step(4)
        assert tr._bucketer.generation == gen1 + 1
    finally:
        os.environ.pop("MXNET_ALLREDUCE_BUCKET_MB", None)


def test_run_with_recovery_joins_final_async_save(tmp_path):
    """Review fix: a failed FINAL async save re-enters the retry loop
    instead of being silently dropped at supervisor return."""
    from mxnet_tpu import fault
    from mxnet_tpu.checkpoint import CheckpointManager, run_with_recovery

    net = _make_net(seed=11)
    mgr = CheckpointManager(str(tmp_path))
    attempts = []
    # held in the enclosing scope: a context manager armed with
    # __enter__() and then dropped is DISARMED when the suspended
    # generator is garbage collected (its finally runs) — the armed
    # fault must outlive train_fn's return
    armed = []

    def train_fn(start, manager):
        attempts.append(start)
        if len(attempts) == 1:
            cm = fault.inject("checkpoint.publish", error=OSError, times=1)
            cm.__enter__()
            armed.append(cm)
            manager.save(7, net, async_=True)
            return "done"  # final save still in flight (and will fail)
        manager.save(7, net, async_=False)
        return "done-after-retry"

    try:
        out = run_with_recovery(train_fn, mgr, max_restarts=2)
    finally:
        for cm in armed:
            cm.__exit__(None, None, None)
    assert out == "done-after-retry"
    assert len(attempts) == 2  # the lost final step was re-trained
    assert mgr.latest_valid_step() == 7


def test_dist_store_fusion_deterministic_and_exact():
    """Single-process dist store: fusion plan is stable across pushes and
    push+pull round-trips values exactly."""
    from mxnet_tpu import kvstore as kvs

    kv = kvs.create("dist_tpu_sync")
    rng = np.random.RandomState(0)
    vals = {str(i): rng.randn(5, 3).astype("f") for i in range(4)}
    kv.init(list(vals), [nd.zeros((5, 3)) for _ in vals])
    kv.push(list(vals), [nd.array(v) for v in vals.values()])
    outs = [nd.zeros((5, 3)) for _ in vals]
    kv.pull(list(vals), out=outs)
    for v, o in zip(vals.values(), outs):
        assert np.array_equal(v, o.asnumpy())


# ---------------------------------------------------------------------------
# prefetcher
# ---------------------------------------------------------------------------
def test_prefetch_iterator_preserves_order_and_values():
    batches = [(np.full((2, 2), i, "f"), np.full((2,), i, "i"))
               for i in range(8)]
    it = PrefetchIterator(iter(batches), depth=3)
    got = list(it)
    it.close()
    assert len(got) == 8
    for i, (x, y) in enumerate(got):
        assert np.array_equal(x.asnumpy(), batches[i][0])
        assert np.array_equal(y.asnumpy(), batches[i][1])


def test_prefetch_depth_zero_is_serial_passthrough():
    batches = [np.full((2,), i, "f") for i in range(4)]
    it = PrefetchIterator(iter(batches), depth=0)
    assert it._thread is None
    got = [b.asnumpy() for b in it]
    assert [int(b[0]) for b in got] == [0, 1, 2, 3]


def test_prefetch_env_knob_disables(monkeypatch):
    monkeypatch.setenv("MXNET_PREFETCH_BUFFER", "0")
    it = PrefetchIterator(iter([np.zeros(2, "f")]))
    assert it._thread is None
    monkeypatch.setenv("MXNET_PREFETCH_BUFFER", "4")
    it = PrefetchIterator(iter([np.zeros(2, "f")]))
    assert it._depth == 4
    it.close()


def test_prefetch_source_error_fails_fast():
    """A source that raises (the PR 2 worker-liveness error) reaches the
    consumer promptly — never a hang, never swallowed."""
    def gen():
        yield np.zeros((2,), "f")
        raise MXNetError("DataLoader process worker(s) died while "
                         "computing batch 1: pid=1 exitcode=-9")

    it = PrefetchIterator(gen(), depth=2)
    next(it)
    t0 = time.perf_counter()
    with pytest.raises(MXNetError, match="worker"):
        next(it)
    assert time.perf_counter() - t0 < 5.0
    it.close()


def test_prefetch_close_mid_iteration_unblocks_producer():
    produced = []

    def gen():
        for i in range(1000):
            produced.append(i)
            yield np.zeros((2,), "f")

    it = PrefetchIterator(gen(), depth=2)
    next(it)
    it.close()
    n = len(produced)
    time.sleep(0.3)
    assert len(produced) <= n + 4  # producer stopped, not draining 1000
    assert threading.active_count() < 50


def test_prefetch_records_telemetry():
    hits0 = telemetry.counter("mxnet_prefetch_hits_total").value
    miss0 = telemetry.counter("mxnet_prefetch_misses_total").value

    def slow_consumer():
        it = PrefetchIterator(
            iter([np.zeros((2,), "f")] * 6), depth=4)
        for b in it:
            time.sleep(0.02)  # let the producer stay ahead
        it.close()

    slow_consumer()
    hits = telemetry.counter("mxnet_prefetch_hits_total").value - hits0
    misses = telemetry.counter("mxnet_prefetch_misses_total").value - miss0
    assert hits + misses == 6
    assert hits >= 3  # steady state serves from the ready queue


def test_dataloader_prefetch_to_device_yields_same_values():
    X = np.random.RandomState(0).randn(32, 4).astype("f")
    Y = np.arange(32).astype("i")
    ds = ArrayDataset(X, Y)
    plain = list(DataLoader(ds, batch_size=8))
    pf = list(DataLoader(ds, batch_size=8, prefetch_to_device=True))
    assert len(plain) == len(pf) == 4
    for (a, b), (c, d) in zip(plain, pf):
        assert np.array_equal(a.asnumpy(), c.asnumpy())
        assert np.array_equal(b.asnumpy(), d.asnumpy())
    # staged batches are already on device (committed jax arrays)
    assert pf[0][0]._get().committed


def test_train_step_run_matches_call_loop():
    """TrainStep.run (prefetched) reproduces the __call__ loop bitwise."""
    from mxnet_tpu.parallel.data_parallel import TrainStep

    def ce(logits, labels):
        import jax
        import jax.numpy as jnp

        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1)

    rng = np.random.RandomState(1)
    batches = [(rng.randn(8, 8).astype("f"),
                (rng.randn(8) > 0).astype("i")) for _ in range(5)]
    s1 = TrainStep(_make_net(seed=5), ce, optimizer="sgd",
                   optimizer_params={"learning_rate": 0.1})
    l1 = [float(s1(x, y)) for x, y in batches]
    s2 = TrainStep(_make_net(seed=5), ce, optimizer="sgd",
                   optimizer_params={"learning_rate": 0.1})
    l2 = [float(v) for v in s2.run(batches)]
    assert l1 == l2
    for (k1, v1), (k2, v2) in zip(sorted(s1.params.items()),
                                  sorted(s2.params.items())):
        assert np.array_equal(np.asarray(v1), np.asarray(v2)), (k1, k2)


def test_full_overlap_trajectory_bit_identical_to_serial():
    """Acceptance: prefetch + bucketing together reproduce the serial
    path's loss/param trajectory bit-for-bit (CPU, fp32, 5 steps)."""
    X = np.random.RandomState(0).randn(40, 8).astype("f")
    Y = (X.sum(axis=1, keepdims=True) > 0).astype("f") * np.ones((40, 4), "f")

    def run(prefetch, bucket_mb):
        os.environ["MXNET_ALLREDUCE_BUCKET_MB"] = str(bucket_mb)
        try:
            net = _make_net(seed=2)
            tr = gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1}, kvstore="device")
            dl = DataLoader(ArrayDataset(X, Y), batch_size=8,
                            prefetch_to_device=True if prefetch else None)
            losses = []
            for x, y in dl:
                with autograd.record():
                    loss = ((net(x) - y) ** 2).mean()
                loss.backward()
                tr.step(8)
                losses.append(loss.asnumpy())
            return losses, {k: v.data().asnumpy()
                            for k, v in net.collect_params().items()}
        finally:
            os.environ.pop("MXNET_ALLREDUCE_BUCKET_MB", None)

    sl, sp = run(prefetch=False, bucket_mb=0)
    ol, op_ = run(prefetch=True, bucket_mb=32)
    for a, b in zip(sl, ol):
        assert np.array_equal(a, b)
    for (ks, vs), (ko, vo) in zip(sorted(sp.items()), sorted(op_.items())):
        assert np.array_equal(vs, vo), (ks, ko)


# ---------------------------------------------------------------------------
# async checkpoint
# ---------------------------------------------------------------------------
def test_async_save_roundtrip_bit_exact(tmp_path):
    from mxnet_tpu.checkpoint import CheckpointManager

    net = _make_net(seed=3)
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    x = nd.array(np.random.randn(4, 8).astype("f"))
    with autograd.record():
        loss = (net(x) ** 2).mean()
    loss.backward()
    tr.step(4)
    with CheckpointManager(str(tmp_path)) as mgr:
        mgr.save(1, net, tr, async_=True)
    assert mgr.all_steps() == [1]
    assert mgr.verify(1) is None  # sha256 manifest intact
    net2 = _make_net(seed=9)
    tr2 = gluon.Trainer(net2.collect_params(), "sgd",
                        {"learning_rate": 0.1})
    mgr2 = CheckpointManager(str(tmp_path))
    assert mgr2.restore(net2, tr2) == 1
    for (k, v), (_, v2) in zip(sorted(net.collect_params().items()),
                               sorted(net2.collect_params().items())):
        assert np.array_equal(v.data().asnumpy(), v2.data().asnumpy()), k


def test_async_save_snapshot_isolated_from_later_updates(tmp_path):
    """Params mutated right after save(async_=True) must not leak into
    the published file — the snapshot is the save-time value."""
    from mxnet_tpu.checkpoint import CheckpointManager

    net = _make_net(seed=4)
    # keyed by block-path name (what save_parameters writes): stable
    # across net instances, unlike gluon's global auto-names
    want = {k: v.data().asnumpy().copy()
            for k, v in net._collect_params_with_prefix().items()}
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, net, async_=True)
    for _, p in net.collect_params().items():   # mutate immediately
        p.set_data(nd.array(np.zeros(p.shape, "f")))
    mgr.close()
    net2 = _make_net(seed=4)
    mgr.restore(net2)
    for k, v in net2._collect_params_with_prefix().items():
        assert np.array_equal(v.data().asnumpy(), want[k]), k


def test_async_save_failure_surfaces_on_next_save_and_costs_one_step(
        tmp_path):
    from mxnet_tpu import fault
    from mxnet_tpu.checkpoint import CheckpointManager

    net = _make_net(seed=6)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, net)  # good baseline step
    with fault.inject("checkpoint.publish", error=OSError, times=10):
        mgr.save(2, net, async_=True)
        with pytest.raises(MXNetError, match="async checkpoint.*step 2"):
            mgr.save(3, net, async_=True)
    # step 2 was never published; the job resumes from step 1
    assert mgr.latest_valid_step() == 1
    # and the manager keeps working once the fault clears
    mgr.save(4, net, async_=True)
    mgr.close()
    assert mgr.latest_valid_step() == 4


def test_async_save_corruption_falls_back_one_step(tmp_path):
    """PR 2 corruption contract holds for async-published checkpoints."""
    from mxnet_tpu.checkpoint import CheckpointManager

    net = _make_net(seed=7)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, net, async_=True)
    mgr.save(2, net, async_=True)
    mgr.close()
    # bit-flip step 2's payload
    p = os.path.join(str(tmp_path), "step_00000002", "model.params")
    blob = bytearray(open(p, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(p, "wb").write(bytes(blob))
    assert mgr.latest_valid_step() == 1
    net2 = _make_net(seed=8)
    assert mgr.restore(net2) == 1


def test_run_with_recovery_credits_only_published_async_steps(tmp_path):
    from mxnet_tpu.checkpoint import CheckpointManager, run_with_recovery

    net = _make_net(seed=10)
    mgr = CheckpointManager(str(tmp_path))
    calls = []

    def train_fn(start, manager):
        calls.append(start)
        if len(calls) == 1:
            manager.save(5, net, async_=True)
            raise OSError("preempted mid-flight")  # write still in flight
        return start

    out = run_with_recovery(train_fn, mgr, max_restarts=2)
    # the supervisor joined the in-flight write: restart resumed from the
    # PUBLISHED step 5, not from 0
    assert out == 5
    assert calls == [0, 5]


def test_checkpoint_inflight_gauge(tmp_path):
    from mxnet_tpu.checkpoint import CheckpointManager

    gate = threading.Event()

    class SlowNet:
        def _collect_params_with_prefix(self):
            return {}

        def save_parameters(self, path):  # pragma: no cover
            raise AssertionError("async path must snapshot, not call this")

    mgr = CheckpointManager(str(tmp_path))
    orig = mgr._write_step

    def slow_write(*a, **k):
        gate.wait(5)
        return orig(*a, **k)

    mgr._write_step = slow_write
    mgr.save(1, SlowNet(), async_=True)
    assert telemetry.gauge("mxnet_checkpoint_inflight").value == 1
    gate.set()
    mgr.close()
    assert telemetry.gauge("mxnet_checkpoint_inflight").value == 0
