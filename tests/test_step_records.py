"""A fused step's record (``telemetry.snapshot()["step_records"]``): one
step and one batch number from the producer's staging to the instant a
later call sees the step complete, with no wait on the dispatch path."""
import itertools
import threading
import time
from collections import deque

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, telemetry
from mxnet_tpu.gluon.data.prefetcher import PrefetchIterator
from mxnet_tpu.parallel import data_parallel
from mxnet_tpu.parallel.data_parallel import TrainStep

PREPARE, EXECUTE, COMPILE = (data_parallel.PHASE_PREPARE,
                             data_parallel.PHASE_EXECUTE,
                             data_parallel.PHASE_COMPILE)


@pytest.fixture(autouse=True)
def _clean():
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture(scope="module")
def net():
    mx.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    net(mx.nd.zeros((8, 8)))
    return net


def _step(net):
    return TrainStep(net, lambda out, y: (out - y) ** 2, optimizer="sgd",
                     optimizer_params={"learning_rate": 0.01})


def _pool(n=4):
    rng = np.random.default_rng(0)
    return [(rng.standard_normal((8, 8)).astype("float32"),
             rng.standard_normal((8, 4)).astype("float32"))
            for _ in range(n)]


def _records():
    return telemetry.snapshot()["step_records"]["records"]


def _count(name):
    family = telemetry.snapshot()["metrics"][name]
    return family["samples"][0].get("value", family["samples"][0].get(
        "count"))


class _no_waiting:
    """Within it every way a call could wait for the device raises."""

    def __enter__(self):
        from jax._src import array

        def refuse(*a, **kw):
            raise AssertionError("the dispatch path waited for the device")

        self._patch = pytest.MonkeyPatch()
        for name in ("block_until_ready", "__float__", "__array__",
                     "item", "tolist"):
            self._patch.setattr(array.ArrayImpl, name, refuse)
        self._patch.setattr(jax, "block_until_ready", refuse)
        self._patch.setattr(jax, "device_get", refuse)

    def __exit__(self, *exc):
        self._patch.undo()


def test_one_step_and_one_batch_number_across_the_two_threads(net):
    step = _step(net)
    with PrefetchIterator(itertools.cycle(_pool()), depth=2) as feed:
        for _ in range(6):
            x, y = next(feed)
            float(step(x, y))
    records = _records()
    assert [r["step"] for r in records] == list(range(6))
    assert [r["batch"] for r in records] == list(range(6))
    me = threading.get_ident()
    for r in records:
        assert (r["kind"], r["net"]) == ("fused", "HybridSequential")
        spans = r["spans"]
        stage, wait = spans["prefetch.stage"], spans["prefetch.wait"]
        assert stage[2] != me and wait[2] == me == r["thread"]
        # staged, then taken from the queue, then prepared, then dispatched
        assert stage[0] <= stage[1] <= wait[1] <= r["opened"] \
            <= spans[PREPARE][0] <= spans[PREPARE][1] \
            <= spans[EXECUTE][0] <= spans[EXECUTE][1] <= r["seen_complete"]
        assert (COMPILE in spans) == (r["step"] == 0)
    first = records[0]["spans"]
    assert first[PREPARE][1] <= first[COMPILE][0] <= first[COMPILE][1] \
        <= first[EXECUTE][0]
    # one stamp serves the record and the phase's histogram
    phases = {s["labels"]["phase"]: s for s in telemetry.snapshot()[
        "metrics"]["mxnet_step_phase_seconds"]["samples"]}
    for name in (PREPARE, EXECUTE):
        assert phases[name]["sum"] == pytest.approx(sum(
            r["spans"][name][1] - r["spans"][name][0] for r in records),
            rel=1e-9)
    # the compile event's seconds are the call's, from the same stamps
    event, = [e for e in telemetry.compile_events()
              if e["kind"] == "train_step"]
    assert event["elapsed_s"] == first[EXECUTE][1] - first[PREPARE][0]


def test_a_batch_from_no_prefetcher_has_no_number(net):
    step = _step(net)
    pool = _pool()
    step(*pool[0])
    with PrefetchIterator(iter(pool), depth=0) as feed:    # staged in place
        step(*next(feed))
    step(*pool[1])
    first, staged, bare = _records()
    assert first["batch"] is None and "prefetch.stage" not in first["spans"]
    assert staged["batch"] == 0 and "prefetch.wait" not in staged["spans"]
    assert staged["spans"]["prefetch.stage"][2] == threading.get_ident()
    assert bare["batch"] is None and set(bare["spans"]) == {PREPARE, EXECUTE}


def test_seen_complete_is_stamped_with_no_wait_on_the_dispatch_path(net):
    step = _step(net)
    pool = _pool()
    losses = [step(*pool[0])]
    float(losses[0])
    for i in range(1, 5):
        with _no_waiting():
            losses.append(step(*pool[i % 4]))
        float(losses[-1])       # the loop's own wait, outside the call
    with telemetry._LOCK:
        live = [r for r in telemetry._STEPS if r.get("kind") == "fused"]
    # each call's look found the step before it done, and stamped it then
    assert [r["seen_complete"] for r in live[:4]] \
        == [r["opened"] for r in live[1:]]
    assert live[4]["seen_complete"] is None
    assert [r["interval_s"] for r in live[1:4]] == pytest.approx(
        [b["opened"] - a["opened"] for a, b in zip(live[1:], live[2:])])
    # the loop has waited for the last step: who reads the metrics stamps it
    assert _records()[4]["seen_complete"] >= live[4]["spans"][EXECUTE][1]


def test_in_flight_counts_the_steps_not_yet_seen_complete(net, monkeypatch):
    step, other = _step(net), _step(net)
    pool = _pool()
    float(step(*pool[0]))
    float(other(*pool[0]))
    ready = {"now": False}
    # the look at earlier steps finds them running until the test says so
    from jax._src import array

    monkeypatch.setattr(array.ArrayImpl, "is_ready",
                        lambda self: ready["now"])
    for i in range(3):
        step(*pool[i])
    other(*pool[0])
    ready["now"] = True
    step(*pool[3])
    mine = [r for r in _records() if r["track"] == step._track.id]
    theirs = [r for r in _records() if r["track"] == other._track.id]
    # the other step's second call saw this one's first complete, and each
    # counts its own
    assert [r["in_flight"] for r in mine] == [0, 0, 1, 2, 0]
    assert [r["in_flight"] for r in theirs] == [0, 1]
    # the oldest step in flight is the one a look reports on
    assert theirs[0]["unready_at"] == theirs[1]["opened"]
    assert theirs[0]["seen_complete"] == mine[4]["opened"]
    assert mine[0]["since_previous_call"] is None
    since = mine[2]["since_previous_call"]
    assert since["seconds"] == pytest.approx(mine[2]["opened"]
                                             - mine[1]["opened"])
    assert {"nivcsw", "nvcsw", "majflt", "inblock", "oublock", "cpu_s",
            "gc2", "compiles", "cache_misses", "cache_retrieval_s",
            "backend_compile_s"} <= set(since)


def test_what_the_process_did_since_the_previous_call(net):
    import gc

    step = _step(net)
    pool = _pool()
    float(step(*pool[0]))
    t0 = time.perf_counter()
    gc.collect()                  # a generation-2 pass
    gc.collect(0)                 # a young one: not kept
    t1 = time.perf_counter()
    telemetry.compile_event("op", "made_up", 0.0, "new_op")
    float(step(*pool[1]))
    float(step(*pool[2]))
    _, second, third = _records()
    (a, b), = second["since_previous_call"]["gc2"]
    assert t0 <= a <= b <= t1
    # since the previous call opened: that call's own compile and this one
    assert second["since_previous_call"]["compiles"] == 2
    assert second["since_previous_call"]["cpu_s"] > 0
    assert third["since_previous_call"]["gc2"] == []
    assert third["since_previous_call"]["compiles"] == 0


def test_a_slow_step_is_a_stall_and_its_record_outlives_the_ring(
        net, monkeypatch):
    monkeypatch.setattr(telemetry, "_STEPS", deque(maxlen=16))
    noted = []
    monkeypatch.setattr(telemetry, "_flight_note",
                        lambda kind, **f: noted.append((kind, f)))
    step = _step(net)
    pool = _pool()
    for i in range(40):
        # a step every 20 ms, so that a busy machine's jitter is no stall
        time.sleep(0.5 if i == 14 else 0.02)
        float(step(*pool[i % 4]))
    # step 13 is seen complete half a second late (a loaded machine may
    # add a stall of its own)
    assert _count("mxnet_train_step_stalls_total") >= 1
    assert _count("mxnet_train_step_interval_seconds") == 38
    got = telemetry.snapshot()["step_records"]
    assert len(got["records"]) == 16        # the ring stays bounded
    assert got["records"][0]["step"] == 24
    assert len(got["stalls"]) == _count("mxnet_train_step_stalls_total")
    stall, = [s for s in got["stalls"] if s["step"] == 13]
    assert (stall["step"], stall["net"]) == (13, "HybridSequential")
    assert stall["interval_s"] > 0.5 > 4 * stall["median_s"]
    assert [r["step"] for r in stall["records"]] == [11, 12, 13, 14, 15]
    # the call that saw it says what the process did meanwhile
    seen_by, = stall["calls_meanwhile"]
    assert seen_by["step"] == 14
    assert seen_by["since_previous_call"]["seconds"] == pytest.approx(
        stall["interval_s"])
    assert stall["records"][4]["seen_complete"] is not None  # joined later
    fields, = [f for kind, f in noted
               if kind == "step_stall" and f["step"] == 13]
    assert fields["interval_s"] == stall["interval_s"]
    # the last 8 stalls are kept, and a reset forgets them
    for _ in range(12):
        telemetry._STALLS.append(dict(stall))
    assert len(telemetry.step_records()["stalls"]) == 8
    telemetry.reset()
    assert telemetry.step_records() == {"records": [], "stalls": []}


def test_the_timeline_reads_as_before(net):
    step = _step(net)
    pool = _pool()
    for i in range(3):
        with telemetry.step_scope(10 + i):
            with telemetry.phase("data"):
                batch = pool[i]
            with telemetry.phase("forward_backward"):
                float(step(*batch))
    timeline = telemetry.timeline()
    assert [r["step"] for r in timeline] == [10, 11, 12]
    for r in timeline:
        assert set(r) == {"step", "time", "wall_s", "phases"}
        assert {"data", "forward_backward", PREPARE, EXECUTE} \
            <= set(r["phases"])
        assert sum(r["phases"].values()) == pytest.approx(r["wall_s"])
    snap = telemetry.snapshot()
    assert snap["steps"] == timeline
    assert [r["step"] for r in snap["step_records"]["records"]] == [0, 1, 2]
    # inside a timeline step the phases go to that step; the record's
    # stamps are the same readings
    assert snap["step_phase_totals"][EXECUTE] == pytest.approx(sum(
        r["spans"][EXECUTE][1] - r["spans"][EXECUTE][0]
        for r in snap["step_records"]["records"]), rel=1e-6)


def test_the_records_leave_the_lowered_step_alone(net):
    import re

    from mxnet_tpu import profiler

    def lowered(step, x, y):
        return step._step.lower(
            step.train_params, step.rest_params, step.opt_state,
            jax.random.PRNGKey(0), jax.numpy.asarray(x),
            jax.numpy.asarray(y))

    def names(low):
        """The ops' names in the program's locations (source files and the
        tracing call's stack are there too, and are not the program's)."""
        found = re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True))
        return {n for n in found if n.startswith("jit(")}

    pool = _pool()
    never_called = lowered(_step(net), *pool[0])
    step = _step(net)
    with PrefetchIterator(itertools.cycle(pool), depth=2) as feed:
        for _ in range(3):
            float(step(*next(feed)))
    assert len(_records()) == 3
    called = lowered(step, *pool[0])
    assert called.as_text() == never_called.as_text()
    # the module is named by the scopes' digest, as the parent's rule has
    # it, and no name of the records' is in the program
    assert f"jit_train_step_{profiler.scope_digest()}" in called.as_text()
    assert names(called) == names(never_called)
    assert any(profiler.SCOPE_FORWARD in n for n in names(called))
    for word in ("record", "seen_complete", "prefetch", "in_flight",
                 "track", "batch="):
        assert not any(word in n for n in names(called)), word


def test_the_look_reads_nothing_and_the_scalars_follow_the_dispatch(
        monkeypatch):
    """A step's scalars are transfers from the device: the look that stamps
    a step (before the next one is prepared) makes none, they are read
    once that next step is handed over."""
    import jax.numpy as jnp
    from jax._src import array

    class Counted(gluon.HybridBlock):
        def hybrid_forward(self, F, x):
            telemetry.step_scalar("mxnet_moe_routed_pairs_total",
                                  3.0 * jnp.ones(()))
            return x

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(4), Counted())
    net.initialize()
    net(mx.nd.zeros((8, 8)))
    step = _step(net)
    look, read_during_look = telemetry._look, []

    def looking(now, judged=True):
        with monkeypatch.context() as m:
            m.setattr(array.ArrayImpl, "__array__",
                      lambda *a, **kw: read_during_look.append(1))
            look(now, judged)

    monkeypatch.setattr(telemetry, "_look", looking)
    pool = _pool()
    for i in range(4):
        float(step(*pool[i]))
        # the step before this one was stamped by this call's look and its
        # scalar read behind this call's dispatch
        assert telemetry.MOE_ROUTED_PAIRS.value == 3.0 * i
    assert not read_during_look
    assert _count("mxnet_moe_routed_pairs_total") == 12.0   # the reader's
    assert [r["seen_complete"] is not None for r in _records()] == [True] * 4
