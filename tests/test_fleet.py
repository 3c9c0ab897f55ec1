"""Serving fleet: router, health, policy, and recovery (ISSUE 17).

Unit layers (health state machine, fair-share queue, hedging,
rendezvous affinity, shedding, autoscaler, idempotency ledger) run on
fake replicas with injected clocks — no engine, no sleeps beyond the
hedge windows under test.  The integration layer drives a real
two-replica :class:`LocalReplica` fleet over a shared tiny llama and
proves the recovery contracts end to end: crash-resubmit exactly once,
hedge dedup, cross-process trace grafting, greedy parity with a bare
engine.  Chaos enters only through the four ISSUE-17 fault seams
(``router.dispatch``, ``router.health_probe``, ``fleet.spawn``,
``replica.crash``).
"""
import ast
import pathlib
import threading
import time

import pytest

from mxnet_tpu import fault
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import fleet
from mxnet_tpu.serving.fleet import (EJECTED, HEALTHY, PROBING, SUSPECT,
                                     Autoscaler, FairShareQueue,
                                     FleetBusyError, FleetManager,
                                     HealthMonitor, HedgePolicy,
                                     IdempotencyLedger, ReplicaHandle,
                                     ReplicaHealth, Router,
                                     prefix_key, rendezvous_order)
from mxnet_tpu.serving.scheduler import QueueFullError


# -- fakes ------------------------------------------------------------------
class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class FakeReplica(ReplicaHandle):
    """Replica with a programmable body; transport still flows through
    the funnel so the router.dispatch / router.health_probe seams are
    live exactly as in production."""

    def __init__(self, rid, body=None, **kw):
        super().__init__(rid, **kw)
        self._up = True
        self.served = []

        def default_body(freq):
            return {"rid": self.rid, "request_id": freq.id,
                    "token_ids": [1, 2], "finish_reason": "length"}

        self._body = body or default_body

    def alive(self):
        return self._up

    def kill(self):
        self._up = False

    def probe(self):
        return fleet.call_local(self._probe_body,
                                deadline=time.monotonic() + 1.0,
                                seam="router.health_probe")

    def _probe_body(self):
        if not self._up:
            raise ConnectionError(f"{self.rid} is down")
        return {"queue_depth": 0, "ttft_s": {"p99": 0.001}}

    def submit(self, freq, retries=0):
        return fleet.call_local(self._submit_body, freq,
                                deadline=freq.deadline,
                                seam="router.dispatch", retries=retries)

    def _submit_body(self, freq):
        if not self._up:
            raise ConnectionError(f"{self.rid} is down")
        res = self._body(freq)
        self.served.append(freq.id)
        return res

    def shutdown(self, drain=True, timeout=30):
        self._up = False


def mk_router(replicas, **kw):
    kw.setdefault("hedge_ms", 10_000)      # no hedging unless asked
    kw.setdefault("retry_budget", 0)
    kw.setdefault("probe_interval_ms", 20)
    kw.setdefault("trace_requests", True)
    return Router(replicas=replicas, **kw)


# -- health state machine ---------------------------------------------------
def test_health_ejects_after_threshold_then_halfopen_recovers():
    clk = FakeClock()
    h = ReplicaHealth(eject_threshold=3, cooldown_s=1.0,
                      probe_budget=2, probe_successes=2, clock=clk)
    assert h.state == HEALTHY
    h.note_failure()
    assert h.state == SUSPECT          # below threshold: deprioritized
    h.note_failure()
    h.note_failure()
    assert h.state == EJECTED and not h.dispatchable()
    # cooldown not yet elapsed: still ejected
    clk.advance(0.5)
    h.tick()
    assert h.state == EJECTED
    clk.advance(0.6)
    h.tick()
    assert h.state == PROBING
    # half-open: at most probe_budget concurrent live requests
    assert h.try_acquire_probe()
    assert h.try_acquire_probe()
    assert not h.try_acquire_probe()   # budget exhausted
    h.release_probe()
    assert h.try_acquire_probe()
    # probe_successes consecutive wins restore HEALTHY + reset streak
    h.note_success()
    assert h.state == PROBING
    h.note_success()
    assert h.state == HEALTHY
    assert h.ejections == 0
    assert h.try_acquire_probe()       # healthy grants unconditionally


def test_health_probe_failure_reejects_with_doubled_cooldown():
    clk = FakeClock()
    h = ReplicaHealth(eject_threshold=1, cooldown_s=1.0, clock=clk)
    h.note_failure()
    assert h.state == EJECTED and h.cooldown_s() == 1.0
    clk.advance(1.1)
    h.tick()
    assert h.state == PROBING
    h.note_failure()                   # ANY half-open failure re-ejects
    assert h.state == EJECTED
    assert h.cooldown_s() == 2.0       # doubled
    clk.advance(1.5)
    h.tick()
    assert h.state == EJECTED          # longer cooldown holds
    clk.advance(1.0)
    h.tick()
    assert h.state == PROBING


def test_health_suspect_is_soft():
    h = ReplicaHealth()
    h.note_suspect("queue depth 40")
    assert h.state == SUSPECT
    assert h.consecutive_failures == 0  # no progress toward ejection
    assert h.dispatchable()             # still takes traffic
    h.note_success()
    assert h.state == HEALTHY


def test_monitor_detects_dead_replica_and_fires_once():
    r = FakeReplica("r1")
    dead = []
    mon = HealthMonitor(lambda: [r], on_dead=dead.append)
    mon.poll_once()
    assert r.health.state == HEALTHY and dead == []
    r.kill()
    mon.poll_once()
    mon.poll_once()
    assert dead == [r]                  # exactly once
    assert r.health.consecutive_failures >= 2


def test_monitor_heartbeat_gauges_mark_overload_suspect():
    r = FakeReplica("r1")
    r._probe_body = lambda: {"queue_depth": 99,
                             "ttft_s": {"p99": 0.5}}
    mon = HealthMonitor(lambda: [r], suspect_queue_depth=32)
    mon.poll_once()
    assert r.health.state == SUSPECT
    assert r.health.queue_depth == 99


def test_chaos_health_probe_seam_counts_as_failure():
    r = FakeReplica("r1")
    mon = HealthMonitor(lambda: [r], on_dead=lambda _: None)
    with fault.inject("router.health_probe", error=ConnectionError,
                      times=2):
        mon.poll_once()
        mon.poll_once()
    assert r.health.consecutive_failures == 2
    assert r.health.state == SUSPECT    # alive, so not fired dead
    mon.poll_once()                     # seam disarmed: recovers
    assert r.health.state == HEALTHY


# -- policy -----------------------------------------------------------------
def test_fair_share_interleaves_tenants():
    q = FairShareQueue(bound=64, tenant_bound=32)
    for i in range(6):
        q.put(("a", i), tenant="a")
    for i in range(2):
        q.put(("b", i), tenant="b")
    order = [q.pop_ready() for _ in range(8)]
    # tenant b's 2 requests are NOT stuck behind all 6 of tenant a's
    first_four = order[:4]
    assert {"a", "b"} == {t for t, _ in first_four}
    assert order.count(("b", 0)) == 1 and len(q) == 0


def test_fair_share_bounds_and_requeue_exemption():
    q = FairShareQueue(bound=3, tenant_bound=2)
    q.put(1, tenant="a")
    q.put(2, tenant="a")
    with pytest.raises(QueueFullError):
        q.put(3, tenant="a")            # tenant bound
    q.put(4, tenant="b")
    with pytest.raises(QueueFullError):
        q.put(5, tenant="b")            # global bound
    q.requeue(6, tenant="b")            # bound-exempt, front of line
    assert len(q) == 4


def test_fair_share_pop_ready_expires_outside_lock():
    q = FairShareQueue()
    q.put("dead", tenant="a")
    q.put("live", tenant="a")
    expired = []
    got = q.pop_ready(is_expired=lambda r: r == "dead",
                      on_expire=expired.append)
    assert got == "live" and expired == ["dead"]


def test_hedge_policy_floor_then_p99():
    hp = HedgePolicy(floor_ms=50, min_samples=4)
    assert hp.delay_s() == 0.05         # empty window: floor only
    for _ in range(10):
        hp.observe(0.2)
    assert hp.delay_s() == pytest.approx(0.2)
    hp2 = HedgePolicy(floor_ms=500, min_samples=4)
    for _ in range(10):
        hp2.observe(0.01)
    assert hp2.delay_s() == 0.5         # floor wins over a fast p99


def test_rendezvous_fallback_is_stable_under_removal():
    ids = ["r1", "r2", "r3", "r4"]
    key = prefix_key([5, 6, 7])
    order = rendezvous_order(key, ids)
    # removing the home replica promotes the old fallback — the
    # relative order of survivors NEVER changes (no remap churn)
    survivors = [r for r in ids if r != order[0]]
    assert rendezvous_order(key, survivors) == order[1:]
    # shared prefixes map to the same key (same warm replica)
    assert prefix_key(list(range(16)) + [99]) == \
        prefix_key(list(range(16)) + [42])
    assert prefix_key([1, 2]) != prefix_key([2, 1])


def test_shedding_policy_retry_after_tracks_drain_rate():
    clk = FakeClock()
    sp = fleet.SheddingPolicy(slo_depth=4, clock=clk)
    assert not sp.should_shed(3)
    assert sp.should_shed(4)
    assert sp.retry_after_s(8) == 1.0   # no data yet: floor
    for _ in range(11):
        sp.note_completion()
        clk.advance(0.5)                # 2 completions/s
    assert sp.retry_after_s(8) == pytest.approx(4.0)   # 8 deep / 2 per s
    assert sp.retry_after_s(1000) == 30.0              # clamped


def test_autoscaler_debounce_and_idle_scale_down():
    clk = FakeClock()
    ups, downs = [], []
    a = Autoscaler(scale_up=ups.append, scale_down=downs.append,
                   min_replicas=1, max_replicas=3,
                   replica_count=lambda: 2, cooldown_s=5.0,
                   idle_ticks=3, clock=clk)
    assert a.note_queue_breach(50)
    assert not a.note_queue_breach(60)  # inside cooldown: debounced
    clk.advance(6)
    assert a.note_goodput_breach(0.80, 0.95, 3)
    assert len(ups) == 2 and not downs
    clk.advance(6)
    for _ in range(3):
        a.note_tick(queue_depth=0)
    assert downs and "idle" in downs[0]
    clk.advance(6)
    a2 = Autoscaler(scale_up=ups.append, replica_count=lambda: 3,
                    max_replicas=3, clock=clk)
    assert not a2.note_queue_breach(9)  # at max: no action


def test_idempotency_ledger_first_claim_wins():
    led = IdempotencyLedger(cap=4)
    assert led.claim(1)
    assert not led.claim(1)
    assert led.stats()["duplicates_suppressed"] == 1
    for rid in range(2, 8):
        assert led.claim(rid)
    assert led.stats()["claimed"] <= 4  # bounded


# -- router on fake replicas ------------------------------------------------
def test_router_round_trip_and_trace_tree():
    r1 = FakeReplica("r1")
    router = mk_router([r1]).start()
    try:
        req = router.submit([1, 2, 3], max_new_tokens=4,
                            deadline_ms=10_000)
        res = req.response(timeout=10)
        assert res["rid"] == "r1"
        tree = req.trace.to_dict()
        names = [s["name"] for s in tree["tree"]["children"]]
        assert "queue_wait" in names and "dispatch" in names
        assert tree["trace_id"] == req.id
    finally:
        router.close()


def test_hedge_dedup_delivers_exactly_one_completion():
    release = threading.Event()

    def slow_body(freq):
        release.wait(5)
        return {"rid": "slow", "request_id": freq.id}

    prompt = [7, 8, 9]
    ids = ["r1", "r2"]
    home = rendezvous_order(prefix_key(prompt), sorted(ids))[0]
    other = [r for r in ids if r != home][0]
    reps = {home: FakeReplica(home, body=slow_body),
            other: FakeReplica(other)}
    router = mk_router([reps["r1"], reps["r2"]], hedge_ms=30).start()
    try:
        req = router.submit(prompt, deadline_ms=10_000)
        res = req.response(timeout=10)
        assert res["rid"] == other      # the hedge won
        assert req.hedges == 1
        release.set()                   # let the slow primary finish
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and \
                router._ledger.stats()["duplicates_suppressed"] < 1:
            time.sleep(0.01)
        # the primary's late answer was suppressed, never delivered
        assert router._ledger.stats()["duplicates_suppressed"] == 1
        assert req.result["rid"] == other
        events = [e["name"] for e in req.trace.to_dict()["events"]]
        assert "hedged" in events
    finally:
        release.set()
        router.close()


def test_hedge_not_sent_when_primary_is_fast():
    r1, r2 = FakeReplica("r1"), FakeReplica("r2")
    router = mk_router([r1, r2], hedge_ms=2_000).start()
    try:
        for _ in range(4):
            req = router.submit([3, 1, 4], deadline_ms=10_000)
            req.response(timeout=10)
            assert req.hedges == 0
        assert len(r1.served) + len(r2.served) == 4
    finally:
        router.close()


def test_crash_resubmit_exactly_once_on_fakes():
    """A replica dies mid-request: the health monitor's death handler
    and the failing dispatch thread race to requeue — the atomic
    state machine lets exactly one win, and the survivor serves the
    request exactly once."""
    started = threading.Event()
    prompt = [2, 7, 1]
    ids = ["r1", "r2"]
    home = rendezvous_order(prefix_key(prompt), sorted(ids))[0]
    other = [r for r in ids if r != home][0]

    def dying_body(freq):
        started.set()
        reps[home]._up = False          # the "process" is gone
        raise ConnectionError("killed mid-request")

    reps = {home: FakeReplica(home, body=dying_body),
            other: FakeReplica(other)}
    router = mk_router([reps["r1"], reps["r2"]]).start()
    try:
        req = router.submit(prompt, deadline_ms=10_000)
        assert started.wait(5)
        res = req.response(timeout=10)
        assert res["rid"] == other
        assert reps[other].served == [req.id]      # exactly once
        assert req.attempts >= 2
        led = router._ledger.stats()
        assert led["duplicates_suppressed"] == 0   # no double delivery
    finally:
        router.close()


def test_prefix_affinity_routes_home_then_falls_back_on_ejection():
    reps = [FakeReplica(r) for r in ("r1", "r2", "r3")]
    by_id = {r.rid: r for r in reps}
    prompt = [11, 12, 13]
    order = rendezvous_order(prefix_key(prompt),
                             sorted(by_id))
    router = mk_router(reps).start()
    try:
        for _ in range(3):
            req = router.submit(prompt, deadline_ms=10_000)
            assert req.response(timeout=10)["rid"] == order[0]
        # eject the home: same ordering, next rank takes over
        for _ in range(3):
            by_id[order[0]].health.note_failure()
        assert by_id[order[0]].health.state == EJECTED
        req = router.submit(prompt, deadline_ms=10_000)
        assert req.response(timeout=10)["rid"] == order[1]
    finally:
        router.close()


def test_shedding_429_with_retry_after():
    r1 = FakeReplica("r1")
    router = mk_router([r1], shed_depth=2)      # NOT started: queue grows
    router.submit([1], deadline_ms=10_000)
    router.submit([2], deadline_ms=10_000)
    with pytest.raises(FleetBusyError) as ei:
        router.submit([3], deadline_ms=10_000)
    assert ei.value.retry_after_s >= 1.0
    assert isinstance(ei.value, QueueFullError)  # HTTP layer maps to 429


def test_chaos_dispatch_seam_transient_is_retried():
    r1 = FakeReplica("r1")
    router = mk_router([r1], retry_budget=2).start()
    try:
        before = fault.stats()["router.dispatch"]["trips"]
        with fault.inject("router.dispatch", error=OSError, times=1):
            req = router.submit([5, 5], deadline_ms=10_000)
            res = req.response(timeout=10)
        assert res["rid"] == "r1"       # absorbed by the retry budget
        assert fault.stats()["router.dispatch"]["trips"] == before + 1
        assert req.attempts == 1        # retried INSIDE the attempt
    finally:
        router.close()


def test_chaos_dispatch_seam_exhaustion_fails_over():
    """Trips past the retry budget exhaust the attempt; the failover
    requeue hands the request to the other replica.  What is read is the
    order of events (one failed attempt on the home, then the other serves),
    with the probe thread out of the way: a probe's success between the
    failure and the lines below makes the home healthy again and zeroes its
    failures, which at a probe every 20 ms it did under six xdist workers."""
    prompt = [9, 9, 1]
    ids = ["r1", "r2"]
    home = rendezvous_order(prefix_key(prompt), sorted(ids))[0]
    other = [r for r in ids if r != home][0]
    reps = {r: FakeReplica(r) for r in ids}
    router = mk_router([reps["r1"], reps["r2"]], retry_budget=0,
                       probe_interval_ms=3_600_000).start()
    try:
        with fault.inject("router.dispatch", error=ConnectionError,
                          times=1):
            req = router.submit(prompt, deadline_ms=120_000)
            res = req.response(timeout=120)
        assert res["rid"] == other and req.attempts == 2
        assert reps[home].served == [] and reps[other].served == [req.id]
        assert reps[home].health.consecutive_failures == 1
    finally:
        router.close()


def test_chaos_spawn_seam_retries_then_fleet_heals():
    class StubEngine:
        def running(self):
            return True

        def close(self, drain=True, timeout=0):
            pass

    calls = []

    def factory(rid, donor):
        calls.append(rid)
        return StubEngine()

    mgr = FleetManager(engine_factory=factory, replicas=2,
                       probe_interval_ms=20)
    router = mk_router([])
    mgr.attach_router(router)
    before = fault.stats()["fleet.spawn"]["trips"]
    with fault.inject("fleet.spawn", error=OSError, times=1):
        mgr.ensure(2)
    assert len(router.replicas()) == 2
    assert fault.stats()["fleet.spawn"]["trips"] == before + 1
    assert len(calls) == 2              # the trip retried, not doubled
    assert [r.rid for r in router.replicas()] == \
        ["replica-1", "replica-2"]


def test_router_modules_never_import_jax():
    pkg = pathlib.Path(fleet.__file__).parent
    for py in sorted(pkg.glob("*.py")):
        tree = ast.parse(py.read_text())
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                roots = [a.name.split(".")[0] for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                roots = [(node.module or "").split(".")[0]]
            else:
                continue
            assert "jax" not in roots, (py.name, node.lineno)


def test_fleet_knobs_register_and_describe():
    from mxnet_tpu import env
    assert env.fleet_replicas() >= 1
    assert env.fleet_hedge_ms() >= 0
    assert env.fleet_retry_budget() >= 0
    assert env.fleet_probe_interval_ms() >= 10
    assert env.fleet_eject_threshold() >= 1
    text = env.describe()
    for knob in ("MXNET_FLEET_REPLICAS", "MXNET_FLEET_HEDGE_MS",
                 "MXNET_FLEET_RETRY_BUDGET",
                 "MXNET_FLEET_PROBE_INTERVAL_MS",
                 "MXNET_FLEET_EJECT_THRESHOLD"):
        assert knob in text


def test_all_new_seams_registered():
    for seam in ("router.dispatch", "router.health_probe",
                 "fleet.spawn", "replica.crash"):
        assert seam in fault.SEAMS


# -- integration: real engines ----------------------------------------------
# (marked slow: the module-scoped engine pair costs ~20s of AOT warmup,
# which the `-m 'not slow'` unit tier can't afford; the chaos lane runs
# this file unfiltered, and ci/fleet_smoke.py covers the process mode)
@pytest.fixture(scope="module")
def net():
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.language.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    net(nd.zeros((1, 8), dtype="int32"))
    return net


ENGINE_KW = dict(batch_buckets=[1, 2], prefill_buckets=[8, 16],
                 kv_pages=32, page_size=8, max_batch=2)


def mk_engine(net, donor=None):
    from mxnet_tpu import serving

    if donor is not None:
        return serving.ServingEngine.join_replica(
            net, donor, **ENGINE_KW).start()
    return serving.ServingEngine(net, **ENGINE_KW).start()


@pytest.fixture(scope="module")
def engines(net):
    e1, e2 = mk_engine(net), mk_engine(net)
    yield e1, e2
    for e in (e1, e2):
        try:
            e.close(drain=False, timeout=10)
        except Exception:
            pass


@pytest.mark.slow
def test_local_fleet_parity_and_grafted_trace(net, engines):
    """Greedy completions through the router bit-match a bare engine,
    and the router's trace tree carries the replica's span tree grafted
    under the dispatch span with the router's request id as trace id."""
    e1, e2 = engines
    reps = [fleet.LocalReplica("r1", e1, probe_interval_s=0.05),
            fleet.LocalReplica("r2", e2, probe_interval_s=0.05)]
    router = mk_router(reps, probe_interval_ms=50).start()
    try:
        prompt = [3, 1, 4, 1, 5]
        req = router.submit(prompt, max_new_tokens=6, deadline_ms=30_000)
        res = req.response(timeout=60)
        ref = e1.submit(prompt, max_new_tokens=6).result(timeout=60)
        assert res["token_ids"] == ref["token_ids"]    # greedy parity
        tree = req.trace.to_dict()
        assert tree["trace_id"] == req.id
        disp = [s for s in tree["tree"]["children"]
                if s["name"] == "dispatch"]
        assert disp and "replica_trace" in disp[0]["attrs"]
        grafted = disp[0]["attrs"]["replica_trace"]
        # the replica stamped the ROUTER's id into its own trace
        assert grafted["trace_id"] == req.id
        rep_names = [s["name"]
                     for s in grafted["tree"]["children"]]
        assert any(n.startswith(("prefill", "decode", "queue"))
                   for n in rep_names), rep_names
    finally:
        router.close()


@pytest.mark.slow
def test_chaos_replica_crash_seam_recovers_end_to_end(net, engines):
    """The replica.crash seam takes a real replica down mid-request:
    the request fails over to the survivor, completes exactly once,
    and the trace records the failed dispatch."""
    e1, e2 = engines
    reps = [fleet.LocalReplica("r1", e1, probe_interval_s=0.05),
            fleet.LocalReplica("r2", e2, probe_interval_s=0.05)]
    router = mk_router(reps, probe_interval_ms=50).start()
    try:
        with fault.inject("replica.crash", error=OSError, times=1):
            req = router.submit([2, 7, 1, 8], max_new_tokens=4,
                                deadline_ms=30_000)
            res = req.response(timeout=60)
        assert res["finish_reason"] in ("length", "stop", "eos")
        # exactly one replica handle went dark
        assert sum(0 if r.alive() else 1 for r in reps) == 1
        assert router._ledger.stats()["duplicates_suppressed"] == 0
        events = [e["name"] for e in req.trace.to_dict()["events"]]
        assert "dispatch_failed" in events
        # both engines themselves still run (the HANDLE died, the
        # donor-able engine survives for join_replica warm paths)
        assert e1.running() and e2.running()
    finally:
        router.close()


@pytest.mark.slow
def test_fleet_manager_warm_replacement_via_join_replica(net, engines):
    """Killing a LocalReplica triggers the manager's heal path: the
    replacement is spawned through the fleet.spawn seam with a healthy
    donor engine (ServingEngine.join_replica) and serves traffic."""
    e1, _ = engines
    extra = mk_engine(net)
    spawned = []

    def factory(rid, donor):
        assert donor is not None        # warm path: donated params
        eng = mk_engine(net, donor=donor)
        spawned.append(eng)
        return eng

    reps = [fleet.LocalReplica("k1", extra, probe_interval_s=0.05),
            fleet.LocalReplica("k2", e1, probe_interval_s=0.05)]
    mgr = FleetManager(engine_factory=factory, replicas=2,
                       probe_interval_ms=50)
    router = mk_router(reps, probe_interval_ms=50, manager=mgr)
    mgr.attach_router(router)
    router.start()
    try:
        reps[0].kill()                  # takes the extra engine down
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            rids = [r.rid for r in router.replicas()]
            if "k1" not in rids and any(
                    r.startswith("replica-") for r in rids):
                break
            time.sleep(0.05)
        rids = [r.rid for r in router.replicas()]
        assert "k1" not in rids
        assert any(r.startswith("replica-") for r in rids), rids
        assert spawned                  # went through the factory
        req = router.submit([6, 6, 6], max_new_tokens=4,
                            deadline_ms=30_000)
        assert req.response(timeout=60)["finish_reason"]
        assert mgr.spawn_times and \
            mgr.spawn_times[0][1] == "replacement"
    finally:
        router.close()
        for eng in spawned:
            try:
                eng.close(drain=False, timeout=10)
            except Exception:
                pass


@pytest.mark.slow
def test_router_http_front_door(net, engines):
    """POST /v1/completions end to end, /v1/fleet snapshot, and the
    fleet block stamped on the response."""
    import http.client
    import json as _json

    from mxnet_tpu import telemetry

    e1, _ = engines
    reps = [fleet.LocalReplica("h1", e1, probe_interval_s=0.05)]
    router = mk_router(reps, probe_interval_ms=50).start()
    server = telemetry.start_http_server(0)
    port = server.server_address[1]
    router.mount_http()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/v1/completions", body=_json.dumps(
            {"prompt": [1, 2, 3], "max_new_tokens": 4,
             "deadline_ms": 30_000}),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        doc = _json.loads(resp.read())
        assert resp.status == 200, doc
        assert doc["fleet"]["request_id"] > 0
        assert len(doc["token_ids"]) == 4
        conn.request("GET", "/v1/fleet")
        fdoc = _json.loads(conn.getresponse().read())
        assert fdoc["replicas"][0]["health"]["state"] == HEALTHY
        conn.request("GET", "/v1/requests")
        rdoc = _json.loads(conn.getresponse().read())
        assert rdoc["enabled"] and rdoc["traced_requests"] >= 1
        conn.close()
    finally:
        router.close()
        telemetry.stop_http_server()
