"""Zero-downtime elasticity: plan-to-plan live resharding + the fused
step's one dispatch route and the one persistent cache a restart warms
from (ISSUE 13, ISSUE 44).

Acceptance pins:
- the transfer plan is pure and digest-stable (identical across fresh
  processes — the determinism contract sharding/bucket plans set);
- params AND ZeRO momentum live-resharded dp=8 → dp=4/2 bit-match both
  the uninterrupted run and the checkpoint-restore path;
- a ``resharding.transfer`` fault costs one supervised retry, never
  torn state;
- a warm restart (TrainStep, serving) finds every executable in JAX's
  persistent cache and adds none (in real child processes);
- a TrainStep keeps one executable a signature, donates its state at
  every step and, over a mesh, hands it back in the placed layout;
- serving replica handoff: the joiner's output bit-matches, the donor
  keeps serving, join-to-first-token is measured.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, fault, gluon, nd, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.checkpoint import CheckpointManager, run_with_recovery
from mxnet_tpu.parallel import planner, resharding
from mxnet_tpu.parallel.functional import functionalize

import jax
import jax.numpy as jnp

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# helpers (the test_planner conventions)
# ---------------------------------------------------------------------------
def _tiny_net(width=8, hidden=16, out=4, seed=0):
    np.random.seed(seed)
    mx.random.seed(seed)
    from mxnet_tpu.gluon import block as _block

    _block._NAME_SCOPE.counters.clear()
    del _block._NAME_SCOPE.scope_stack[:]
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(hidden, activation="relu"),
            gluon.nn.Dense(out))
    net.initialize()
    net(nd.zeros((2, width)))
    return net


def _plan_for_net(net, dp):
    _, params = functionalize(net)
    cfg = planner.PlannerConfig(mesh={"dp": dp}, rules="replicated",
                                optimizer="sgd_momentum", zero=True)
    return planner.plan_sharding(cfg, planner.signature_of(params), dp)


def _one_step(net, tr, rng, width=8, out=4, batch=8):
    x = nd.array(rng.randn(batch, width).astype("f"))
    y = nd.array((rng.randn(batch, out) > 0).astype("f"))
    with autograd.record():
        loss = ((net(x) - y) ** 2).mean()
    loss.backward()
    tr.step(batch)


def _zero_train(steps, net=None, trainer=None, skip=0):
    os.environ["MXNET_ZERO"] = "1"
    if net is None:
        net = _tiny_net(seed=0)
    if trainer is None:
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1, "momentum": 0.9},
                                kvstore="device")
    rng = np.random.RandomState(7)
    for _ in range(skip):
        rng.randn(8, 8), rng.randn(8, 4)
    for _ in range(steps):
        _one_step(net, trainer, rng)
    return net, trainer


def _net_params(net):
    return {k: v.data().asnumpy()
            for k, v in net.collect_params().items()}


def _assert_params_equal(a, b):
    assert len(a) == len(b)
    for (ka, va), (kb, vb) in zip(sorted(a.items()), sorted(b.items())):
        assert np.array_equal(va, vb), (ka, kb)


def _assert_payloads_equal(pa, pb):
    assert set(pa["members"]) == set(pb["members"])
    for k in pa["members"]:
        for x, y in zip(pa["members"][k], pb["members"][k]):
            assert np.array_equal(np.asarray(x), np.asarray(y)), k


@pytest.fixture(autouse=True)
def _clean_env():
    planner.set_default_plan(None)
    yield
    planner.set_default_plan(None)
    os.environ.pop("MXNET_ZERO", None)
    fault.reset_stats()


# ---------------------------------------------------------------------------
# transfer plan: purity / digest stability
# ---------------------------------------------------------------------------
def _fsdp_plan(net_or_sig, n, fsdp):
    sig = net_or_sig if isinstance(net_or_sig, tuple) else \
        planner.signature_of(functionalize(net_or_sig)[1])
    cfg = planner.PlannerConfig(mesh={"dp": 1, "fsdp": fsdp},
                                rules="fsdp")
    return planner.plan_sharding(cfg, sig, n)


def test_transfer_plan_pure_and_digest_stable():
    net = _tiny_net(seed=0)
    sig = planner.signature_of(functionalize(net)[1])
    p8, p4 = _fsdp_plan(sig, 8, 8), _fsdp_plan(sig, 4, 4)
    a = resharding.compute_transfer_plan(p8, p4, sig)
    b = resharding.compute_transfer_plan(p8, p4, sig)
    assert a.digest() == b.digest()
    assert a.total_bytes() > 0
    # json round-trip is the digest's substrate: must be loadable
    doc = json.loads(a.to_json())
    assert doc["entries"][0]["kind"] == "param"
    # zero buckets extend the same plan with flat entries
    z = resharding.compute_transfer_plan(
        p8, p4, sig, zero_buckets=[("gen-1.b0", 100, "float32", 1)])
    assert any(e["kind"] == "zero" for e in z.entries)
    assert z.digest() != a.digest()
    # the planner-side entry point is the same pure function
    via_plan = p8.transfer_plan_to(p4, signature=sig)
    assert via_plan.digest() == a.digest()
    a.discard(), b.discard(), z.discard(), via_plan.discard()


def test_transfer_plan_digest_equal_across_processes():
    """The determinism fingerprint the elastic smoke compares: a FRESH
    interpreter computes a byte-identical plan."""
    child = (
        "import sys; sys.path.insert(0, %r)\n"
        "import os\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
        "flags = os.environ.get('XLA_FLAGS', '')\n"
        "if 'xla_force_host_platform_device_count' not in flags:\n"
        "    os.environ['XLA_FLAGS'] = (flags + "
        "' --xla_force_host_platform_device_count=8').strip()\n"
        "from mxnet_tpu.parallel import planner, resharding\n"
        "sig = (('dense0.weight', (16, 8), 'float32'),"
        " ('dense0.bias', (16,), 'float32'))\n"
        "p8 = planner.plan_sharding(planner.PlannerConfig("
        "mesh={'dp': 1, 'fsdp': 8}, rules='fsdp'), sig, 8)\n"
        "p4 = planner.plan_sharding(planner.PlannerConfig("
        "mesh={'dp': 1, 'fsdp': 4}, rules='fsdp'), sig, 4)\n"
        "plan = resharding.compute_transfer_plan(p8, p4, sig,"
        " zero_buckets=[('g.b0', 100, 'float32', 1)])\n"
        "print(plan.digest())\n"
        "plan.discard()\n"
    ) % REPO_ROOT
    outs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", child],
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        outs.append(r.stdout.strip().splitlines()[-1])
    assert outs[0] == outs[1]
    assert len(outs[0]) == 64


# ---------------------------------------------------------------------------
# param transfers: bit parity across layouts
# ---------------------------------------------------------------------------
def test_param_transfer_bit_parity_fsdp8_to_fsdp4():
    rng = np.random.RandomState(0)
    arrs = {"w": jnp.asarray(rng.randn(16, 8).astype("f")),
            "b": jnp.asarray(rng.randn(16).astype("f"))}
    sig = planner.signature_of(arrs)
    p8, p4 = _fsdp_plan(sig, 8, 8), _fsdp_plan(sig, 4, 4)
    m8 = p8.build_mesh()
    placed = {k: jax.device_put(v, p8.sharding(k, m8))
              for k, v in arrs.items()}
    out = resharding.transfer_params(placed, src_plan=p8, tgt_plan=p4)
    for k, v in arrs.items():
        assert np.array_equal(np.asarray(out[k]), np.asarray(v)), k
        # genuinely in the target layout
        assert "fsdp" in str(out[k].sharding.spec)


def test_param_transfer_replicated_roundtrip_and_budget():
    rng = np.random.RandomState(1)
    arrs = {"w": jnp.asarray(rng.randn(32, 8).astype("f"))}
    sig = planner.signature_of(arrs)
    rep = planner.plan_sharding(
        planner.PlannerConfig(mesh={"dp": 1}, rules="replicated"), sig, 1)
    p4 = _fsdp_plan(sig, 4, 4)
    # a tiny in-flight budget forces many rounds; parity must hold
    sharded = resharding.transfer_params(arrs, src_plan=rep, tgt_plan=p4,
                                         budget_bytes=64)
    back = resharding.transfer_params(sharded, src_plan=p4, tgt_plan=rep,
                                      budget_bytes=64)
    assert np.array_equal(np.asarray(back["w"]), np.asarray(arrs["w"]))


# ---------------------------------------------------------------------------
# acceptance: dp=8 -> dp=4/2 live reshard ==bit== checkpoint restore
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sub_dp", [4, 2])
def test_zero_live_reshard_bit_matches_checkpoint_restore(tmp_path,
                                                          sub_dp):
    """Three trajectories over the same batches must be bit-identical in
    params AND momentum: (a) uninterrupted 5 steps under a dp=8 plan,
    (b) 3 steps + save_states + load_states under a dp=sub plan + 2
    steps (the PR 10 elastic-restore path), (c) 3 steps + LIVE
    ``ZeroBucketEngine.reshard`` to the dp=sub plan + 2 steps — no disk
    round trip."""
    # (a) uninterrupted
    planner.set_default_plan(_plan_for_net(_tiny_net(seed=0), 8))
    full_net, full_tr = _zero_train(5, net=_tiny_net(seed=0))
    full_payload = full_tr._zero.state_payload()

    # (b) checkpoint-restore path
    planner.set_default_plan(_plan_for_net(_tiny_net(seed=0), 8))
    net_b, tr_b = _zero_train(3, net=_tiny_net(seed=0))
    fname = str(tmp_path / f"trainer_{sub_dp}.states")
    tr_b.save_states(fname)
    plan_sub = _plan_for_net(_tiny_net(seed=0), sub_dp)
    planner.set_default_plan(plan_sub)
    os.environ["MXNET_ZERO"] = "1"
    net_b2 = _tiny_net(seed=0)
    for (_, p2), (_, p1) in zip(sorted(net_b2.collect_params().items()),
                                sorted(net_b.collect_params().items())):
        p2.set_data(p1.data())
    tr_b2 = gluon.Trainer(net_b2.collect_params(), "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9},
                          kvstore="device")
    tr_b2.load_states(fname)
    _zero_train(2, net=net_b2, trainer=tr_b2, skip=3)

    # (c) live reshard — surviving in-process state, no disk
    planner.set_default_plan(_plan_for_net(_tiny_net(seed=0), 8))
    net_c, tr_c = _zero_train(3, net=_tiny_net(seed=0))
    assert tr_c._zero.dp == 8
    tr_c._zero.reshard(plan_sub)
    planner.set_default_plan(plan_sub)
    assert tr_c._zero.dp == sub_dp
    _zero_train(2, net=net_c, trainer=tr_c, skip=3)

    _assert_params_equal(_net_params(full_net), _net_params(net_b2))
    _assert_params_equal(_net_params(full_net), _net_params(net_c))
    _assert_payloads_equal(full_payload, tr_b2._zero.state_payload())
    _assert_payloads_equal(full_payload, tr_c._zero.state_payload())


def test_zero_live_reshard_grow_dp2_to_dp8():
    """Elasticity goes both ways: a grown pod reshards dp=2 state onto
    the dp=8 plan and continues bit-identically."""
    planner.set_default_plan(_plan_for_net(_tiny_net(seed=0), 2))
    full_net, full_tr = _zero_train(5, net=_tiny_net(seed=0))
    planner.set_default_plan(_plan_for_net(_tiny_net(seed=0), 2))
    net, tr = _zero_train(3, net=_tiny_net(seed=0))
    plan8 = _plan_for_net(_tiny_net(seed=0), 8)
    tr._zero.reshard(plan8)
    planner.set_default_plan(plan8)
    _zero_train(2, net=net, trainer=tr, skip=3)
    assert tr._zero.dp == 8
    _assert_params_equal(_net_params(full_net), _net_params(net))
    _assert_payloads_equal(full_tr._zero.state_payload(),
                           tr._zero.state_payload())


# ---------------------------------------------------------------------------
# fault: one supervised retry, never torn state
# ---------------------------------------------------------------------------
def test_transfer_fault_costs_one_retry_never_torn():
    rng = np.random.RandomState(2)
    arrs = {"w": jnp.asarray(rng.randn(16, 8).astype("f"))}
    sig = planner.signature_of(arrs)
    p8, p4 = _fsdp_plan(sig, 8, 8), _fsdp_plan(sig, 4, 4)
    fault.reset_stats()
    with fault.inject("resharding.transfer", error=OSError, times=1):
        out = resharding.transfer_params(arrs, src_plan=p8, tgt_plan=p4)
    st = fault.stats()["resharding.transfer"]
    assert st["trips"] == 1 and st["retries"] == 1
    assert np.array_equal(np.asarray(out["w"]), np.asarray(arrs["w"]))


def test_transfer_fault_exhaustion_leaves_source_whole():
    """Retry exhaustion raises — and the SOURCE state is untouched, so
    the checkpoint fallback (or a later retry) starts from intact
    arrays, never torn ones."""
    planner.set_default_plan(_plan_for_net(_tiny_net(seed=0), 8))
    net, tr = _zero_train(3, net=_tiny_net(seed=0))
    before = tr._zero.state_payload()
    plan2 = _plan_for_net(_tiny_net(seed=0), 2)
    with fault.inject("resharding.transfer", error=OSError, times=10):
        with pytest.raises(MXNetError):
            tr._zero.reshard(plan2)
    # the engine's resident leaves were never swapped: harvest equals
    # the pre-fault payload bit for bit, and a clean reshard still works
    _assert_payloads_equal(before, tr._zero.state_payload())
    tr._zero.reshard(plan2)
    _assert_payloads_equal(before, tr._zero.state_payload())


def test_run_with_recovery_live_reshard_path(tmp_path):
    """The supervisor takes the live path when the resharder accepts,
    and the checkpoint path when it declines — chosen automatically per
    failure."""
    from mxnet_tpu import lifecycle

    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    calls = {"train": [], "reshard": 0}
    state = {"intact": True, "step": 7}

    def check_fn(exc):
        return state["intact"], state["step"]

    def reshard_fn(step):
        calls["reshard"] += 1
        return step

    resharder = lifecycle.elastic_resharder(check_fn, reshard_fn)

    def train(start, manager):
        calls["train"].append(start)
        if len(calls["train"]) == 1:
            manager.save(3)
            raise OSError("preempted")
        if len(calls["train"]) == 2:
            state["intact"] = False       # second failure: state damaged
            raise OSError("preempted again")
        return "done"

    assert run_with_recovery(train, mgr, max_restarts=3,
                             resharder=resharder) == "done"
    # start steps: 0 (fresh), 7 (live reshard), 3 (checkpoint fallback)
    assert calls["train"] == [0, 7, 3]
    assert calls["reshard"] == 1


def test_run_with_recovery_live_progress_resets_budget(tmp_path):
    """A job preempted more often than it checkpoints but recovering
    through ADVANCING live reshards is healthy: live progress resets
    the restart budget exactly like checkpoint progress (review
    finding: the budget verdict must come after the resharder)."""
    mgr = CheckpointManager(str(tmp_path / "ck"))
    n = {"i": 0}

    def train(start, manager):
        n["i"] += 1
        if n["i"] <= 5:
            raise OSError("preempted")
        return "done"

    # live step advances on every recovery; 5 failures > max_restarts=2
    # must still succeed because progress keeps resetting the budget
    out = run_with_recovery(train, mgr, max_restarts=2,
                            resharder=lambda exc: n["i"] * 10)
    assert out == "done"
    assert n["i"] == 6


def test_elastic_resharder_swallows_nothing_on_decline():
    from mxnet_tpu import lifecycle

    resharder = lifecycle.elastic_resharder(
        lambda exc: (False, None), lambda step: 99)
    assert resharder(RuntimeError("x")) is None


def test_elastic_resharder_check_fn_raise_is_a_not_intact_vote():
    """A check_fn that raises (probing torn state) must become a
    not-intact VOTE — the agreement collective is still issued, so
    peers are never stranded in it (review finding)."""
    from mxnet_tpu import lifecycle
    from mxnet_tpu.parallel import resharding as rs

    votes = []
    orig = rs.peers_agree_intact

    def spy(ok):
        votes.append(ok)
        return orig(ok)

    def bad_check(exc):
        raise ValueError("probing torn state went wrong")

    rs_mod_attr = "peers_agree_intact"
    setattr(rs, rs_mod_attr, spy)
    try:
        resharder = lifecycle.elastic_resharder(bad_check,
                                                lambda step: 99)
        assert resharder(RuntimeError("x")) is None
    finally:
        setattr(rs, rs_mod_attr, orig)
    assert votes == [False]     # the collective WAS issued, voting no


def test_run_with_recovery_checkpoint_progress_after_lost_live_reshard(
        tmp_path):
    """A live reshard that outran the checkpoints and was then lost
    must not poison the budget: later checkpoint advances BELOW the
    lost live step are still progress (per-path markers, review
    finding)."""
    mgr = CheckpointManager(str(tmp_path / "ck"))
    n = {"i": 0}

    def resharder(exc):
        # first failure recovers live at step 50; afterwards the state
        # is gone and every recovery falls back to checkpoints
        return 50 if n["i"] == 1 else None

    def train(start, manager):
        n["i"] += 1
        if n["i"] == 1:
            raise OSError("preempted at live step 50")
        if n["i"] <= 5:
            manager.save(n["i"] * 2)     # 4, 6, 8, 10 — all below 50
            raise OSError("preempted again")
        return "done"

    # 5 failures with max_restarts=1: every post-live failure advanced
    # the CHECKPOINT clock, so the budget keeps resetting
    out = run_with_recovery(train, mgr, max_restarts=1,
                            resharder=resharder)
    assert out == "done"


# ---------------------------------------------------------------------------
# warm restart: the one persistent cache (JAX's) holds what a second
# process needs
# ---------------------------------------------------------------------------
_CHILD_HEAD = """
import sys; sys.path.insert(0, {root!r})
import json
import numpy as np
import mxnet_tpu as mx
"""

_WARM_CHILDREN = {
    "train_step": _CHILD_HEAD + """
from mxnet_tpu import gluon
from mxnet_tpu.parallel.data_parallel import TrainStep

np.random.seed(0); mx.random.seed(0)
net = gluon.nn.HybridSequential()
net.add(gluon.nn.Dense(16, activation="relu", in_units=8),
        gluon.nn.Dense(4, in_units=16))
net.initialize()
step = TrainStep(net, lambda out, y: (out - y) ** 2, optimizer="sgd",
                 optimizer_params={{"learning_rate": 0.1,
                                    "momentum": 0.9}})
rng = np.random.RandomState(7)
walked = []
for _ in range(3):
    x = rng.randn(8, 8).astype("f")
    y = (rng.randn(8, 4) > 0).astype("f")
    walked.append(float(np.asarray(step(x, y))))
walked.append(float(sum(np.asarray(v).sum()
                        for v in step.train_params.values())))
print(json.dumps(walked))
""",
    "serving": _CHILD_HEAD + """
from mxnet_tpu.gluon.model_zoo.language import llama
from mxnet_tpu.serving.engine import ServingEngine

np.random.seed(0); mx.random.seed(0)
net = llama.LlamaForCausalLM(llama.LlamaConfig(
    vocab_size=32, hidden_size=16, num_layers=1, num_heads=2,
    num_kv_heads=1, intermediate_size=32, max_seq_len=32))
net.initialize(ctx=mx.cpu())
eng = ServingEngine(net, batch_buckets=[1], prefill_buckets=[8],
                    kv_pages=3, page_size=4, max_batch=1)
eng.start()
out = eng.submit([1, 2, 3, 4], max_new_tokens=3).result(60)
eng.close()
print(json.dumps(out["token_ids"]))
""",
}


@pytest.mark.parametrize("program", sorted(_WARM_CHILDREN))
def test_warm_restart_adds_nothing_to_the_persistent_cache(
        program, tmp_path):
    """A second process of the same program finds every executable it
    needs in JAX's persistent cache (it stores none) and walks the same
    losses / tokens: the warm-restart property, of the one cache."""
    cache_dir = tmp_path / "jax_cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               # the toys compile in under the 1 s the cache skips
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")

    def run():
        r = subprocess.run(
            [sys.executable, "-c",
             _WARM_CHILDREN[program].format(root=REPO_ROOT)],
            env=env, capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr
        stored = sorted(f for f in os.listdir(cache_dir)
                        if not f.endswith("-atime"))
        return json.loads(r.stdout.strip().splitlines()[-1]), stored

    cold, stored = run()
    assert stored                      # the cold run filled the cache
    warm, stored_after = run()
    assert stored_after == stored      # the warm run compiled nothing
    assert warm == cold


# ---------------------------------------------------------------------------
# TrainStep: one dispatch route
# ---------------------------------------------------------------------------
def _sq_loss(out, y):
    return (out - y) ** 2


def _tiny_step(**kw):
    from mxnet_tpu.parallel.data_parallel import TrainStep

    return TrainStep(_tiny_net(seed=3), _sq_loss, optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1,
                                       "momentum": 0.9}, **kw)


def _batch(rows=8, seed=5):
    rng = np.random.RandomState(seed)
    return (rng.randn(rows, 8).astype("f"),
            (rng.randn(rows, 4) > 0).astype("f"))


def test_every_step_donates_its_state():
    """The state a step takes is the state it gives back, in place: after
    every call, the first one too, the buffers it was handed are gone."""
    step = _tiny_step()
    for _ in range(3):
        held = jax.tree_util.tree_leaves(
            (step.train_params, step.opt_state))
        step(*_batch())
        assert held and all(leaf.is_deleted() for leaf in held)


def test_one_executable_a_signature():
    step = _tiny_step()
    for _ in range(5):
        for rows in (8, 4):
            step(*_batch(rows))
    assert len(step._seen_sigs) == 2
    assert sorted(step._compiled) == sorted(step._seen_sigs)
    for compiled, flops in step._compiled.values():
        assert callable(compiled) and (flops is None or flops > 0)
    assert step.step_count == 10


@pytest.mark.parametrize("fault_kind", ["shape", "device"])
def test_a_failing_executable_raises(fault_kind, monkeypatch):
    """A state the signature's executable cannot take is the caller's
    error: it comes out of the call, and nothing else is lowered in the
    executable's place."""
    step = _tiny_step()
    step(*_batch())
    (sig, entry), = step._compiled.items()
    lowered = []
    real = step._aot_step
    monkeypatch.setattr(
        step, "_aot_step",
        lambda args: lowered.append(1) or real(args))
    name = sorted(step.train_params)[0]
    leaf = step.train_params[name]
    step.train_params[name] = jnp.zeros((3, 3), leaf.dtype) \
        if fault_kind == "shape" else \
        jax.device_put(np.asarray(leaf), jax.devices()[1])
    with pytest.raises((TypeError, ValueError)):
        step(*_batch())
    assert not lowered
    assert step._compiled == {sig: entry}


# ---------------------------------------------------------------------------
# serving: replica handoff + chaos seams
# ---------------------------------------------------------------------------
def _make_llama_net():
    from mxnet_tpu.gluon.model_zoo.language import llama

    cfg = llama.LlamaConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=4, num_kv_heads=2,
                            intermediate_size=48, max_seq_len=64)
    net = llama.LlamaForCausalLM(cfg)
    net.initialize(ctx=mx.cpu())
    net(mx.nd.zeros((1, 8), dtype="int32"))
    return net


_SERVE_KW = dict(batch_buckets=[1], prefill_buckets=[8], kv_pages=16,
                 page_size=4, max_batch=1)


def test_serving_replica_handoff_bit_match_and_join_metric():
    from mxnet_tpu.serving.engine import ServingEngine

    net = _make_llama_net()
    prompt = [1, 2, 3, 4, 5, 6]
    donor = ServingEngine(net, **_SERVE_KW)
    donor.start()
    ref = donor.submit(prompt, max_new_tokens=4).result(60)

    def _join_count():
        fam = telemetry.snapshot()["metrics"].get(
            "mxnet_serving_join_to_first_token_seconds", {})
        return sum(s.get("count", 0) for s in fam.get("samples", []))

    before = _join_count()
    joiner = ServingEngine.join_replica(net, donor, **_SERVE_KW)
    joiner.start()
    out = joiner.submit(prompt, max_new_tokens=4).result(60)
    # the donor kept serving through (and after) the handoff
    ref2 = donor.submit(prompt, max_new_tokens=4).result(60)
    joiner.close()
    donor.close()
    assert out["token_ids"] == ref["token_ids"]
    assert ref2["token_ids"] == ref["token_ids"]
    assert _join_count() == before + 1


def test_serving_admit_fault_requeues_not_loses():
    from mxnet_tpu.serving.engine import ServingEngine

    net = _make_llama_net()
    eng = ServingEngine(net, **_SERVE_KW)
    eng.start()
    try:
        ref = eng.submit([1, 2, 3], max_new_tokens=3).result(60)
        with fault.inject("serving.admit", error=OSError, times=2):
            out = eng.submit([1, 2, 3], max_new_tokens=3).result(60)
        assert out["token_ids"] == ref["token_ids"]
        assert fault.stats()["serving.admit"]["trips"] == 2
    finally:
        eng.close()


def test_serving_decode_fault_absorbed_no_torn_state():
    from mxnet_tpu.serving.engine import ServingEngine

    net = _make_llama_net()
    eng = ServingEngine(net, **_SERVE_KW)
    eng.start()
    try:
        ref = eng.submit([1, 2, 3], max_new_tokens=4).result(60)
        with fault.inject("serving.decode_step", error=RuntimeError,
                          times=2):
            out = eng.submit([1, 2, 3], max_new_tokens=4).result(60)
        # killed decode steps retried; the sequence is bit-identical
        assert out["token_ids"] == ref["token_ids"]
        assert fault.stats()["serving.decode_step"]["trips"] == 2
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# seam registry integration
# ---------------------------------------------------------------------------
def test_new_seams_registered():
    for seam in ("serving.admit", "serving.decode_step",
                 "resharding.transfer"):
        assert seam in fault.SEAMS
        fault.check(seam)          # counts, does not raise when unarmed
        assert fault.stats()[seam]["calls"] >= 1
