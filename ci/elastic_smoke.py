"""Chaos-lane elasticity smoke (ISSUE 13): live resharding + a warm
restart out of JAX's persistent compilation cache, against REAL child
processes.

Run by ci/runtest.sh chaos as:

    python ci/elastic_smoke.py

1. **Live reshard** — a child pod trains ZeRO under a dp=4 planner
   mesh, "shrinks" to dp=2 mid-run and RESHARDS IN-FLIGHT
   (``ZeroBucketEngine.reshard``, no checkpoint round trip), then
   finishes; the child asserts params AND momentum bit-match the
   uninterrupted dp=4 run.  Two children also print the transfer
   plan's digest — the parent asserts cross-process determinism.
2. **Warm restart** — a child trains a TrainStep and reports (losses,
   restart-to-first-step wall time).  The parent runs it twice with
   ``JAX_COMPILATION_CACHE_DIR`` at one temporary directory: the SECOND
   (warm) child must add no entry to it (every executable it needs was
   there), walk a bit-identical trajectory, and beat the cold child's
   restart-to-first-step.
"""
import json
import os
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()


def _bootstrap():
    import jax

    jax.config.update("jax_platforms", "cpu")


# ---------------------------------------------------------------------------
# child: dp=4 -> dp=2 live reshard, bit-identical resume
# ---------------------------------------------------------------------------
def child_reshard():
    _bootstrap()
    os.environ["MXNET_ZERO"] = "1"
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.parallel import planner, resharding
    from mxnet_tpu.parallel.functional import functionalize

    def tiny(seed=0):
        np.random.seed(seed)
        mx.random.seed(seed)
        from mxnet_tpu.gluon import block as _block

        _block._NAME_SCOPE.counters.clear()
        del _block._NAME_SCOPE.scope_stack[:]
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16, activation="relu"),
                gluon.nn.Dense(4))
        net.initialize()
        net(nd.zeros((2, 8)))
        return net

    def plan_for(net, dp):
        _, params = functionalize(net)
        cfg = planner.PlannerConfig(mesh={"dp": dp}, rules="replicated",
                                    optimizer="sgd_momentum", zero=True)
        return planner.plan_sharding(cfg, planner.signature_of(params),
                                     dp)

    def train(net, tr, rng, n):
        for _ in range(n):
            x = nd.array(rng.randn(8, 8).astype("f"))
            y = nd.array((rng.randn(8, 4) > 0).astype("f"))
            with autograd.record():
                loss = ((net(x) - y) ** 2).mean()
            loss.backward()
            tr.step(8)

    def trainer(net):
        return gluon.Trainer(net.collect_params(), "sgd",
                             {"learning_rate": 0.1, "momentum": 0.9},
                             kvstore="device")

    # uninterrupted dp=4 reference
    planner.set_default_plan(plan_for(tiny(0), 4))
    net_a = tiny(0)
    tr_a = trainer(net_a)
    train(net_a, tr_a, np.random.RandomState(7), 5)
    pay_a = tr_a._zero.state_payload()

    # the "pod shrink": 3 steps at dp=4, live reshard to dp=2, 2 more
    planner.set_default_plan(plan_for(tiny(0), 4))
    net_b = tiny(0)
    tr_b = trainer(net_b)
    rng = np.random.RandomState(7)
    train(net_b, tr_b, rng, 3)
    plan2 = plan_for(tiny(0), 2)
    t0 = time.perf_counter()
    tr_b._zero.reshard(plan2)
    reshard_s = time.perf_counter() - t0
    planner.set_default_plan(plan2)
    train(net_b, tr_b, rng, 2)
    assert tr_b._zero.dp == 2, tr_b._zero.dp

    for (ka, pa), (kb, pb) in zip(
            sorted(net_a.collect_params().items()),
            sorted(net_b.collect_params().items())):
        assert np.array_equal(pa.data().asnumpy(),
                              pb.data().asnumpy()), (ka, kb)
    pay_b = tr_b._zero.state_payload()
    assert set(pay_a["members"]) == set(pay_b["members"])
    for k in pay_a["members"]:
        for a, b in zip(pay_a["members"][k], pay_b["members"][k]):
            assert np.array_equal(np.asarray(a), np.asarray(b)), k

    # the determinism fingerprint the parent compares across children
    sig = planner.signature_of(functionalize(tiny(0))[1])
    tplan = resharding.compute_transfer_plan(
        plan_for(tiny(0), 4), plan2, sig,
        zero_buckets=[("smoke.b0", 100, "float32", 1)])
    digest = tplan.digest()
    tplan.discard()
    print(json.dumps({"digest": digest,
                      "reshard_s": round(reshard_s, 4),
                      "reshard_bytes": tplan.total_bytes()}))


# ---------------------------------------------------------------------------
# child: TrainStep under the parent's cache directory; prints timing
# ---------------------------------------------------------------------------
def child_train():
    _bootstrap()
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, telemetry
    from mxnet_tpu.parallel import resharding
    from mxnet_tpu.parallel.data_parallel import TrainStep

    np.random.seed(0)
    mx.random.seed(0)
    # deep enough that compiling dominates the first step (the
    # quantity the cache removes) over timer noise on a loaded CI host
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(64, activation="relu", in_units=8),
            gluon.nn.Dense(64, activation="relu", in_units=64),
            gluon.nn.Dense(64, activation="relu", in_units=64),
            gluon.nn.Dense(4, in_units=64))
    net.initialize()

    def loss_fn(out, y):
        return (out - y) ** 2

    # restart-to-first-step: the recovery-path cost a resumed process
    # pays — build the step program and run the first step (cold:
    # trace + XLA compile; warm: trace + load the cached executable).
    # Imports and device init are identical either way and excluded.
    t_start = time.perf_counter()
    step = TrainStep(net, loss_fn, optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1,
                                       "momentum": 0.9})
    rng = np.random.RandomState(7)
    losses = []
    first_step_s = None
    for i in range(3):
        x = rng.randn(8, 8).astype("f")
        y = (rng.randn(8, 4) > 0).astype("f")
        losses.append(float(np.asarray(step(x, y))))
        if i == 0:
            first_step_s = time.perf_counter() - t_start
            resharding.observe_restart_to_first_step(first_step_s)
    fam = telemetry.snapshot()["metrics"].get(
        "mxnet_elastic_restart_to_first_step_seconds", {})
    recorded = sum(s.get("count", 0) for s in fam.get("samples", []))
    print(json.dumps({"losses": losses,
                      "restart_to_first_step_s": round(first_step_s, 4),
                      "telemetry_family_count": recorded}))


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------
def _run_child(*args, timeout=600, **env):
    r = subprocess.run([sys.executable, os.path.abspath(__file__),
                        *args], env={**os.environ, **env},
                       capture_output=True, text=True, timeout=timeout)
    if r.returncode != 0:
        sys.exit(f"elastic_smoke child {args} failed "
                 f"(rc={r.returncode}):\n{r.stdout}\n{r.stderr}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def main():
    # 1) live reshard in a real child — twice, for digest determinism
    a = _run_child("--child-reshard")
    b = _run_child("--child-reshard")
    assert a["digest"] == b["digest"], (a["digest"], b["digest"])
    assert len(a["digest"]) == 64
    print(f"elastic_smoke: live reshard dp4->dp2 bit-identical "
          f"(reshard {a['reshard_s']}s, plan digest "
          f"{a['digest'][:12]}... identical across 2 processes)")

    # 2) warm restart: nothing new to compile + faster
    #    restart-to-first-step
    import tempfile

    cache_dir = tempfile.mkdtemp(prefix="elastic_smoke_cache_")
    # the toy's programs compile in under the 1 s the cache skips by
    # default
    cached = {"JAX_COMPILATION_CACHE_DIR": cache_dir,
              "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    cold = _run_child("--child-train", **cached)
    stored = sorted(os.listdir(cache_dir))
    assert stored, "the cold child stored no executable"
    warm = _run_child("--child-train", **cached)
    assert sorted(os.listdir(cache_dir)) == stored, \
        "the warm child compiled something new"
    assert warm["losses"] == cold["losses"], (cold, warm)
    assert warm["telemetry_family_count"] >= 1, warm
    # the whole point: the warm path must beat the cold restore+compile
    assert warm["restart_to_first_step_s"] < \
        cold["restart_to_first_step_s"], (cold, warm)
    speedup = cold["restart_to_first_step_s"] / \
        warm["restart_to_first_step_s"]
    print(f"elastic_smoke OK: warm restart compiled nothing new "
          f"({len(stored)} cached executables), bit-identical losses, "
          f"restart-to-first-step {cold['restart_to_first_step_s']}s "
          f"cold -> {warm['restart_to_first_step_s']}s warm "
          f"({speedup:.2f}x)")


if __name__ == "__main__":
    if "--child-reshard" in sys.argv:
        child_reshard()
    elif "--child-train" in sys.argv:
        child_train()
    else:
        main()
