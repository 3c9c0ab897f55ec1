"""Headline benchmarks on the real chip: ResNet-50 / BERT-base / Llama-proxy
fused bf16 training steps.

Prints ONE JSON line:
  {"metric", "value", "unit", "vs_baseline", "mfu", "extra": {...}}

- metric/value: ResNet-50 train throughput (img/s/chip), bf16 mixed precision
  (BASELINE config #1).  vs_baseline divides by the reference's recalled V100
  fp32 number (350 img/s mid-range; BASELINE.md marks it unverified) — the
  honest figure is "mfu": achieved training FLOP/s over the chip's bf16 peak.
- extra: BERT-base pretrain samples/s + Llama-proxy tokens/s (BASELINE
  configs #2/#5), each with its own MFU, through the flash-attention kernel.
"""
from __future__ import annotations

import json
import time

import numpy as np

BASELINE_IMG_S_PER_CHIP = 350.0  # recalled V100 fp32, BASELINE.md config #1

# ResNet-50 @224: ~3.9 GFLOPs forward per image, x3 for fwd+bwd
RESNET50_TRAIN_FLOPS_PER_IMG = 11.7e9

# bf16 peak FLOP/s per chip by device_kind substring
_PEAKS = (("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
          ("v6", 918e12), ("v4", 275e12), ("v3", 123e12), ("v2", 45e12))


def chip_peak_flops():
    """bf16 peak of the chip, or None off the chip (the arms' CPU tests).
    A TPU whose ``device_kind`` is not in the table is an error."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    kind = dev.device_kind.lower()
    for sub, peak in _PEAKS:
        if sub in kind:
            return peak
    raise RuntimeError(f"no bf16 peak on record for {dev.device_kind!r}")


def _time_steps(step_fn, args, warmup, iters):
    import jax

    # stage inputs on-device once: measured steps must not pay host->device
    # transfer (the training loop overlaps it via the prefetching input
    # pipeline)
    args = tuple(jax.device_put(a) for a in args)
    for _ in range(warmup):
        np.asarray(step_fn(*args))
    t0 = time.perf_counter()
    loss = None
    for _ in range(iters):
        loss = step_fn(*args)
    lv = float(np.asarray(loss))
    dt = time.perf_counter() - t0
    assert np.isfinite(lv), "non-finite bench loss"
    return dt


def _matmul_params(step):
    """Approximate '6N' N: matmul-participating parameter count (embedding
    lookups excluded — they are gathers, not MXU FLOPs)."""
    return sum(int(np.prod(v.shape)) for k, v in step.params.items()
               if "embedding" not in k and len(v.shape) >= 2)


def bench_resnet50(on_tpu):
    # NHWC: XLA:TPU tiles channel-last convs onto the MXU without the
    # internal relayout transposes logical-NCHW convs pay (override with
    # MXNET_BENCH_LAYOUT=NCHW to A/B the layouts on the chip).  The
    # headline must survive any config failing, so fall back per config.
    #
    # Escalation sweep (PERF_NOTES: if plain NHWC lands under MFU 0.35):
    # on TPU the bench ALSO measures batch-512+remat and the
    # space-to-depth stem unattended, reports each in extras, and
    # headlines the best.  MXNET_BENCH_SWEEP=0 pins the single default
    # config.
    import os
    import sys

    layout = os.environ.get("MXNET_BENCH_LAYOUT", "NHWC")
    sweep = os.environ.get("MXNET_BENCH_SWEEP", "1") != "0"
    # MXNET_BENCH_FORCE_SWEEP=1: exercise the TPU-gated sweep branches on
    # CPU (VERDICT Weak #1: first chip contact must not be the first time
    # this code runs).  CPU keeps the default batch — the point is the
    # code path, not the number.
    force = os.environ.get("MXNET_BENCH_FORCE_SWEEP", "0") == "1"
    configs = [("base", layout, None, False, "conv7")]
    if (on_tpu or force) and sweep and layout == "NHWC":
        sweep_batch = 512 if on_tpu else None
        configs += [("b512_remat", layout, sweep_batch, True, "conv7"),
                    ("b512_remat_s2d", layout, sweep_batch, True, "s2d")]
    results, errors = {}, {}
    last_exc = None
    for name, lay, batch, remat, stem in configs:
        try:
            results[name] = _bench_resnet50_layout(
                on_tpu, lay, batch=batch, remat=remat, stem=stem)
        except Exception as e:
            print(f"bench: resnet config {name} failed ({e!r})",
                  file=sys.stderr)
            errors[name] = repr(e)[:200]
            last_exc = e
    if not results and layout != "NCHW":
        # every NHWC config failed: one last try on the old layout
        print("bench: all NHWC configs failed; falling back to NCHW",
              file=sys.stderr)
        results["base_nchw"] = _bench_resnet50_layout(on_tpu, "NCHW")
    if not results:
        raise last_exc  # surfaced as the parseable error JSON in main()
    best = max(results, key=lambda k: results[k][0])
    extras = {k: {"value": round(v[0], 2), "mfu": round(v[1], 4)}
              for k, v in results.items()}
    # failed configs stay visible, distinguishable from never-swept ones
    for k, err in errors.items():
        extras[k] = {"error": err}
    return results[best] + ({"configs": extras, "best": best},)


def _bench_resnet50_layout(on_tpu, layout, batch=None, remat=False,
                           stem="conv7"):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel.data_parallel import TrainStep

    batch = batch or (256 if on_tpu else 16)
    size = 224 if on_tpu else 64
    net = vision.resnet50_v1(layout=layout, stem=stem)
    net.initialize(ctx=mx.current_context())
    dshape = (1, size, size, 3) if layout == "NHWC" else (1, 3, size, size)
    net(mx.nd.zeros(dshape))  # settle deferred param shapes

    def loss_fn(logits, labels):
        import jax
        import jax.numpy as jnp

        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1)

    step = TrainStep(net, loss_fn, optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                     train_mode=True, dtype="bfloat16", remat=remat)

    xshape = (batch, size, size, 3) if layout == "NHWC" else \
        (batch, 3, size, size)
    x = np.random.uniform(-1, 1, xshape).astype("float32")
    y = np.random.randint(0, 1000, (batch,)).astype("int32")
    iters = 20 if on_tpu else 3
    dt = _time_steps(step, (x, y), warmup=2, iters=iters)
    img_s = batch * iters / dt
    peak = chip_peak_flops()
    mfu = (img_s * RESNET50_TRAIN_FLOPS_PER_IMG / peak) if peak else 0.0
    return img_s, mfu


def bench_bert(on_tpu):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.language import bert
    from mxnet_tpu.parallel.data_parallel import TrainStep

    batch, seq = (64, 128) if on_tpu else (2, 32)
    net = bert.BertForPretraining(
        bert.BertConfig() if on_tpu else
        bert.BertConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=2, intermediate_size=256, max_position=64))
    net.initialize(ctx=mx.current_context())
    ids0 = mx.nd.zeros((1, seq), dtype="int32")
    net(ids0)

    def loss_fn(outs, labels):
        import jax
        import jax.numpy as jnp

        mlm, nsp = outs
        mlm_labels, nsp_labels = labels[:, :-1], labels[:, -1]
        logp = jax.nn.log_softmax(mlm, axis=-1)
        mlm_l = -jnp.take_along_axis(logp, mlm_labels[..., None], axis=-1)
        nsp_logp = jax.nn.log_softmax(nsp, axis=-1)
        nsp_l = -jnp.take_along_axis(nsp_logp, nsp_labels[:, None], axis=-1)
        return jnp.mean(mlm_l) + jnp.mean(nsp_l)

    step = TrainStep(net, loss_fn, optimizer="adam",
                     optimizer_params={"learning_rate": 1e-4},
                     train_mode=True, dtype="bfloat16")
    vocab = net._cfg.vocab_size
    ids = np.random.randint(0, vocab, (batch, seq)).astype("int32")
    labels = np.concatenate(
        [np.random.randint(0, vocab, (batch, seq)),
         np.random.randint(0, 2, (batch, 1))], axis=1).astype("int32")
    iters = 20 if on_tpu else 2
    dt = _time_steps(step, (ids, labels), warmup=2, iters=iters)
    samples_s = batch * iters / dt
    peak = chip_peak_flops()
    flops_per_sample = 6.0 * _matmul_params(step) * seq
    mfu = (samples_s * flops_per_sample / peak) if peak else 0.0
    return samples_s, mfu


def bench_llama(on_tpu):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.language import llama
    from mxnet_tpu.parallel.data_parallel import TrainStep

    if on_tpu:
        # ~250M-param proxy of the Llama-3 architecture sized for one chip
        cfg = dict(vocab_size=32000, hidden_size=1024, num_layers=16,
                   num_heads=16, num_kv_heads=8, intermediate_size=2816,
                   max_seq_len=1024)
        batch, seq = 8, 1024
    else:
        cfg = dict(vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
                   num_kv_heads=2, intermediate_size=256, max_seq_len=256)
        batch, seq = 2, 64
    net = llama.LlamaForCausalLM(llama.LlamaConfig(**cfg))
    net.initialize(ctx=mx.current_context())
    net(mx.nd.zeros((1, seq), dtype="int32"))

    def loss_fn(logits, labels):
        import jax
        import jax.numpy as jnp

        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], axis=-1)

    step = TrainStep(net, loss_fn, optimizer="adam",
                     optimizer_params={"learning_rate": 3e-4},
                     train_mode=True, dtype="bfloat16")
    ids = np.random.randint(0, cfg["vocab_size"], (batch, seq)).astype("int32")
    labels = np.random.randint(0, cfg["vocab_size"],
                               (batch, seq)).astype("int32")
    iters = 10 if on_tpu else 2
    dt = _time_steps(step, (ids, labels), warmup=2, iters=iters)
    tokens_s = batch * seq * iters / dt
    peak = chip_peak_flops()
    flops_per_token = 6.0 * _matmul_params(step)
    mfu = (tokens_s * flops_per_token / peak) if peak else 0.0
    return tokens_s, mfu


def bench_eager_op_overhead(iters=300, warmup=30):
    """µs/op over a small-op eager loop, jit-cache on vs off (ISSUE 1
    tentpole: the dispatch fast path must show up as a per-op dispatch win,
    not just a cache-counter win).

    The loop is the pathological imperative workload VERDICT r5 flags
    (batch-1 eager CNN inference): many tiny
    registry-op calls — BatchNorm(inference) / activation / add / softmax —
    where per-call dispatch and per-primitive eager launch, not kernel
    time, dominate.  Returns a dict with us_per_op for both modes, the
    speedup, and the cache stats after the jit-on run.
    """
    import mxnet_tpu as mx
    import numpy as np

    C = 32
    R = np.random.RandomState(0)
    x = mx.nd.array(R.randn(1, C, 8, 8).astype("f"))
    y = mx.nd.array(R.randn(1, C, 8, 8).astype("f"))
    gamma = mx.nd.array(np.ones(C, "f"))
    beta = mx.nd.array(np.zeros(C, "f"))
    rmean = mx.nd.array(np.zeros(C, "f"))
    rvar = mx.nd.array(np.ones(C, "f"))

    def loop(n):
        out = x
        for _ in range(n):
            h = mx.nd.BatchNorm(out, gamma, beta, rmean, rvar,
                                training=False)[0]
            h = h + y
            h = mx.nd.Activation(h, act_type="softsign")
            out = h.softmax(axis=1)
        out.asnumpy()  # sync: async dispatch must not flatter the number
        return 4 * n   # registry-op invokes per iteration

    def measure(jit_on):
        prev = mx.nd.set_eager_jit(jit_on)
        try:
            loop(warmup)  # warm cache / warm eager dispatch
            t0 = time.perf_counter()
            nops = loop(iters)
            dt = time.perf_counter() - t0
        finally:
            mx.nd.set_eager_jit(prev)
        return dt / nops * 1e6

    mx.nd.reset_dispatch_stats()
    us_jit = measure(True)
    stats = mx.nd.dispatch_stats()
    us_eager = measure(False)
    return {
        "us_per_op_jit": round(us_jit, 2),
        "us_per_op_eager": round(us_eager, 2),
        "speedup": round(us_eager / us_jit, 2) if us_jit else 0.0,
        "cache": {k: stats[k] for k in ("hits", "misses", "evictions",
                                        "bypasses", "size")},
    }


def bench_overlap():
    """Overlap-engine A/B (ISSUE 4).

    - ``input_bound``: a synthetic loader whose per-batch host latency is
      calibrated to one compute step (the input-bound regime prefetch
      exists for), driven with the device prefetcher on vs off.  Each arm
      syncs the loss per step — the realistic logging-loop pattern where
      jax async dispatch alone cannot hide the input wait.  Ideal speedup
      is 2x; the acceptance bar is >= 1.5x.
    - ``allreduce_fused``: per-key vs bucket-fused kvstore round trips at
      1 KiB..64 MiB message sizes.  On one device this measures the
      per-call dispatch+copy overhead fusion amortizes (the collective
      itself is the identity); on a pod the same code path adds the
      per-collective latency win.  Each size also gets a ZeRO-1 arm
      (ISSUE 7): the fused flat buffer through reduce-scatter +
      all-gather in one jitted shard_map — the exact collective pair
      ``MXNET_ZERO=1`` issues per bucket (``rs_ag_ms``/``rs_ag_gb_s``).
    - ``zero_optimizer``: per-rank optimizer-state bytes, ZeRO vs
      replicated, from a real 2-step ``MXNET_ZERO=1`` Trainer loop —
      the ~1/dp memory win, read from the telemetry gauge.
    """
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.data.prefetcher import PrefetchIterator
    from mxnet_tpu.parallel import bucketing
    from mxnet_tpu.parallel.data_parallel import TrainStep

    out = {}
    # -- input-bound A/B ---------------------------------------------------
    np.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(256, activation="relu"),
            nn.Dense(256, activation="relu"), nn.Dense(10))
    net.initialize()
    net(mx.nd.zeros((2, 128)))

    def loss_fn(o, y):
        import jax.numpy as jnp

        return jnp.mean((o - y) ** 2)

    step = TrainStep(net, loss_fn, optimizer="sgd")
    x = np.random.randn(64, 128).astype("f")
    y = np.random.randn(64, 10).astype("f")
    for _ in range(3):
        np.asarray(step(x, y))  # warm the jit
    t0 = time.perf_counter()
    for _ in range(5):
        np.asarray(step(x, y))
    compute_s = (time.perf_counter() - t0) / 5
    delay_s = max(compute_s, 1e-3)
    n_steps = 30

    def batches():
        for _ in range(n_steps):
            time.sleep(delay_s)  # synthetic decode/augment latency
            yield (x, y)

    def run_epoch(depth):
        it = PrefetchIterator(batches(), depth=depth,
                              sharding=step._batch_shard)
        t0 = time.perf_counter()
        try:
            for bx, by in it:
                float(np.asarray(step(bx, by)))  # per-step sync
        finally:
            it.close()  # a mid-epoch failure must not leak the producer
        return n_steps / (time.perf_counter() - t0)

    without = run_epoch(0)
    with_pf = run_epoch(2)
    out["input_bound"] = {
        "steps_s_prefetch": round(with_pf, 2),
        "steps_s_serial": round(without, 2),
        "speedup": round(with_pf / without, 2) if without else 0.0,
        "loader_delay_ms": round(delay_s * 1e3, 3),
        "compute_ms": round(compute_s * 1e3, 3),
    }

    # -- fused vs per-key allreduce curve ----------------------------------
    # drives the REAL collective issue path (allreduce_hosts with the
    # single-process identity short-circuit off): per-key = K collectives,
    # fused = pack + 1 collective + unpack — the exact code the dist store
    # runs per push.  On one chip this isolates per-collective issue cost;
    # on a pod the same curve adds the network latency win.
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.parallel import collectives as coll
    from mxnet_tpu.parallel.collectives import allreduce_hosts

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    dp = len(jax.devices())

    # the MXNET_ZERO per-bucket pair: reduce-scatter to 1/dp shards,
    # all-gather the (here: identity) updated shard back — one jit,
    # same program shape as ZeroBucketEngine._make_step; jit
    # re-specializes per padded flat size
    def _rs_ag_body(x):
        s = coll.reduce_scatter(x, axis_name="dp")
        return coll.all_gather(s, axis_name="dp", axis=0, tiled=True)

    rs_ag_pair = jax.jit(coll.shard_map(_rs_ag_body, mesh, in_specs=(P(),),
                                        out_specs=P()))

    curve = {}
    for label, elems, k in (("1KiB", 256, 16), ("32KiB", 8192, 16),
                            ("1MiB", 1 << 18, 16), ("8MiB", 1 << 21, 4),
                            ("64MiB", 1 << 24, 2)):
        vals = [jnp.asarray(np.random.randn(elems).astype("f"))
                for _ in range(k)]
        plan = bucketing.assign_buckets(
            [(i, (elems,), "float32") for i in range(k)],
            cap_bytes=64 << 20)
        iters = 3

        def per_key():
            outs = [allreduce_hosts(v, _testing_force=True) for v in vals]
            jax.block_until_ready(outs)  # ALL results: async dispatch
            # must not let late collectives escape the timed region

        def fused():
            outs = []
            for b in plan.buckets:
                flat = bucketing.pack([vals[i] for i in b.keys])
                outs.extend(bucketing.unpack(
                    b, allreduce_hosts(flat, _testing_force=True)))
            jax.block_until_ready(outs)

        def rs_ag():
            outs = []
            for b in plan.buckets:
                flat = bucketing.pack([vals[i] for i in b.keys])
                _, _, pad = bucketing.shard_layout(b.size, dp)
                if pad:
                    flat = jnp.pad(flat, (0, pad))
                outs.extend(bucketing.unpack(b, rs_ag_pair(flat)))
            jax.block_until_ready(outs)

        per_key()
        fused()  # warm every jit path
        rs_ag()
        t0 = time.perf_counter()
        for _ in range(iters):
            per_key()
        t_key = (time.perf_counter() - t0) / iters
        t0 = time.perf_counter()
        for _ in range(iters):
            fused()
        t_fused = (time.perf_counter() - t0) / iters
        t0 = time.perf_counter()
        for _ in range(iters):
            rs_ag()
        t_zero = (time.perf_counter() - t0) / iters
        total_mb = k * elems * 4 / (1 << 20)
        curve[label] = {
            "tensors": k,
            "buckets": len(plan.buckets),
            "per_key_ms": round(t_key * 1e3, 3),
            "fused_ms": round(t_fused * 1e3, 3),
            "rs_ag_ms": round(t_zero * 1e3, 3),
            "speedup": round(t_key / t_fused, 2) if t_fused else 0.0,
            "per_key_gb_s": round(total_mb / 1024 / t_key, 2),
            "fused_gb_s": round(total_mb / 1024 / t_fused, 2),
            "rs_ag_gb_s": round(total_mb / 1024 / t_zero, 2),
        }
    out["allreduce_fused"] = curve
    out["zero_optimizer"] = _bench_zero_optimizer_bytes(dp)
    return out


def _bench_zero_optimizer_bytes(dp):
    """Per-rank optimizer-state bytes, sharded vs replicated (the
    MXNET_ZERO ~1/dp HBM win), measured from a real 2-step Trainer loop
    through the telemetry gauge."""
    import os

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd, telemetry

    prev = os.environ.get("MXNET_ZERO")
    os.environ["MXNET_ZERO"] = "1"
    try:
        np.random.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(256, activation="relu"), gluon.nn.Dense(64))
        net.initialize()
        net(nd.zeros((2, 128)))
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9},
                           kvstore="device")
        x = np.random.randn(8, 128).astype("f")
        y = np.random.randn(8, 64).astype("f")
        for _ in range(2):
            with autograd.record():
                loss = ((net(nd.array(x)) - nd.array(y)) ** 2).mean()
            loss.backward()
            tr.step(8)
        sharded = telemetry.gauge("mxnet_zero_optimizer_bytes_per_rank").value
        # replicated momentum = one fp32 buffer per parameter element
        replicated = sum(
            int(np.prod(p.shape)) * 4
            for p in net.collect_params().values())
        return {
            "dp": dp,
            "bytes_per_rank": int(sharded),
            "replicated_bytes": int(replicated),
            "ratio": round(sharded / replicated, 4) if replicated else 0.0,
        }
    finally:
        if prev is None:
            os.environ.pop("MXNET_ZERO", None)
        else:
            os.environ["MXNET_ZERO"] = prev


def bench_planner():
    """Sharding planner (ISSUE 10): plan-time overhead (one-time, host
    only), the zero-per-step-cost contract (compile-tracer-asserted:
    after the warmup step every further planner-driven step performs
    ZERO fresh traces and zero plan work), and estimated-vs-actual HBM
    bytes for the llama proxy under 2 mesh shapes."""
    import time

    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.gluon.model_zoo.language import llama
    from mxnet_tpu.parallel import planner
    from mxnet_tpu.parallel.data_parallel import TrainStep
    from mxnet_tpu.parallel.functional import functionalize

    cfg = dict(vocab_size=512, hidden_size=128, num_layers=2,
               num_heads=4, num_kv_heads=2, intermediate_size=256,
               max_seq_len=256)
    # the global batch shards over the data axes: keep it divisible by
    # the device count on any mesh this arm builds
    n_dev = len(jax.devices())
    batch, seq = max(2, n_dev), 64

    def make_net():
        net = llama.LlamaForCausalLM(llama.LlamaConfig(**cfg))
        net.initialize(ctx=mx.current_context())
        net(mx.nd.zeros((1, seq), dtype="int32"))
        return net

    def loss_fn(logits, labels):
        import jax.numpy as jnp

        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], axis=-1)

    def actual_resident_bytes(step):
        """Measured per-device bytes of params + optimizer state (the
        plan-governed resident footprint; grads/activations are
        transient inside the donated jit)."""
        total = 0
        leaves = list(step.train_params.values()) \
            + list(step.rest_params.values()) \
            + jax.tree_util.tree_leaves(step.opt_state)
        for leaf in leaves:
            shard = leaf.sharding.shard_shape(leaf.shape)
            total += int(np.prod(shard) or 1) * leaf.dtype.itemsize
        return total

    meshes = {"dp": {"dp": n_dev}}
    if n_dev % 2 == 0 and n_dev > 1:
        # dp*fsdp = n_dev here, so `batch` stays divisible; an odd
        # device count has no even dp×fsdp split — skip the arm, keep
        # the dp numbers
        meshes["dp_fsdp"] = {"dp": n_dev // 2, "fsdp": 2}
    out = {"device_count": n_dev}
    ids = np.random.randint(0, cfg["vocab_size"],
                            (batch, seq)).astype("int32")
    labels = np.random.randint(0, cfg["vocab_size"],
                               (batch, seq)).astype("int32")
    for name, axes in meshes.items():
        # one net per arm, planned from ITS OWN signature — plan specs
        # key on param names, and gluon auto-name prefixes differ
        # between net instances
        net = make_net()
        sig = planner.signature_of(functionalize(net)[1])
        t0 = time.perf_counter()
        plan = planner.plan_sharding(
            planner.PlannerConfig(mesh=axes, rules="fsdp",
                                  optimizer="sgd_momentum",
                                  batch_rows=batch), sig, n_dev)
        plan_ms = (time.perf_counter() - t0) * 1e3
        step = TrainStep(net, loss_fn, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.01,
                                           "momentum": 0.9}, plan=plan)
        step(ids, labels)            # warmup: the one compile
        before = telemetry.snapshot()["compile"]["count"]
        iters = 4
        t0 = time.perf_counter()
        for _ in range(iters):
            step(ids, labels)
        last = step(ids, labels)
        np.asarray(last)             # drain async dispatch
        dt = time.perf_counter() - t0
        fresh = telemetry.snapshot()["compile"]["count"] - before
        est = plan.hbm
        actual = actual_resident_bytes(step)
        est_resident = est["params"] + est["optimizer"]
        out[name] = {
            "plan_ms": round(plan_ms, 2),
            "steady_steps_per_s": round((iters + 1) / dt, 2),
            "fresh_traces_after_warmup": int(fresh),
            "estimated_resident_bytes": int(est_resident),
            "actual_resident_bytes": int(actual),
            "estimate_ratio": round(actual / max(1, est_resident), 3),
            "estimated_total_bytes": int(est["total"]),
        }
        assert fresh == 0, \
            f"planner arm {name}: {fresh} fresh traces after warmup " \
            "(the zero-per-step-cost contract is compile-tracer-asserted)"
    return out


def bench_elastic():
    """Zero-downtime elasticity (ISSUE 13): live ZeRO resharding vs the
    checkpoint-restore round trip, and serving replica handoff
    join-to-first-token — the ROADMAP's target metrics, measured rather
    than asserted."""
    import os
    import tempfile
    import time

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, telemetry
    from mxnet_tpu import autograd
    from mxnet_tpu.parallel import planner
    from mxnet_tpu.parallel.functional import functionalize

    out = {}
    tmp = tempfile.mkdtemp(prefix="bench_elastic_")

    def make_net(seed=0):
        np.random.seed(seed)
        mx.random.seed(seed)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(64, activation="relu", in_units=8),
                gluon.nn.Dense(64, activation="relu", in_units=64),
                gluon.nn.Dense(64, activation="relu", in_units=64),
                gluon.nn.Dense(4, in_units=64))
        net.initialize()
        return net

    # -- live ZeRO reshard vs checkpoint-restore round trip ------------
    def plan_for(net, dp):
        _, params = functionalize(net)
        pcfg = planner.PlannerConfig(mesh={"dp": dp},
                                     rules="replicated",
                                     optimizer="sgd_momentum",
                                     zero=True)
        return planner.plan_sharding(pcfg,
                                     planner.signature_of(params), dp)

    os.environ["MXNET_ZERO"] = "1"
    try:
        net = make_net()
        net(nd.zeros((2, 8)))
        planner.set_default_plan(plan_for(net, 8))
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9},
                           kvstore="device")
        rng = np.random.RandomState(3)
        for _ in range(3):
            x = nd.array(rng.randn(8, 8).astype("f"))
            y = nd.array((rng.randn(8, 4) > 0).astype("f"))
            with autograd.record():
                loss = ((net(x) - y) ** 2).mean()
            loss.backward()
            tr.step(8)
        def moved_bytes():
            snap = telemetry.snapshot()["metrics"]
            return {s["labels"].get("kind", "?"): int(s["value"])
                    for s in snap.get("mxnet_reshard_bytes_total",
                                      {}).get("samples", [])}

        # live reshard FIRST, while the sharded state is resident (a
        # load_states would harvest it to host pieces and give the
        # transfer nothing to move)
        plan2 = plan_for(net, 2)
        base = moved_bytes()
        t0 = time.perf_counter()
        tr._zero.reshard(plan2)
        reshard_s = time.perf_counter() - t0
        moved = {k: v - base.get(k, 0) for k, v in
                 moved_bytes().items() if v - base.get(k, 0)}
        fname = os.path.join(tmp, "trainer.states")
        t0 = time.perf_counter()
        tr.save_states(fname)
        tr.load_states(fname)
        ckpt_s = time.perf_counter() - t0
        out["zero_reshard_dp8_to_dp2"] = {
            "live_reshard_s": round(reshard_s, 4),
            "checkpoint_roundtrip_s": round(ckpt_s, 4),
            "resharded_bytes": moved,
            # at this toy scale the "disk" is tmpfs and the payload is
            # KB, so the checkpoint arm is unrealistically cheap; the
            # live path's win is O(state/dp) device moves vs O(state)
            # host round trips at real scale — the real-pod numbers are
            # the ROADMAP's outstanding TPU round
            "note": "toy-scale: tmpfs checkpoint, KB payload"}
    finally:
        os.environ.pop("MXNET_ZERO", None)
        planner.set_default_plan(None)

    # -- serving replica handoff: join-to-first-token ------------------
    from mxnet_tpu.gluon.model_zoo.language import llama
    from mxnet_tpu.serving.engine import ServingEngine

    lcfg = llama.LlamaConfig(vocab_size=64, hidden_size=32,
                             num_layers=2, num_heads=4, num_kv_heads=2,
                             intermediate_size=48, max_seq_len=64)
    lnet = llama.LlamaForCausalLM(lcfg)
    lnet.initialize(ctx=mx.current_context())
    lnet(mx.nd.zeros((1, 8), dtype="int32"))
    kw = dict(batch_buckets=[1], prefill_buckets=[8], kv_pages=16,
              page_size=4, max_batch=1)

    def ttft(engine):
        t0 = time.monotonic()
        engine.start()
        engine.submit([1, 2, 3, 4], max_new_tokens=2).result(120)
        return time.monotonic() - t0

    cold_eng = ServingEngine(lnet, **kw)
    cold_ttft = ttft(cold_eng)             # AOT-compiles
    joiner = ServingEngine.join_replica(lnet, cold_eng, **kw)
    join_ttft = ttft(joiner)               # donated params
    joiner.close()
    cold_eng.close()
    out["serving_replica_handoff"] = {
        "cold_start_to_first_token_s": round(cold_ttft, 4),
        "join_to_first_token_s": round(join_ttft, 4),
        "speedup": round(cold_ttft / max(join_ttft, 1e-9), 2)}
    return out


def bench_serving():
    """Serving-engine load generator (ISSUE 8).

    Two arms against the AOT-compiled continuous-batching engine on the
    tiny llama proxy:

    - **closed loop**: N concurrent clients, each submitting its next
      request the moment the previous completes — measures the
      latency/throughput trade as the decode batch fills.
    - **open loop**: requests arrive on a fixed schedule (at ~60% of the
      closed-loop peak rate) regardless of completions — measures
      latency under sustained arrival pressure, queueing included.

    Reports p50/p99 latency and tokens/s(/chip) per concurrency level,
    plus the engine diagnosis context: warmup cost, compiled-signature
    count, batch occupancy, and the steady-state fresh-trace count
    (which must be 0 — the ISSUE 8 contract)."""
    import threading

    import jax
    import numpy as np

    from mxnet_tpu import nd, serving, telemetry
    from mxnet_tpu.gluon.model_zoo.language.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    net(nd.zeros((1, 8), dtype="int32"))
    eng = serving.ServingEngine(net, batch_buckets=[1, 2, 4],
                                prefill_buckets=[8, 16], kv_pages=64,
                                page_size=8, max_batch=4)
    t0 = time.perf_counter()
    eng.start()
    warmup_s = time.perf_counter() - t0
    # touch every bucket once so steady state is honestly steady
    warm = [eng.submit(np.random.RandomState(k).randint(
        1, 512, (n,)).astype("int32"), max_new_tokens=2)
        for k, n in enumerate((3, 8, 11, 16))]
    for q in warm:
        q.result(timeout=300)
    compile_before = telemetry.snapshot()["compile"]["count"]
    n_chips = max(1, jax.local_device_count())
    max_new = 8

    def percentile(lat, p):
        return lat[min(len(lat) - 1, int(p * len(lat)))]

    def run_closed(conc, total=16):
        lat, lock = [], threading.Lock()
        per_client = total // conc

        def client(k):
            rr = np.random.RandomState(1000 + k)
            for _ in range(per_client):
                prompt = rr.randint(1, 512,
                                    (int(rr.randint(1, 17)),)).astype("int32")
                t1 = time.perf_counter()
                eng.submit(prompt, max_new_tokens=max_new).result(
                    timeout=600)
                with lock:
                    lat.append(time.perf_counter() - t1)

        t1 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(conc)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t1
        lat.sort()
        toks = len(lat) * max_new
        return {
            "requests": len(lat),
            "p50_ms": round(percentile(lat, 0.50) * 1e3, 1),
            "p99_ms": round(percentile(lat, 0.99) * 1e3, 1),
            "requests_per_s": round(len(lat) / wall, 2),
            "tokens_per_s": round(toks / wall, 1),
            "tokens_per_s_chip": round(toks / wall / n_chips, 1),
        }

    closed = {str(c): run_closed(c) for c in (1, 2, 4)}

    def run_open(rate_rps, total=24):
        pending = []
        start = time.perf_counter()
        rr = np.random.RandomState(7)
        for i in range(total):
            target = start + i / rate_rps
            now = time.perf_counter()
            if target > now:
                time.sleep(target - now)
            prompt = rr.randint(1, 512,
                                (int(rr.randint(1, 17)),)).astype("int32")
            pending.append(eng.submit(prompt, max_new_tokens=max_new))
        lat = []
        for req in pending:
            # the request records its own submit->done latency, so late
            # collection here cannot inflate early completions
            lat.append(req.result(timeout=600)["latency_s"])
        wall = time.perf_counter() - start
        lat.sort()
        return {
            "arrival_rps": round(rate_rps, 2),
            "requests": total,
            "p50_ms": round(percentile(lat, 0.50) * 1e3, 1),
            "p99_ms": round(percentile(lat, 0.99) * 1e3, 1),
            "tokens_per_s": round(total * max_new / wall, 1),
            "tokens_per_s_chip": round(total * max_new / wall / n_chips,
                                       1),
        }

    open_loop = run_open(max(0.5, 0.6 * closed["4"]["requests_per_s"]))
    snap = telemetry.snapshot()
    occ = snap["metrics"].get("mxnet_serving_batch_occupancy", {})
    occ_samples = occ.get("samples", [])
    occupancy = None
    if occ_samples and occ_samples[0].get("count"):
        occupancy = round(occ_samples[0]["sum"] / occ_samples[0]["count"],
                          3)
    fresh = snap["compile"]["count"] - compile_before
    stats = eng.stats()
    eng.close()
    return {
        "model": "llama_tiny",
        "warmup_s": round(warmup_s, 2),
        "compiled_signatures": stats["compiled_signatures"],
        "fresh_traces_steady_state": int(fresh),
        "batch_occupancy_mean": occupancy,
        "kv_pool_bytes": stats["kv_pages"]["pool_bytes"],
        "closed_loop": closed,
        "open_loop": open_loop,
    }


def bench_fleet():
    """Serving fleet router (ISSUE 17): throughput scaling and
    kill-recovery cost.

    - **scaling**: closed-loop load through the fleet router at 1 and 3
      in-process replicas — p50/p99 latency and tokens/s.  Router
      overhead shows up as the 1-replica delta vs ``extra.serving``;
      scaling efficiency as the 3-vs-1 tokens/s ratio (sub-linear on a
      shared CPU, near-linear across real chips).
    - **kill recovery**: SIGKILL-equivalent on one of 3 replicas under
      load — time from kill to a ``join_replica`` replacement back in
      rotation, with the replacement's ready time reported next to the
      cold first spawn for comparison (process-mode warm-vs-cold is
      asserted by ci/fleet_smoke.py; in-process on one contended CPU
      the compile-cache win can wash out)."""
    import threading

    import numpy as np

    from mxnet_tpu import nd, serving
    from mxnet_tpu.serving import fleet
    from mxnet_tpu.gluon.model_zoo.language.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    net(nd.zeros((1, 8), dtype="int32"))
    kw = dict(batch_buckets=[1, 2], prefill_buckets=[8, 16],
              kv_pages=32, page_size=8, max_batch=2)

    def factory(rid, donor):
        if donor is not None:
            return serving.ServingEngine.join_replica(
                net, donor, **kw).start()
        return serving.ServingEngine(net, **kw).start()

    max_new = 8

    def percentile(lat, p):
        return lat[min(len(lat) - 1, int(p * len(lat)))]

    def mk_fleet(n):
        mgr = fleet.FleetManager(engine_factory=factory, replicas=n,
                                 probe_interval_ms=100)
        router = fleet.Router(retry_budget=1, hedge_ms=5_000,
                              probe_interval_ms=100, manager=mgr)
        mgr.attach_router(router)
        mgr.ensure(n)
        router.start()
        return mgr, router

    def run_closed(router, conc=4, total=24):
        lat, lock = [], threading.Lock()
        per_client = total // conc

        def client(k):
            rr = np.random.RandomState(500 + k)
            for _ in range(per_client):
                prompt = rr.randint(
                    1, 512, (int(rr.randint(2, 13)),)).tolist()
                t1 = time.perf_counter()
                router.submit(prompt, max_new_tokens=max_new,
                              deadline_ms=300_000).response(timeout=600)
                with lock:
                    lat.append(time.perf_counter() - t1)

        t1 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(conc)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t1
        lat.sort()
        return {
            "requests": len(lat),
            "p50_ms": round(percentile(lat, 0.50) * 1e3, 1),
            "p99_ms": round(percentile(lat, 0.99) * 1e3, 1),
            "tokens_per_s": round(len(lat) * max_new / wall, 1),
        }

    out = {}
    for n in (1, 3):
        mgr, router = mk_fleet(n)
        try:
            out[f"replicas_{n}"] = run_closed(router)
        finally:
            router.close()
            mgr.drain_all(timeout=60)

    # -- kill recovery -----------------------------------------------------
    mgr, router = mk_fleet(3)
    try:
        results, errors = {}, []

        def bg_client(k):
            rr = np.random.RandomState(900 + k)
            for _ in range(8):
                prompt = rr.randint(
                    1, 512, (int(rr.randint(2, 13)),)).tolist()
                try:
                    req = router.submit(prompt, max_new_tokens=4,
                                        deadline_ms=300_000)
                    results[req.id] = req.response(timeout=600)
                except Exception as e:
                    errors.append(repr(e)[:120])

        threads = [threading.Thread(target=bg_client, args=(k,))
                   for k in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        victim = router.replicas()[0]
        t_kill = time.perf_counter()
        victim.kill()
        recovered = None
        while time.perf_counter() - t_kill < 600:
            if len(router.replicas()) >= 3 and any(
                    k == "replacement" for _, k, _ in mgr.spawn_times):
                recovered = time.perf_counter() - t_kill
                break
            time.sleep(0.05)
        for t in threads:
            t.join()
        repl_ready = [dt for _, k, dt in mgr.spawn_times
                      if k == "replacement"]
        cold_ready = mgr.spawn_times[0][2] if mgr.spawn_times else None
        out["kill_recovery"] = {
            "requests_lost": 24 - len(results),
            "errors": errors[:3],
            "kill_to_replacement_s": round(recovered, 2)
            if recovered is not None else None,
            "replacement_ready_s": round(repl_ready[0], 2)
            if repl_ready else None,
            "cold_ready_s": round(cold_ready, 2)
            if cold_ready is not None else None,
        }
    finally:
        mgr.auto_heal = False
        router.close()
        mgr.drain_all(timeout=60)
    return out


def bench_observability():
    """Runtime introspection plane (ISSUE 14): prove the instrumentation
    is free where it must be, and right where it measures.

    - **eager A/B**: the eager dispatch path gains ZERO work from the
      introspection plane; µs/op with request tracing + aggregation
      ticking enabled vs everything off must be within noise.
    - **serving A/B**: engine tokens/s with per-request tracing on vs
      ``MXNET_TRACE_REQUESTS=0`` — host-side stamps only, within noise.
    - **online-vs-offline MFU pin** (llama proxy): the online gauge and
      an offline ``steps × flops / (wall × peak × devices)`` computed
      from the SAME cost_analysis FLOPs source must agree tightly (the
      only divergence is window-edge timing).
    """
    import os

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import introspection, nd, serving, telemetry

    out = {}
    # -- eager A/B ---------------------------------------------------------
    # process-level warmup first (jit-cache fill, jax internals), then
    # best-of-2 per arm — the instrumentation adds literally zero code
    # to this path, so any residual delta IS scheduler noise and must
    # not flip the verdict
    bench_eager_op_overhead(iters=60, warmup=20)

    def eager_us(trace_env):
        prev = os.environ.get("MXNET_TRACE_REQUESTS")
        os.environ["MXNET_TRACE_REQUESTS"] = trace_env
        try:
            return min(bench_eager_op_overhead(
                iters=150, warmup=20)["us_per_op_jit"]
                for _ in range(2))
        finally:
            if prev is None:
                os.environ.pop("MXNET_TRACE_REQUESTS", None)
            else:
                os.environ["MXNET_TRACE_REQUESTS"] = prev

    us_on = eager_us("1")
    us_off = eager_us("0")
    ratio = us_on / us_off if us_off else 1.0
    out["eager_overhead"] = {
        "us_per_op_introspection_on": us_on,
        "us_per_op_introspection_off": us_off,
        "ratio": round(ratio, 3),
        "within_noise": bool(0.8 <= ratio <= 1.25),
    }

    # -- serving tokens/s A/B ---------------------------------------------
    from mxnet_tpu.gluon.model_zoo.language.llama import llama_tiny

    def serving_tokens_per_s(trace_on):
        net = llama_tiny()
        net.initialize()
        net(nd.zeros((1, 8), dtype="int32"))
        eng = serving.ServingEngine(
            net, batch_buckets=[1, 2, 4], prefill_buckets=[8, 16],
            kv_pages=64, page_size=8, max_batch=4,
            trace_requests=trace_on)
        eng.start()
        R = np.random.RandomState(0)
        # warm every bucket, then measure a fixed closed-loop burst
        for n in (3, 8, 11, 16):
            eng.submit(R.randint(1, 512, (n,)).astype("int32"),
                       max_new_tokens=2).result(timeout=300)
        t0 = time.perf_counter()
        reqs = [eng.submit(R.randint(1, 512, (8,)).astype("int32"),
                           max_new_tokens=8) for _ in range(12)]
        for r in reqs:
            r.result(timeout=300)
        dt = time.perf_counter() - t0
        eng.close()
        return 12 * 8 / dt

    # first engine of the process pays one-time warmup (jax internals,
    # libtpu init) regardless of the arm — throw it away, then
    # ALTERNATE the arms (slow drift hits both equally) and take the
    # best of three per arm so scheduler noise cannot flip the verdict
    serving_tokens_per_s(False)
    on_runs, off_runs = [], []
    for _ in range(3):
        on_runs.append(serving_tokens_per_s(True))
        off_runs.append(serving_tokens_per_s(False))
    tps_on, tps_off = max(on_runs), max(off_runs)
    sratio = tps_on / tps_off if tps_off else 1.0
    out["serving_overhead"] = {
        "tokens_per_s_trace_on": round(tps_on, 1),
        "tokens_per_s_trace_off": round(tps_off, 1),
        "ratio": round(sratio, 3),
        "within_noise": bool(sratio >= 0.8),
    }

    # -- online-vs-offline MFU pin (same FLOPs source) ---------------------
    import jax

    from mxnet_tpu.gluon.model_zoo.language import llama
    from mxnet_tpu.parallel.data_parallel import TrainStep

    cfg = dict(vocab_size=512, hidden_size=128, num_layers=2,
               num_heads=4, num_kv_heads=2, intermediate_size=256,
               max_seq_len=256)
    net = llama.LlamaForCausalLM(llama.LlamaConfig(**cfg))
    net.initialize(ctx=mx.current_context())
    net(mx.nd.zeros((1, 64), dtype="int32"))

    def loss_fn(logits, labels):
        import jax.numpy as jnp

        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], axis=-1)

    step = TrainStep(net, loss_fn, optimizer="adam",
                     optimizer_params={"learning_rate": 3e-4})
    ids = np.random.RandomState(1).randint(
        0, cfg["vocab_size"], (2, 64)).astype("int32")
    labels = np.random.RandomState(2).randint(
        0, cfg["vocab_size"], (2, 64)).astype("int32")
    peak = introspection.device_peak_flops() or 1e12
    prev_peak = os.environ.get("MXNET_DEVICE_PEAK_FLOPS")
    os.environ["MXNET_DEVICE_PEAK_FLOPS"] = repr(peak)
    try:
        np.asarray(step(ids, labels))        # warmup: trace + compile
        introspection.reset()
        iters = 8
        t0 = time.perf_counter()
        for _ in range(iters):
            np.asarray(step(ids, labels))    # per-step sync loop
        wall = time.perf_counter() - t0
        online = introspection.utilization()
        _, flops_per_step = step._compiled[next(iter(step._compiled))]
        ndev = max(1, jax.device_count())
        offline = (iters * (flops_per_step or 0)
                   / (wall * peak * ndev)) if flops_per_step else None
    finally:
        if prev_peak is None:
            os.environ.pop("MXNET_DEVICE_PEAK_FLOPS", None)
        else:
            os.environ["MXNET_DEVICE_PEAK_FLOPS"] = prev_peak
    mfu_ratio = (online / offline) if (online and offline) else None
    out["mfu_pin"] = {
        "flops_per_step": flops_per_step,
        "online_mfu": round(online, 6) if online else None,
        "offline_mfu": round(offline, 6) if offline else None,
        "ratio": round(mfu_ratio, 3) if mfu_ratio else None,
        # same FLOPs source: only window-edge timing can diverge
        "within_tolerance": bool(mfu_ratio and
                                 0.75 <= mfu_ratio <= 1.35),
    }
    out["goodput"] = telemetry.goodput_summary()

    # -- flight-recorder A/B (ISSUE 15): recorder-on vs
    # MXNET_FLIGHT_RECORDER=0 within noise on eager µs/op AND serving
    # tokens/s.  The recorder stamps only Python-level collective issue
    # points + step boundaries — the eager dispatch path and the
    # serving decode loop gain literally zero code — so any residual
    # delta is scheduler noise (same arm-alternating discipline as the
    # tracing A/B above).
    from mxnet_tpu import flight_recorder

    def _flight_env(flag):
        prev = os.environ.get("MXNET_FLIGHT_RECORDER")
        os.environ["MXNET_FLIGHT_RECORDER"] = flag
        flight_recorder.reset()     # re-resolve the cached gate
        return prev

    def _flight_restore(prev):
        if prev is None:
            os.environ.pop("MXNET_FLIGHT_RECORDER", None)
        else:
            os.environ["MXNET_FLIGHT_RECORDER"] = prev
        flight_recorder.reset()

    def flight_eager(flag):
        prev = _flight_env(flag)
        try:
            return min(bench_eager_op_overhead(
                iters=150, warmup=20)["us_per_op_jit"]
                for _ in range(2))
        finally:
            _flight_restore(prev)

    def flight_serving(flag):
        prev = _flight_env(flag)
        try:
            return serving_tokens_per_s(False)
        finally:
            _flight_restore(prev)

    fe_on, fe_off = flight_eager("1"), flight_eager("0")
    fs_on, fs_off = [], []
    for _ in range(2):
        fs_on.append(flight_serving("1"))
        fs_off.append(flight_serving("0"))
    fe_ratio = fe_on / fe_off if fe_off else 1.0
    fs_ratio = max(fs_on) / max(fs_off) if max(fs_off) else 1.0
    out["flight_overhead"] = {
        "eager_us_recorder_on": fe_on,
        "eager_us_recorder_off": fe_off,
        "eager_ratio": round(fe_ratio, 3),
        "serving_tokens_per_s_on": round(max(fs_on), 1),
        "serving_tokens_per_s_off": round(max(fs_off), 1),
        "serving_ratio": round(fs_ratio, 3),
        "within_noise": bool(0.8 <= fe_ratio <= 1.25
                             and fs_ratio >= 0.8),
    }
    return out


def bench_guard(steps=30, warmup=5):
    """Numerical-integrity guard A/B (ISSUE 20).

    Arm-alternating guard-on vs guard-off training steps/s on a small
    MLP — the same discipline as the tracing/flight-recorder A/Bs above
    (interleaved arms, best-of-2, so scheduler drift hits both arms
    equally).  The guard's contract is ONE fused sentinel reduction +
    ONE host sync per step over values the step already computes, so
    the throughput ratio must land within noise AND the compile-event
    counter must stay flat across both measured arms (the sentinel
    introduces no new traced program).
    """
    import time

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd, telemetry
    from mxnet_tpu import guard as guard_mod

    X = np.random.RandomState(11).randn(32, 16).astype("f")
    Y = (X.sum(1) > 0).astype("f")
    lf = gluon.loss.SoftmaxCrossEntropyLoss()

    def build(guarded):
        np.random.seed(0)
        mx.random.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(64, in_units=16, activation="relu"),
                gluon.nn.Dense(2, in_units=64))
        net.initialize(mx.init.Xavier())
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.05})
        if guarded:
            guard_mod.attach(trainer, guard=guard_mod.Guard(window=32))
        return net, trainer

    def compile_count():
        fam = telemetry.snapshot()["metrics"].get(
            "mxnet_compile_events_total")
        if not fam or not fam["samples"]:
            return 0.0
        return sum(s["value"] for s in fam["samples"])

    def run_arm(guarded):
        net, trainer = build(guarded)
        xs, ys = nd.array(X), nd.array(Y)

        def one_step():
            with autograd.record():
                loss = lf(net(xs), ys)
            loss.backward()
            trainer.step(X.shape[0])
            return loss

        for _ in range(warmup):
            one_step()
        t0 = time.perf_counter()
        last = None
        for _ in range(steps):
            last = one_step()
        np.asarray(last._get())      # settle the tail before stamping
        return steps / (time.perf_counter() - t0)

    # warm every trace in BOTH arms before measuring, so the measured
    # arms read pure steady state and the compile counter can be
    # asserted flat over them
    run_arm(False)
    run_arm(True)
    c0 = compile_count()
    on, off = [], []
    for _ in range(2):
        off.append(run_arm(False))
        on.append(run_arm(True))
    compile_delta = compile_count() - c0
    ratio = max(on) / max(off) if max(off) else 1.0
    return {
        "steps_per_s_guard_on": round(max(on), 2),
        "steps_per_s_guard_off": round(max(off), 2),
        "ratio": round(ratio, 3),
        # one fused sync per step is the design; anything beyond ~20%
        # on this CPU microbench is a regression, not noise
        "within_noise": bool(ratio >= 0.8),
        "compile_events_measured_arms": compile_delta,
        "compile_flat": bool(compile_delta == 0),
    }


def _effective_knobs():
    """The resolved tuning-knob configuration (value + provenance:
    default/env/tuned/trial) stamped into every ``extra.*`` result
    block — A/B arms can never silently run different configs, and a
    BENCH_*.json trajectory always says which knob values produced
    its numbers."""
    try:
        from mxnet_tpu import tuning

        return tuning.effective_config()
    except Exception as e:
        return {"error": repr(e)[:120]}


def bench_tune(workloads=None, rungs=2, budget0=2, serving=False):
    """Offline knob-space search (``bench.py --tune``, ISSUE 16).

    For each selected knob: run the deterministic grid +
    successive-halving schedule (``mxnet_tpu.tuning.search``), score
    every candidate with the live gauges — the telemetry step timeline
    (step wall seconds) for training arms, tokens/s + p99 TTFT folded
    into one ascending score for serving arms — and persist the winner
    into the tuning DB (``MXNET_TUNE_DB_DIR``) keyed by workload
    signature + device kind + jax fingerprint.  A warm process with
    ``MXNET_TUNE=1`` then replays the winner with ZERO search trials.

    Training workloads:

    - ``allreduce_bucket_mb`` — the ≤32KiB fused-allreduce regime (16
      tensors x 32KiB), the measured win/loss crossover from
      bench_overlap: per-key (cap 0) pays 16 collective launches where
      one fused bucket pays 1.
    - ``prefetch_buffer`` — an input-bound producer/consumer pipeline
      (~1 ms host work per side); depth overlaps them.

    Serving workloads (``--tune-serving``; engine spin-up per trial is
    the budget hog): ``serving_batch_buckets`` and
    ``serving_page_size`` on the tiny llama proxy — score is
    ``1/tokens_per_s + p99_ttft_s`` (ascending: throughput first,
    tail TTFT as the tiebreak).
    """
    import numpy as np

    import jax
    from mxnet_tpu import nd, telemetry, tuning
    from mxnet_tpu.parallel import bucketing
    from mxnet_tpu.parallel.collectives import allreduce_hosts

    db = tuning.default_db()

    def timed_step(once, budget):
        """min step-wall over ``budget`` timeline steps (the PR 14
        gauge the training arms score with; min = least-noise)."""
        best = None
        for _ in range(budget):
            telemetry.step_begin()
            once()
            rec = telemetry.step_end()
            if best is None or rec["wall_s"] < best:
                best = rec["wall_s"]
        return best

    # -- allreduce_bucket_mb: the <=32KiB fused-allreduce regime ----------
    n_tensors, elems = 16, 8192
    vals = [jax.numpy.asarray(
        np.random.RandomState(i).randn(elems).astype("f"))
        for i in range(n_tensors)]
    entries = [(i, (elems,), "float32") for i in range(n_tensors)]
    bucket_sig = ("allreduce_small", n_tensors, elems, "float32")

    def measure_bucket(value, budget):
        # cap flows trial -> tuning.resolve -> bucket_cap_bytes ->
        # assign_buckets: exactly the path production bucketing takes
        plan = bucketing.assign_buckets(entries)

        def once():
            outs = []
            for b in plan.buckets:
                flat = bucketing.pack([vals[i] for i in b.keys])
                outs.extend(bucketing.unpack(
                    b, allreduce_hosts(flat, _testing_force=True)))
            jax.block_until_ready(outs)

        once()                              # warm every jit path
        return timed_step(once, budget)

    # -- prefetch_buffer: input-bound producer/consumer pipeline ----------
    def measure_prefetch(value, budget):
        from mxnet_tpu.gluon.data.prefetcher import PrefetchIterator

        n = 8 * budget

        def src():
            for i in range(n):
                time.sleep(0.001)           # host-side input staging
                yield np.full((4, 8), i % 7, "float32")

        telemetry.step_begin()
        it = PrefetchIterator(src())        # depth from the funnel
        for batch in it:
            time.sleep(0.001)               # the "compute" side
            jax.block_until_ready(batch)
        it.close()
        rec = telemetry.step_end()
        return rec["wall_s"] / n

    measures = {
        "allreduce_bucket_mb": (measure_bucket, bucket_sig, "s/step"),
        "prefetch_buffer": (measure_prefetch,
                            ("prefetch_pipeline", 8), "s/batch"),
    }

    if serving:
        from mxnet_tpu import serving as _serving
        from mxnet_tpu.gluon.model_zoo.language.llama import llama_tiny

        def make_serving_measure():
            def measure(value, budget):
                net = llama_tiny()
                net.initialize()
                net(nd.zeros((1, 8), dtype="int32"))
                # batch buckets + page size resolve through the funnel
                # inside the ctor (the trial override is live here)
                eng = _serving.ServingEngine(
                    net, prefill_buckets=[8, 16], kv_pages=64,
                    max_batch=2)
                try:
                    eng.start()
                    rr = np.random.RandomState(0)
                    warm = eng.submit(rr.randint(1, 64, (3,)).astype(
                        "int32"), max_new_tokens=2)
                    warm.result(timeout=600)
                    # throughput phase: 2-deep closed loop
                    max_new, total = 4, 4 * budget
                    t0 = time.perf_counter()
                    pending = []
                    done = 0
                    for k in range(total):
                        pending.append(eng.submit(
                            rr.randint(1, 64, (1 + k % 8,)).astype(
                                "int32"), max_new_tokens=max_new))
                        while len(pending) >= 2:
                            pending.pop(0).result(timeout=600)
                            done += 1
                    for q in pending:
                        q.result(timeout=600)
                        done += 1
                    wall = time.perf_counter() - t0
                    tps = done * max_new / wall
                    # tail phase: max_new=1 completions ~ TTFT
                    lat = []
                    for k in range(2 * budget):
                        t1 = time.perf_counter()
                        eng.submit(rr.randint(1, 64, (4,)).astype(
                            "int32"), max_new_tokens=1).result(
                            timeout=600)
                        lat.append(time.perf_counter() - t1)
                    lat.sort()
                    p99 = lat[min(len(lat) - 1, int(0.99 * len(lat)))]
                finally:
                    eng.close()
                return 1.0 / max(tps, 1e-9) + p99
            return measure

        measures["serving_batch_buckets"] = (
            make_serving_measure(), ("llama_tiny_serving",),
            "1/tps+p99ttft_s")
        measures["serving_page_size"] = (
            make_serving_measure(), ("llama_tiny_serving",),
            "1/tps+p99ttft_s")

    selected = list(workloads) if workloads else \
        [k for k in measures if tuning.get_knob(k).kind == "training"
         or serving]
    reports = {}
    for name in selected:
        if name not in measures:
            reports[name] = {"error": f"no tune workload for {name!r}"}
            continue
        measure, sig, unit = measures[name]
        reports[name] = tuning.tune_knob(
            name, measure, db=db, signature=sig, rungs=rungs,
            budget0=budget0, unit=unit, log=lambda m: None)
    return reports


def tune_main(argv):
    """``bench.py --tune`` driver: run the search, persist winners,
    print ONE JSON line with best-vs-default deltas per knob + DB
    stats (the ci/tuning_smoke.py contract)."""
    workloads = None
    rungs, budget0 = 2, 2
    serving = "--tune-serving" in argv
    for arg in argv:
        if arg.startswith("--tune-workloads="):
            workloads = [w for w in
                         arg.split("=", 1)[1].split(",") if w]
        elif arg.startswith("--tune-rungs="):
            rungs = max(1, int(arg.split("=", 1)[1]))
        elif arg.startswith("--tune-budget="):
            budget0 = max(1, int(arg.split("=", 1)[1]))
    from mxnet_tpu import telemetry, tuning

    reports = bench_tune(workloads=workloads, rungs=rungs,
                         budget0=budget0, serving=serving)
    db = tuning.default_db()
    snap = telemetry.snapshot()["metrics"]

    def total(name):
        return sum(int(s["value"])
                   for s in snap.get(name, {}).get("samples", ()))

    out = {
        "metric": "tuning_search",
        "tune": reports,
        "db": db.stats() if db is not None else
        {"error": "MXNET_TUNE_DB_DIR unset; winners NOT persisted"},
        "trials_total": total("mxnet_tuning_trials_total"),
        "db_stores_total": total("mxnet_tuning_db_stores_total"),
        "knobs": _effective_knobs(),
    }
    print(json.dumps(out))


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the chip and JAX has none "
            f"(jax.devices()[0].platform == {dev.platform!r})")
    img_s, resnet_mfu, resnet_cfgs = bench_resnet50(on_tpu=True)
    extra = {"resnet_configs": resnet_cfgs}
    try:
        bert_s, bert_mfu = bench_bert(on_tpu=True)
        extra["bert_base_pretrain"] = {
            "value": round(bert_s, 2), "unit": "samples/s/chip",
            "mfu": round(bert_mfu, 4)}
    except Exception as e:  # keep the headline alive
        extra["bert_base_pretrain"] = {"error": repr(e)[:200]}
    try:
        llama_s, llama_mfu = bench_llama(on_tpu=True)
        extra["llama_proxy_train"] = {
            "value": round(llama_s, 2), "unit": "tokens/s/chip",
            "mfu": round(llama_mfu, 4)}
    except Exception as e:
        extra["llama_proxy_train"] = {"error": repr(e)[:200]}
    try:
        # tentpole observability (ISSUE 1): the eager dispatch fast path's
        # µs/op win, measured on whatever backend this run has
        extra["eager_op_overhead"] = bench_eager_op_overhead()
    except Exception as e:
        extra["eager_op_overhead"] = {"error": repr(e)[:200]}
    try:
        # overlap engine (ISSUE 4): input-bound prefetch A/B + fused
        # allreduce curve, so the next TPU driver run captures the win
        # unattended
        extra["overlap"] = bench_overlap()
    except Exception as e:
        extra["overlap"] = {"error": repr(e)[:200]}
    try:
        # serving engine (ISSUE 8): closed/open-loop load generation
        # against the AOT-compiled continuous-batching server — p50/p99
        # + tokens/s/chip per concurrency, with the zero-fresh-trace
        # steady-state contract measured, not assumed
        extra["serving"] = bench_serving()
    except Exception as e:
        extra["serving"] = {"error": repr(e)[:200]}
    try:
        # sharding planner (ISSUE 10): one-time plan cost, the
        # zero-per-step-cost pin, and the HBM model's estimated-vs-
        # actual bytes under two mesh shapes
        extra["planner"] = bench_planner()
    except Exception as e:
        extra["planner"] = {"error": repr(e)[:200]}
    try:
        # zero-downtime elasticity (ISSUE 13): live ZeRO reshard vs
        # checkpoint round trip, serving replica handoff
        # join-to-first-token
        extra["elastic"] = bench_elastic()
    except Exception as e:
        extra["elastic"] = {"error": repr(e)[:200]}
    try:
        # runtime introspection plane (ISSUE 14): A/B instrumentation
        # overhead (eager µs/op + serving tokens/s, tracing on vs off)
        # and the online-vs-offline MFU pin on the llama proxy (same
        # cost_analysis FLOPs source => tight tolerance)
        extra["observability"] = bench_observability()
    except Exception as e:
        extra["observability"] = {"error": repr(e)[:200]}
    try:
        # serving fleet router (ISSUE 17): closed-loop p50/p99 +
        # tokens/s at 1 vs 3 replicas (router overhead + scaling), and
        # kill-to-warm-replacement recovery time under load
        extra["fleet"] = bench_fleet()
    except Exception as e:
        extra["fleet"] = {"error": repr(e)[:200]}
    try:
        # numerical-integrity guard (ISSUE 20): arm-alternating A/B —
        # guard-on vs guard-off steps/s within noise (one fused
        # sentinel sync per step) with the compile counter flat over
        # the measured arms
        extra["guard"] = bench_guard()
    except Exception as e:
        extra["guard"] = {"error": repr(e)[:200]}
    try:
        # BASELINE binding metric: allreduce bandwidth (tools/bandwidth_
        # measure.py ≙ reference tools/bandwidth/measure.py).  The bus
        # formula is zero at one device, so the metric only reports on a
        # real multi-device mesh (pod / virtual mesh).
        import jax as _jax

        if len(_jax.devices()) > 1:
            import os as _os
            import sys as _sys

            _sys.path.insert(0, _os.path.join(
                _os.path.dirname(_os.path.abspath(__file__)), "tools"))
            import bandwidth_measure as _bwm

            dt, bw = _bwm.measure_allreduce(64 << 20, iters=5)
            extra["allreduce_bw_64mb"] = {"value": round(bw, 2),
                                          "unit": "GB/s"}
        else:
            extra["allreduce_bw_64mb"] = {
                "skipped": "single device (bus formula is 0 at n=1)"}
    except Exception as e:
        extra["allreduce_bw_64mb"] = {"error": repr(e)[:200]}
    try:
        # runtime telemetry (ISSUE 3): attach diagnosis context — cache
        # efficiency, compile pressure, and the step-phase breakdown — so
        # BENCH_*.json trajectories explain their throughput, not just
        # report it
        import mxnet_tpu as _mx
        from mxnet_tpu import telemetry as _telemetry

        snap = _telemetry.snapshot()
        ds = _mx.nd.dispatch_stats()
        looked = ds["hits"] + ds["misses"]
        # by_cause from the COUNTER family, not the bounded event ring —
        # a >512-compile retrace storm would otherwise undercount exactly
        # when the breakdown matters most
        by_cause = {}
        for s in snap["metrics"]["mxnet_compile_events_total"]["samples"]:
            cause = s["labels"].get("cause", "?")
            by_cause[cause] = by_cause.get(cause, 0) + int(s["value"])
        extra["telemetry"] = {
            "dispatch_cache": {
                "hit_rate": round(ds["hits"] / looked, 4) if looked else None,
                "hits": ds["hits"], "misses": ds["misses"],
                "evictions": ds["evictions"], "bypasses": ds["bypasses"]},
            "compile": {"count": snap["compile"]["count"],
                        "total_s": round(snap["compile"]["total_s"], 3),
                        "by_cause": by_cause},
            "step_phase_totals_s": {
                k: round(v, 4)
                for k, v in snap["step_phase_totals"].items()},
        }
    except Exception as e:
        extra["telemetry"] = {"error": repr(e)[:200]}

    # effective knob configuration (value + default/env/tuned source) in
    # EVERY result block: a number without its knob config is not
    # reproducible (ISSUE 16 satellite)
    knobs = _effective_knobs()
    for block in extra.values():
        if isinstance(block, dict):
            block["knobs"] = knobs

    out = {
        "metric": "resnet50_train_throughput",
        "value": round(img_s, 2),
        "unit": "img/s/chip",
        "vs_baseline": round(img_s / BASELINE_IMG_S_PER_CHIP, 4),
        "mfu": round(resnet_mfu, 4),
        "precision": "bf16_amp",
        "extra": extra,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    import sys as _sys

    if "--tune" in _sys.argv:
        tune_main(_sys.argv[1:])
    else:
        main()
