"""Chip smoke: the quickest proof that the system still starts on the TPU.

    python chip_smoke.py

Drives the training path once through the entry points a user calls, at
the full width of BERT-base (``BertConfig()`` defaults, sequence 512,
bf16): first the README Quickstart (``mx.tpu()`` -> imperative dispatch ->
``hybridize()`` -> ``Trainer.step``), then ``BertForPretraining`` through
``TrainStep`` on one chip and, where JAX finds several, over
``make_mesh()`` with the batch split over ``dp``.  Every phase checks what
came out (finite losses of the expected size that fall, values on the
device their context names, the Pallas kernel compiled by Mosaic); any
failed check raises and the exit code is not 0.

There is no option: ``main()`` always runs the full width and always
demands the chip.  The seconds it prints are smoke timings, not benchmark
numbers.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np

SEQ = 512              # BertConfig().max_position: the Pallas side of the
                       # flash-attention gate (>= 256)
BATCH_PER_CHIP = 16    # 8,192 tokens a chip, the token count of bench.py's arm
STEPS = 8
# Loss of a freshly initialised model on random labels is ln(vocab) for
# the MLM head plus ln 2 for NSP, plus about half the variance of the
# logits: a float32 CPU forward of BERT-base at initialisation gives MLM
# logits of standard deviation 1.1, which adds 0.6.
FIRST_LOSS_TOL = 1.0
# One chip and the mesh see different batches (16 and 16 x chips rows)
# and different dropout masks; both losses are means over >= 8k
# random-label tokens of the same initial weights.
MESH_LOSS_TOL = 0.05


def check(ok, *detail):
    """A check that ``python -O`` does not remove."""
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {detail}")


def _check_placed(nd_array, ctx):
    """Label and device agree: the array says ``ctx`` and sits on it."""
    check(nd_array.context == ctx, nd_array.context, ctx)
    check(nd_array._get().devices() == {ctx.device}, ctx,
          nd_array._get().devices())


def quickstart_leg(ctx, steps=3):
    """README Quickstart on ``ctx``, then the placement of what it made."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(64, 3, padding=1, activation="relu"),
            gluon.nn.BatchNorm(), gluon.nn.Flatten(), gluon.nn.Dense(10))
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.hybridize()
    # the README's 0.1 memorises these 32 samples in one step (loss 0.0
    # from the second on); 0.01 leaves a decline to check
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.randn(32, 3, 16, 16).astype("float32"), ctx=ctx)
    y = mx.nd.array(rs.randint(0, 10, (32,)).astype("float32"), ctx=ctx)
    losses = []
    for _ in range(steps):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(32)
        losses.append(float(loss.mean().asscalar()))
    check(np.isfinite(losses).all(), losses)
    check(all(b < a for a, b in zip(losses, losses[1:])), losses)
    for p in net.collect_params().values():
        _check_placed(p.data(), ctx)
    _check_placed(loss, ctx)
    # creation with no context lands where JAX computes by default,
    # creation with one lands there
    _check_placed(mx.nd.zeros((2, 2)), mx.current_context())
    _check_placed(mx.nd.zeros((2, 2), ctx=mx.cpu()), mx.cpu())
    return losses


def pretrain_loss(outs, labels):
    """MLM + NSP cross-entropy, as bench.py's BERT arm computes it."""
    import jax
    import jax.numpy as jnp

    mlm, nsp = outs
    mlm_labels, nsp_labels = labels[:, :-1], labels[:, -1]
    logp = jax.nn.log_softmax(mlm, axis=-1)
    mlm_l = -jnp.take_along_axis(logp, mlm_labels[..., None], axis=-1)
    nsp_logp = jax.nn.log_softmax(nsp, axis=-1)
    nsp_l = -jnp.take_along_axis(nsp_logp, nsp_labels[:, None], axis=-1)
    return jnp.mean(mlm_l) + jnp.mean(nsp_l)


def bert_leg(net, batch, seq, steps, mesh=None):
    """``steps`` fused bf16 training steps of ``net`` (a settled
    ``BertForPretraining``) on one fixed batch made from a seed, through
    ``TrainStep`` as bench.py builds it.  ``mesh=None`` is one device;
    a mesh splits the batch over ``dp``.  Checks the losses and where
    everything sits; returns what it saw."""
    import jax

    from mxnet_tpu.parallel.data_parallel import TrainStep

    vocab = net._cfg.vocab_size
    rs = np.random.RandomState(0)
    ids = rs.randint(0, vocab, (batch, seq)).astype("int32")
    labels = np.concatenate(
        [rs.randint(0, vocab, (batch, seq)),
         rs.randint(0, 2, (batch, 1))], axis=1).astype("int32")

    step = TrainStep(net, pretrain_loss, optimizer="adam",
                     optimizer_params={"learning_rate": 1e-4},
                     train_mode=True, dtype="bfloat16", mesh=mesh,
                     batch_axes=("dp",))
    x, y = step._stage_batch(ids), step._stage_batch(labels)
    lowered = step._step.lower(
        step.train_params, step.rest_params, step.opt_state,
        jax.random.PRNGKey(0), x, y)
    mosaic_call = "tpu_custom_call" in lowered.as_text()

    losses, t = [], [time.perf_counter()]
    for _ in range(steps):
        loss = step(x, y)
        losses.append(float(loss))       # the read waits for the step
        t.append(time.perf_counter())

    expected = math.log(vocab) + math.log(2.0)
    check(np.isfinite(losses).all(), losses)
    check(abs(losses[0] - expected) < FIRST_LOSS_TOL, losses[0], expected)
    check(losses[-1] < losses[0], losses)

    want = set(mesh.devices.flat) if mesh is not None else {jax.devices()[0]}
    found = set(loss.devices())
    for name, leaf in step.train_params.items():
        check(leaf.devices() == want, name, leaf.devices())
        found |= leaf.devices()
    if mesh is not None:
        n = len(want)
        check(x.sharding.shard_shape(x.shape) == (batch // n, seq),
              x.sharding)
        for d in want:
            stats = d.memory_stats()     # None on the CPU backend
            check(stats is None or stats["bytes_in_use"] > 0, d)
    return {"losses": losses, "first_step_s": t[1] - t[0],
            "steady_step_s": (t[-1] - t[1]) / (steps - 1),
            "mosaic_call": mosaic_call,
            "platforms": sorted({d.platform for d in found})}


def _cache_entries(path):
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for f in os.listdir(path) if not f.endswith("-atime"))


def main():
    import importlib.metadata as md

    import mxnet_tpu as mx      # places the compile cache, touches no backend
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("chip_smoke: FAIL: JAX found no TPU "
              f"(jax.devices()[0].platform == {dev.platform!r})",
              file=sys.stderr)
        return 1
    count = len(jax.devices())
    cache_dir = jax.config.jax_compilation_cache_dir

    def say(key, value):
        print(f"chip_smoke: {key}: {value}", flush=True)

    say("device", f"{dev.platform} / {dev.device_kind} x {count}")
    say("versions", " ".join(f"{p} {md.version(p)}"
                             for p in ("jax", "jaxlib", "libtpu")))
    say("compile cache", f"{cache_dir} "
        f"({_cache_entries(cache_dir)} entries before)")

    t0 = time.perf_counter()
    say("quickstart losses", quickstart_leg(mx.tpu()))
    say("quickstart seconds (smoke timing)",
        round(time.perf_counter() - t0, 1))
    if count > 1:
        _check_placed(mx.nd.zeros((2, 2), ctx=mx.tpu(1)), mx.tpu(1))

    from mxnet_tpu.gluon.model_zoo.language import bert

    mx.random.seed(0)
    net = bert.BertForPretraining(bert.BertConfig())
    net.initialize(ctx=mx.tpu())
    net(mx.nd.zeros((1, SEQ), dtype="int32"))   # settle deferred shapes

    def report(tag, r):
        check(r["platforms"] == ["tpu"], r["platforms"])
        check(r["mosaic_call"], "the lowered step holds no tpu_custom_call: "
              "the Pallas forward was not compiled for the chip")
        say(f"{tag} losses", [round(v, 4) for v in r["losses"]])
        say(f"{tag} seconds to first step (smoke timing)",
            round(r["first_step_s"], 1))
        say(f"{tag} seconds per steady step (smoke timing)",
            round(r["steady_step_s"], 3))

    one = bert_leg(net, BATCH_PER_CHIP, SEQ, STEPS)
    report("bert-base 1 chip", one)
    if count > 1:
        from mxnet_tpu.parallel.mesh import make_mesh

        many = bert_leg(net, BATCH_PER_CHIP * count, SEQ, STEPS,
                        mesh=make_mesh())
        report(f"bert-base dp={count}", many)
        gap = abs(many["losses"][0] - one["losses"][0])
        check(gap < MESH_LOSS_TOL, many["losses"][0], one["losses"][0])
        say("first loss, mesh against one chip", f"differ by {gap:.4f}")

    say("peak_bytes_in_use", [d.memory_stats()["peak_bytes_in_use"]
                              for d in jax.devices()])
    say("compile cache entries after", _cache_entries(cache_dir))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
