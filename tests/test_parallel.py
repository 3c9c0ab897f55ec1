"""SPMD parallel layer: mesh construction, collectives, fused TrainStep.

Reference analog: tests/python/unittest/test_kvstore.py + the nightly
dist_sync_kvstore.py multi-process tests (SURVEY.md §5.4) — here exercised
on the 8-virtual-device CPU mesh.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, autograd
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.data_parallel import TrainStep, fsdp_specs
from mxnet_tpu.parallel.functional import functionalize


def _tiny_net(classes=4):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"),
            gluon.nn.BatchNorm(),
            gluon.nn.Dense(classes))
    net.initialize()
    net(nd.zeros((2, 8)))
    return net


def _ce(logits, labels):
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)


def test_functionalize_matches_eager():
    net = _tiny_net()
    import jax

    apply_fn, params = functionalize(net)
    x = np.random.randn(4, 8).astype("float32")
    out = apply_fn(params, jax.random.PRNGKey(0), x)
    eager = net(nd.array(x)).asnumpy()
    np.testing.assert_allclose(np.asarray(out), eager, rtol=1e-5, atol=1e-5)


def test_functionalize_plain_block_params_traced():
    """Plain (non-hybrid) Blocks must read traced param values, not bake
    constants (otherwise grads silently vanish)."""
    import jax

    class Plain(gluon.Block):
        def __init__(self):
            super().__init__()
            self.w = self.params.get("w", shape=(3, 3), init="ones")

        def forward(self, x):
            return nd.dot(x, self.w.data())

    net = Plain()
    net.initialize()
    apply_fn, params = functionalize(net)
    (name,) = list(params)

    def loss(p, x):
        return apply_fn(p, jax.random.PRNGKey(0), x).sum()

    x = np.random.randn(2, 3).astype("float32")
    grads = jax.grad(loss)(params, x)
    assert float(np.abs(np.asarray(grads[name])).sum()) > 0


def test_train_step_single_device_loss_decreases():
    net = _tiny_net()
    step = TrainStep(net, _ce, optimizer="sgd",
                     optimizer_params={"learning_rate": 0.5, "momentum": 0.9})
    x = np.random.randn(32, 8).astype("float32")
    y = (x.sum(axis=1) > 0).astype("int32")
    first = float(step(x, y))
    for _ in range(20):
        last = float(step(x, y))
    assert last < first

    # BatchNorm moving stats must have moved (state threading works)
    bn_means = [v for k, v in step.params.items() if "running_mean" in k]
    assert bn_means and float(np.abs(np.asarray(bn_means[0])).sum()) > 0

    # write_back must not crash and must sync values
    step.write_back()
    for name, p in net.collect_params().items():
        np.testing.assert_allclose(p.data().asnumpy(),
                                   np.asarray(step.params[name]), rtol=1e-6)


def test_train_step_net_stays_alive_after_donation():
    """Donated jit args must not invalidate the Gluon net's own buffers."""
    net = _tiny_net()
    step = TrainStep(net, _ce, optimizer="sgd")
    x = np.random.randn(8, 8).astype("float32")
    y = np.zeros((8,), "int32")
    step(x, y)
    out = net(nd.array(x))  # would raise "Array has been deleted" if aliased
    assert out.shape == (8, 4)


def test_train_step_fsdp_mesh_matches_single_device():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    mesh = make_mesh(dp=2, fsdp=2, tp=2, devices=jax.devices()[:8])
    net = _tiny_net()
    stepm = TrainStep(net, _ce, optimizer="sgd",
                      optimizer_params={"learning_rate": 0.1},
                      mesh=mesh, param_sharding="fsdp",
                      batch_axes=("dp", "fsdp"))
    net2 = _tiny_net()
    # same initial params — paired STRUCTURALLY (collect_params insertion
    # order), not by sorted global name: gluon's process-wide name counter
    # means a net whose layers straddle a digit boundary (dense9/dense10)
    # sorts out of structural order, and the pairing silently crosses
    # layers (the old order-dependent flake: whether the boundary was
    # straddled depended on how many layers earlier tests had created)
    for (k, _), (k2, p2) in zip(net.collect_params().items(),
                                net2.collect_params().items()):
        p2.data()._set(stepm.params[k])
    steps = TrainStep(net2, _ce, optimizer="sgd",
                      optimizer_params={"learning_rate": 0.1})
    x = np.random.randn(8, 8).astype("float32")
    y = (x.sum(axis=1) > 0).astype("int32")
    for _ in range(3):
        lm = float(stepm(x, y))
        ls = float(steps(x, y))
    np.testing.assert_allclose(lm, ls, rtol=1e-4, atol=1e-5)


def test_adam_train_step():
    net = _tiny_net()
    step = TrainStep(net, _ce, optimizer="adam",
                     optimizer_params={"learning_rate": 0.01})
    x = np.random.randn(16, 8).astype("float32")
    y = (x.sum(axis=1) > 0).astype("int32")
    first = float(step(x, y))
    for _ in range(20):
        last = float(step(x, y))
    assert last < first


def test_trainstep_mesh_does_not_donate_net_buffers():
    # regression: device_put may alias the net's param buffers when the
    # sharding already matches; donation must not invalidate them
    import jax
    import numpy as np
    from jax.sharding import Mesh

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.data_parallel import TrainStep

    net = nn.Dense(4)
    net.initialize()
    net(mx.nd.ones((2, 3)))
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("dp", "fsdp", "tp"))

    def loss_fn(logits, labels):
        import jax.numpy as jnp

        return jnp.square(logits).mean()

    step = TrainStep(net, loss_fn, mesh=mesh, param_sharding="replicated",
                     batch_axes=("dp", "fsdp"))
    step(np.ones((2, 3), "f"), np.zeros((2,), "i")).block_until_ready()
    out = net(mx.nd.ones((2, 3)))  # must not raise "buffer deleted/donated"
    assert out.shape == (2, 4)


def test_sync_batch_norm_single_process_matches_bn():
    """ndev=1: SyncBatchNorm degenerates to plain BatchNorm (reference
    sync_batch_norm.cc with ndev=1)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon

    x = np.random.RandomState(0).randn(4, 3, 5, 5).astype("f")
    sbn = gluon.contrib.nn.SyncBatchNorm(in_channels=3)
    bn = gluon.nn.BatchNorm(in_channels=3)
    sbn.initialize()
    bn.initialize()
    with autograd.record():
        y1 = sbn(mx.nd.array(x))
    with autograd.record():
        y2 = bn(mx.nd.array(x))
    assert np.allclose(y1.asnumpy(), y2.asnumpy(), atol=1e-5)
    assert np.allclose(sbn.running_mean.data().asnumpy(),
                       bn.running_mean.data().asnumpy(), atol=1e-6)


def test_pipeline_parallel_matches_sequential():
    """GPipe over pp: forward exact + grads match the sequential stack."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from mxnet_tpu.parallel.pipeline_parallel import (pipeline_apply,
                                                      stack_stage_params)

    S, D = 4, 8
    mesh = Mesh(np.array(jax.devices()[:S]), ("pp",))
    rs = np.random.RandomState(0)

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"] + params["b"])

    per_stage = [{"w": jnp.asarray(rs.randn(D, D).astype("f") * 0.5),
                  "b": jnp.asarray(rs.randn(D).astype("f") * 0.1)}
                 for _ in range(S)]
    stacked = stack_stage_params(per_stage)
    x = jnp.asarray(rs.randn(16, D).astype("f"))

    y = pipeline_apply(stage_fn, stacked, x, mesh, num_microbatches=4)
    ref = x
    for p in per_stage:
        ref = stage_fn(p, ref)
    assert float(jnp.abs(y - ref).max()) < 1e-5

    def loss_pp(params):
        return pipeline_apply(stage_fn, params, x, mesh,
                              num_microbatches=4).sum()

    def loss_seq(per):
        h = x
        for p in per:
            h = stage_fn(p, h)
        return h.sum()

    # (jitted: an eager gradient of the shard_map runs the schedule op by op)
    g_pp = jax.jit(jax.grad(loss_pp))(stacked)
    g_seq = stack_stage_params(jax.grad(loss_seq)(per_stage))
    for k in ("w", "b"):
        assert float(jnp.abs(g_pp[k] - g_seq[k]).max()) < 1e-4, k


def test_pipeline_remat_stage_matches():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from mxnet_tpu.parallel.pipeline_parallel import (pipeline_apply,
                                                      stack_stage_params)

    S, D = 2, 4
    mesh = Mesh(np.array(jax.devices()[:S]), ("pp",))
    rs = np.random.RandomState(1)

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"])

    stacked = stack_stage_params(
        [{"w": jnp.asarray(rs.randn(D, D).astype("f") * 0.5)}
         for _ in range(S)])
    x = jnp.asarray(rs.randn(8, D).astype("f"))
    y1 = pipeline_apply(stage_fn, stacked, x, mesh, 4, remat_stage=False)
    y2 = pipeline_apply(stage_fn, stacked, x, mesh, 4, remat_stage=True)
    assert float(jnp.abs(y1 - y2).max()) < 1e-6


def test_moe_expert_parallel():
    """Switch MoE: matches per-token routing oracle; ep sharding is a
    no-op numerically; capacity drops tokens; grads finite; balance loss."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from mxnet_tpu.parallel.expert_parallel import (moe_apply,
                                                    stack_expert_params)

    rs = np.random.RandomState(0)
    T, d, E = 32, 8, 4
    x = jnp.asarray(rs.randn(T, d).astype("f"))
    wr = jnp.asarray(rs.randn(d, E).astype("f") * 0.5)
    per = [{"w": jnp.asarray(rs.randn(d, d).astype("f") * 0.4)}
           for _ in range(E)]
    params = stack_expert_params(per)

    def expert_fn(p, toks):
        return jnp.tanh(toks @ p["w"])

    out_ref, aux = moe_apply(expert_fn, params, wr, x, mesh=None,
                             capacity_factor=8.0)
    gates = jax.nn.softmax(x @ wr, axis=-1)
    idx = np.asarray(jnp.argmax(gates, axis=-1))
    manual = np.stack([np.asarray(jnp.tanh(x[i] @ per[int(idx[i])]["w"]))
                       * float(gates[i, idx[i]]) for i in range(T)])
    assert np.allclose(np.asarray(out_ref), manual, atol=1e-5)

    mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
    out_sh, _ = jax.jit(lambda p, w, xx: moe_apply(
        expert_fn, p, w, xx, mesh=mesh, capacity_factor=8.0))(params, wr, x)
    assert np.allclose(np.asarray(out_sh), np.asarray(out_ref), atol=1e-5)

    out_c, aux_c = moe_apply(expert_fn, params, wr, x, capacity_factor=0.1)
    assert out_c.shape == (T, d) and float(aux_c["dropped"]) > 0

    g = jax.grad(lambda p: moe_apply(expert_fn, p, wr, x,
                                     capacity_factor=8.0)[0].sum())(params)
    assert np.isfinite(np.asarray(g["w"])).all()
    assert float(aux["load_balance_loss"]) > 0


def test_pipeline_stage_count_mismatch_raises():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pytest
    from jax.sharding import Mesh

    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.parallel.pipeline_parallel import (pipeline_apply,
                                                      stack_stage_params)

    mesh = Mesh(np.array(jax.devices()[:4]), ("pp",))
    stacked = stack_stage_params(
        [{"w": jnp.eye(4)} for _ in range(8)])  # 8 stages, 4 devices
    with pytest.raises(MXNetError, match="leading dim"):
        pipeline_apply(lambda p, h: h @ p["w"], stacked,
                       jnp.ones((8, 4)), mesh, num_microbatches=4)


def test_pipeline_nan_safe_stage():
    """Warmup-tick garbage through a NaN-producing stage must not poison
    valid outputs (review finding: arithmetic masking)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from mxnet_tpu.parallel.pipeline_parallel import (pipeline_apply,
                                                      stack_stage_params)

    S = 2
    mesh = Mesh(np.array(jax.devices()[:S]), ("pp",))

    def stage_fn(p, h):  # un-eps'd normalization: NaN on all-zero input
        return (h / jnp.linalg.norm(h, axis=-1, keepdims=True)) @ p["w"]

    rs = np.random.RandomState(0)
    stacked = stack_stage_params(
        [{"w": jnp.asarray(rs.randn(4, 4).astype("f"))} for _ in range(S)])
    x = jnp.asarray(rs.randn(8, 4).astype("f"))
    y = pipeline_apply(stage_fn, stacked, x, mesh, num_microbatches=4)
    ref = x
    for i in range(S):
        ref = stage_fn({"w": stacked["w"][i]}, ref)
    assert np.isfinite(np.asarray(y)).all()
    assert float(jnp.abs(y - ref).max()) < 1e-4


def test_pipeline_nan_safe_backward():
    """Gradients stay finite (and correct) when the stage would NaN on the
    bubble-tick garbage — the 0*NaN VJP gotcha (review finding)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from mxnet_tpu.parallel.pipeline_parallel import (pipeline_apply,
                                                      stack_stage_params)

    S = 2
    mesh = Mesh(np.array(jax.devices()[:S]), ("pp",))

    def stage_fn(p, h):  # NaN on all-zero input
        return (h / jnp.linalg.norm(h, axis=-1, keepdims=True)) @ p["w"]

    rs = np.random.RandomState(2)
    per = [{"w": jnp.asarray(rs.randn(4, 4).astype("f"))} for _ in range(S)]
    stacked = stack_stage_params(per)
    x = jnp.asarray(rs.randn(8, 4).astype("f"))

    def loss_pp(p):
        return pipeline_apply(stage_fn, p, x, mesh, 4).sum()

    def loss_seq(per_):
        h = x
        for p in per_:
            h = stage_fn(p, h)
        return h.sum()

    g_pp = jax.jit(jax.grad(loss_pp))(stacked)
    assert np.isfinite(np.asarray(g_pp["w"])).all()
    g_seq = stack_stage_params(jax.grad(loss_seq)(per))
    assert float(jnp.abs(g_pp["w"] - g_seq["w"]).max()) < 1e-4


def test_inject_aux_loss_gradient_semantics():
    """inject_aux_loss: forward identity; backward adds d(aux)/d(inputs)
    with coefficient 1 regardless of the downstream reduction."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.parallel.expert_parallel import inject_aux_loss

    w = jnp.asarray(np.array([2.0, -1.0], "f"))
    x = jnp.asarray(np.array([1.0, 3.0], "f"))

    def loss(w):
        y = x * w
        aux = 0.5 * jnp.sum(w ** 2)
        y = inject_aux_loss(y, aux)
        return jnp.mean(y)  # downstream mean must NOT rescale aux

    g = jax.grad(loss)(w)
    expect = x / 2 + w  # d(mean(xw))/dw + d(0.5 w^2)/dw
    assert np.allclose(np.asarray(g), np.asarray(expect), atol=1e-6)
    # forward identity
    assert float(loss(w)) == float(jnp.mean(x * w))


def test_moe_bf16_queue_positions_do_not_collide():
    """Expert-queue positions are counted in int32: with bf16 activations
    and >256 tokens routed to one expert, a cumsum in x.dtype would make
    positions collide above 256 and silently merge/drop tokens (advisor
    finding r4)."""
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.parallel.expert_parallel import (moe_apply,
                                                    stack_expert_params)

    T, d, E = 600, 4, 2
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(T, d).astype("f")).astype(jnp.bfloat16)
    # zero router: argmax ties resolve to index 0, so every token routes
    # to expert 0 regardless of input sign
    wr = jnp.zeros((d, E), jnp.bfloat16)
    params = stack_expert_params(
        [{"w": jnp.asarray(rs.randn(d, d).astype("f") * 0.3
                           ).astype(jnp.bfloat16)} for _ in range(E)])

    def expert_fn(p, toks):
        return jnp.tanh(toks @ p["w"])

    # capacity_factor=E makes C == T: nothing may be dropped
    out, aux = moe_apply(expert_fn, params, wr, x, mesh=None,
                         capacity_factor=float(E))
    assert float(aux["dropped"]) == 0.0, aux["dropped"]
    assert float(aux["expert_load"][0]) == T
    assert np.isfinite(np.asarray(out, dtype="f")).all()


def test_ulysses_attention_matches_reference_and_ring():
    """DeepSpeed-Ulysses all_to_all sequence parallelism (the complement
    of ring attention): output and grads exactly match full attention,
    and agree with the ring schedule."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from mxnet_tpu.ops.flash_attention import _mha_reference
    from mxnet_tpu.parallel.context_parallel import (
        context_parallel_attention, ulysses_context_parallel_attention)

    mesh = Mesh(np.array(jax.devices()[:8]), ("sp",))
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(2, 8, 64, 16).astype("f"))
    k = jnp.asarray(rs.randn(2, 8, 64, 16).astype("f"))
    v = jnp.asarray(rs.randn(2, 8, 64, 16).astype("f"))
    for causal in (False, True):
        o = ulysses_context_parallel_attention(q, k, v, mesh,
                                               causal=causal)
        ref = _mha_reference(q, k, v, causal, 1.0 / np.sqrt(16))
        assert float(jnp.abs(o - ref).max()) < 1e-4
        ring = context_parallel_attention(q, k, v, mesh, causal=causal)
        assert float(jnp.abs(o - ring).max()) < 1e-4

    g = jax.jit(jax.grad(lambda qq: (ulysses_context_parallel_attention(
        qq, k, v, mesh, causal=True) ** 2).sum()))(q)
    gref = jax.grad(lambda qq: (_mha_reference(
        qq, k, v, True, 1.0 / np.sqrt(16)) ** 2).sum())(q)
    assert float(jnp.abs(g - gref).max()) < 1e-3


def test_ulysses_attention_rejects_indivisible_heads():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pytest
    from jax.sharding import Mesh

    from mxnet_tpu.parallel.context_parallel import (
        ulysses_context_parallel_attention)

    mesh = Mesh(np.array(jax.devices()[:8]), ("sp",))
    q = jnp.zeros((1, 4, 16, 8), "f")  # 4 heads, 8-way sp
    with pytest.raises(ValueError, match="divisible"):
        ulysses_context_parallel_attention(q, q, q, mesh)


# --------------------------------------------------------------------------
# one tracer for a Gluon net: NDArray over jax tracers, whoever asks
# --------------------------------------------------------------------------
def _toy_bert():
    from mxnet_tpu.gluon.model_zoo.language import bert

    net = bert.BertForPretraining(bert.BertConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        intermediate_size=64, max_position=16, dropout=0.0))
    ids = np.random.RandomState(0).randint(0, 64, (2, 16)).astype("int32")
    return net, ids, lambda outs, y: (outs[0] ** 2).mean() + outs[1].mean()


def _toy_llama(**extra):
    from mxnet_tpu.gluon.model_zoo.language import llama

    net = llama.LlamaForCausalLM(llama.LlamaConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, max_seq_len=16, **extra))
    ids = np.random.RandomState(0).randint(0, 64, (2, 16)).astype("int32")
    return net, ids, lambda logits, y: (logits ** 2).mean()


def _toy_llama_remat_experts_block_diffusion():
    return _toy_llama(remat=True, num_experts=4, moe_capacity_factor=None,
                      moe_top_k=2, moe_renormalize=True,
                      moe_experts_held=(1, 2), moe_intermediate_size=16,
                      block_diffusion=4)


def _toy_resnet():
    net = gluon.model_zoo.vision.get_model("resnet18_v1", classes=4)
    x = np.random.RandomState(0).randn(2, 3, 32, 32).astype("float32")
    return net, x, lambda logits, y: (logits ** 2).mean()


@pytest.mark.parametrize("how", ["train_step", "hybridize"])
@pytest.mark.parametrize("make", [_toy_bert, _toy_llama,
                                  _toy_llama_remat_experts_block_diffusion,
                                  _toy_resnet])
def test_one_python_forward_a_compile_and_no_graph_tier(make, how,
                                                        monkeypatch):
    """``TrainStep`` and ``hybridize()`` trace a net the same way, once a
    compile: its Python ``forward`` runs one time, and nothing of the
    graph pipeline (no ``graph`` fallback, no ``graph_pass``) is recorded."""
    from mxnet_tpu import telemetry

    net, x, loss = make()
    net.initialize()
    net(nd.array(x, dtype=x.dtype))          # settle deferred shapes
    forwards = []
    hybrid_forward = type(net).hybrid_forward

    def counted(self, *args, **kwargs):
        forwards.append(self)
        return hybrid_forward(self, *args, **kwargs)

    monkeypatch.setattr(type(net), "hybrid_forward", counted)
    telemetry.reset()
    if how == "train_step":
        step = TrainStep(net, loss, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.01})
        y = np.zeros((x.shape[0],), "int32")
        assert np.isfinite(float(step(x, y)))
        assert np.isfinite(float(step(x, y)))
    else:
        net.hybridize()
        for _ in range(2):                    # same signature: no retrace
            with autograd.record():
                out = net(nd.array(x, dtype=x.dtype))
            out = out[0] if isinstance(out, tuple) else out
            out.backward()
            assert np.isfinite(out.asnumpy()).all()
    assert forwards == [net]
    assert not [e for e in telemetry.compile_events()
                if e["kind"].startswith("graph")]


def test_hybridized_remat_llama_checkpoints_its_layers():
    """Under ``hybridize()`` as under ``TrainStep``, a
    ``LlamaConfig(remat=True)`` layer is a ``jax.checkpoint``: the cached
    op's jaxpr holds one a layer, and no warning says it has no effect."""
    import warnings

    import jax

    net, ids, _ = _toy_llama(remat=True)
    net.initialize()
    net(nd.array(ids, dtype="int32"))
    net.hybridize()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        net(nd.array(ids, dtype="int32"))
    (jitted, params_list, _), = net._cached_graph.values()
    jaxpr = jax.make_jaxpr(jitted)(
        [p.data()._get() for p in params_list], jax.random.PRNGKey(0), ids)
    assert str(jaxpr).count("remat2[") >= 2
    apply_fn, params = functionalize(net)
    fused = jax.make_jaxpr(apply_fn)(params, jax.random.PRNGKey(0), ids)
    assert str(fused).count("remat2[") >= 2
