"""Pipeline parallelism: GPipe + 1F1B schedules, Gluon TrainStep entry,
and composition with dp/fsdp/tp (VERDICT r4 item 7; net-new vs the
reference — MXNet 1.x has no pipeline parallelism)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import mxnet_tpu as mx
from mxnet_tpu.parallel.pipeline_parallel import (pipeline_apply,
                                                  stack_stage_params)


def _mesh(n, axes=("pp",), shape=None):
    devs = jax.devices()[:n]
    arr = np.array(devs).reshape(shape or (n,))
    return Mesh(arr, axes)


def _stage_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])


def _mk_stages(rs, S, D):
    return [{"w": jnp.asarray(rs.randn(D, D).astype("f") * 0.5),
             "b": jnp.asarray(rs.randn(D).astype("f") * 0.1)}
            for _ in range(S)]


@pytest.mark.parametrize("S,M", [(2, 2), (4, 4), (4, 2), (2, 6)])
@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pipeline_schedule_grads_match_sequential(S, M, schedule):
    """Forward AND all gradients (stage params + input) of both schedules
    match the sequential composition exactly — the 1F1B backward is a
    hand-written custom_vjp, so this is its correctness oracle."""
    D = 8
    rs = np.random.RandomState(0)
    mesh = _mesh(S)
    per = _mk_stages(rs, S, D)
    stacked = stack_stage_params(per)
    B = 12 if M == 6 else 8
    x = jnp.asarray(rs.randn(B, D).astype("f"))

    def loss(st, xx):
        y = pipeline_apply(_stage_fn, st, xx, mesh, M, schedule=schedule)
        return (y * y).sum(), y

    def loss_seq(pl, xx):
        h = xx
        for i in range(S):
            h = _stage_fn(pl[i], h)
        return (h * h).sum(), h

    # one compiled program a schedule for the output and both gradients (the
    # pipeline's three used to be traced and compiled one after the other,
    # 20 to 40 s a case), and one for the pipeline outside any gradient,
    # which for 1F1B is another function than its custom_vjp's forward
    def both(fn, *args):
        (_, y), grads = jax.jit(jax.value_and_grad(
            fn, (0, 1), has_aux=True))(*args)
        return y, grads

    y, (g, gx) = both(loss, stacked, x)
    ref, (g_seq, gx_seq) = both(loss_seq, per, x)
    plain = jax.jit(lambda st, xx: loss(st, xx)[1])(stacked, x)
    assert float(jnp.abs(y - ref).max()) < 1e-5
    assert float(jnp.abs(plain - ref).max()) < 1e-5
    for k in ("w", "b"):
        seq = jnp.stack([g_seq[i][k] for i in range(S)])
        assert float(jnp.abs(g[k] - seq).max()) < 1e-4, k
    assert float(jnp.abs(gx - gx_seq).max()) < 1e-4


def _lm_loss(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)


def _make_llama(cfg_over=None):
    from mxnet_tpu.gluon.model_zoo.language import llama

    cfg = dict(vocab_size=64, hidden_size=32, num_layers=4, num_heads=4,
               num_kv_heads=2, intermediate_size=48, max_seq_len=32)
    cfg.update(cfg_over or {})
    net = llama.LlamaForCausalLM(llama.LlamaConfig(**cfg))
    net.initialize(ctx=mx.cpu())
    net(mx.nd.zeros((1, 8), dtype="int32"))
    return net


def _suffix(name):
    return name.split("_", 1)[1]


def test_llama_trainstep_pp_matches_dp_trajectory():
    """The VERDICT item-7 'done' bar: a real Llama proxy trains through
    TrainStep(pipeline=...) with pp=2 on the 8-device mesh and follows
    the plain-dp trajectory exactly, for BOTH schedules."""
    from mxnet_tpu.parallel.data_parallel import TrainStep

    rs = np.random.RandomState(0)
    ids = rs.randint(0, 64, (8, 8)).astype("int32")
    lbl = rs.randint(0, 64, (8, 8)).astype("int32")

    net1 = _make_llama()
    step1 = TrainStep(net1, _lm_loss, optimizer="adam",
                      optimizer_params={"learning_rate": 1e-3},
                      mesh=_mesh(8, ("dp",)), batch_axes=("dp",))
    w0 = {_suffix(k): np.asarray(v) for k, v in step1.params.items()}
    ref = [float(np.asarray(step1(ids, lbl))) for _ in range(3)]
    assert ref[-1] < ref[0]  # it actually trains

    for sched in ("gpipe", "1f1b"):
        net2 = _make_llama()
        for name, p in net2.collect_params().items():
            p.set_data(mx.nd.array(w0[_suffix(name)]))
        step2 = TrainStep(
            net2, _lm_loss, optimizer="adam",
            optimizer_params={"learning_rate": 1e-3},
            mesh=_mesh(8, ("dp", "pp"), (4, 2)), batch_axes=("dp",),
            pipeline={"num_microbatches": 2, "schedule": sched})
        losses = [float(np.asarray(step2(ids, lbl))) for _ in range(3)]
        np.testing.assert_allclose(losses, ref, rtol=1e-4, atol=1e-5,
                                   err_msg=sched)


def test_llama_trainstep_pp_heterogeneous_ends_and_remat():
    """Heterogeneous decomposition (embed -> trunk stages -> norm+head)
    with per-stage remat under the GPipe schedule trains and matches the
    non-remat trajectory (remat is numerics-preserving)."""
    from mxnet_tpu.parallel.data_parallel import TrainStep

    rs = np.random.RandomState(1)
    ids = rs.randint(0, 64, (4, 8)).astype("int32")
    lbl = rs.randint(0, 64, (4, 8)).astype("int32")
    net = _make_llama()
    w0 = {_suffix(k): p.data().asnumpy()
          for k, p in net.collect_params().items()}
    losses = {}
    for remat in (False, True):
        n = _make_llama()
        for name, p in n.collect_params().items():
            p.set_data(mx.nd.array(w0[_suffix(name)]))
        step = TrainStep(
            n, _lm_loss, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            mesh=_mesh(4, ("dp", "pp"), (2, 2)), batch_axes=("dp",),
            pipeline={"num_microbatches": 2, "remat_stage": remat})
        losses[remat] = [float(np.asarray(step(ids, lbl)))
                         for _ in range(2)]
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)


def test_llama_trainstep_four_axis_mesh_composition():
    """pp composes with dp/fsdp/tp in ONE jit: 4-axis mesh, fsdp param
    sharding on the non-trunk params, megatron tp specs on the head, pp
    over the trunk — the step runs and the loss is finite/decreasing."""
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.parallel.data_parallel import TrainStep, fsdp_specs
    from mxnet_tpu.parallel.functional import functionalize

    net = _make_llama()
    mesh = _mesh(8, ("dp", "fsdp", "pp", "tp"), (2, 2, 2, 1))
    _, params0 = functionalize(net)
    specs = fsdp_specs(params0, mesh)
    for name in params0:
        if name.endswith("lm_head_weight"):
            # mxtpu: noqa[MXT060] tests the raw param_sharding dict entry
            specs[name] = P("tp", None)  # column-parallel head
    step = TrainStep(
        net, _lm_loss, optimizer="adam",
        optimizer_params={"learning_rate": 1e-3},
        mesh=mesh, param_sharding=specs, batch_axes=("dp", "fsdp"),
        pipeline={"num_microbatches": 2, "schedule": "1f1b"})
    rs = np.random.RandomState(2)
    ids = rs.randint(0, 64, (8, 8)).astype("int32")
    lbl = rs.randint(0, 64, (8, 8)).astype("int32")
    l0 = float(np.asarray(step(ids, lbl)))
    l1 = float(np.asarray(step(ids, lbl)))
    assert np.isfinite([l0, l1]).all()
    assert l1 < l0


def test_pipeline_rejects_bad_configs():
    mesh = _mesh(4)
    rs = np.random.RandomState(0)
    stacked = stack_stage_params(_mk_stages(rs, 3, 4))  # wrong S
    x = jnp.zeros((4, 4), "f")
    with pytest.raises(mx.MXNetError):
        pipeline_apply(_stage_fn, stacked, x, mesh, 2)
    good = stack_stage_params(_mk_stages(rs, 4, 4))
    with pytest.raises(mx.MXNetError):
        pipeline_apply(_stage_fn, good, x, mesh, 3)  # batch % M
    with pytest.raises(mx.MXNetError):
        pipeline_apply(_stage_fn, good, x, mesh, 2, schedule="2f2b")


def test_bert_trainstep_pp_matches_dp_trajectory():
    """BERT (the second LLM family) trains through TrainStep(pipeline=...)
    with pp=2 matching the plain-dp trajectory (dropout=0 for exact
    parity — pipelined and monolithic traces draw different masks)."""
    from mxnet_tpu.gluon.model_zoo.language import bert
    from mxnet_tpu.parallel.data_parallel import TrainStep

    def make_net():
        net = bert.BertForPretraining(bert.BertConfig(
            vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
            intermediate_size=64, max_position=32, dropout=0.0))
        net.initialize(ctx=mx.cpu())
        net(mx.nd.zeros((1, 8), dtype="int32"))
        return net

    def loss_fn(outs, labels):
        mlm, nsp = outs
        mlm_labels, nsp_labels = labels[:, :-1], labels[:, -1]
        logp = jax.nn.log_softmax(mlm, axis=-1)
        mlm_l = -jnp.take_along_axis(logp, mlm_labels[..., None], axis=-1)
        nsp_logp = jax.nn.log_softmax(nsp, axis=-1)
        nsp_l = -jnp.take_along_axis(nsp_logp, nsp_labels[:, None],
                                     axis=-1)
        return jnp.mean(mlm_l) + jnp.mean(nsp_l)

    rs = np.random.RandomState(0)
    ids = rs.randint(0, 128, (8, 8)).astype("int32")
    labels = np.concatenate(
        [rs.randint(0, 128, (8, 8)), rs.randint(0, 2, (8, 1))],
        axis=1).astype("int32")

    net1 = make_net()
    step1 = TrainStep(net1, loss_fn, optimizer="adam",
                      optimizer_params={"learning_rate": 1e-3},
                      mesh=_mesh(8, ("dp",)), batch_axes=("dp",))
    w0 = [p.data().asnumpy() for p in net1.collect_params().values()]
    ref = [float(np.asarray(step1(ids, labels))) for _ in range(3)]

    net2 = make_net()
    for p, v in zip(net2.collect_params().values(), w0):
        p.set_data(mx.nd.array(v))
    step2 = TrainStep(net2, loss_fn, optimizer="adam",
                      optimizer_params={"learning_rate": 1e-3},
                      mesh=_mesh(8, ("dp", "pp"), (4, 2)),
                      batch_axes=("dp",),
                      pipeline={"num_microbatches": 2,
                                "schedule": "1f1b"})
    losses = [float(np.asarray(step2(ids, labels))) for _ in range(3)]
    np.testing.assert_allclose(losses, ref, rtol=1e-4, atol=1e-5)


def test_llama_moe_trainstep_pp_trains():
    """The MoE Llama variant (homogeneous MoE decoder layers) also trains
    through TrainStep(pipeline=...) — loss finite and decreasing."""
    from mxnet_tpu.parallel.data_parallel import TrainStep

    net = _make_llama({"num_experts": 2, "intermediate_size": 32})
    step = TrainStep(net, _lm_loss, optimizer="adam",
                     optimizer_params={"learning_rate": 1e-3},
                     mesh=_mesh(4, ("dp", "pp"), (2, 2)),
                     batch_axes=("dp",),
                     pipeline={"num_microbatches": 2,
                               "schedule": "1f1b"})
    rs = np.random.RandomState(3)
    ids = rs.randint(0, 64, (4, 8)).astype("int32")
    lbl = rs.randint(0, 64, (4, 8)).astype("int32")
    l0 = float(np.asarray(step(ids, lbl)))
    for _ in range(3):
        l1 = float(np.asarray(step(ids, lbl)))
    assert np.isfinite(l1) and l1 < l0
