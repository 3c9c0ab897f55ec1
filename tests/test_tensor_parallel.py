"""Megatron-style tensor parallelism over the virtual 8-device mesh
(SURVEY.md §3.3 parallelism upgrade; no MXNet counterpart)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo.language import llama
from mxnet_tpu.parallel import make_mesh, tensor_parallel
from mxnet_tpu.parallel.data_parallel import TrainStep


def _tiny():
    return llama.LlamaForCausalLM(llama.LlamaConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=48, max_seq_len=32))


def _loss_fn(logits, labels):
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)


def test_megatron_specs_shapes():
    from jax.sharding import PartitionSpec as P

    net = _tiny()
    net.initialize()
    net(mx.nd.zeros((1, 8), dtype="int32"))
    params = {k: p.data() for k, p in net.collect_params().items()}
    mesh = make_mesh(tp=2)
    specs = tensor_parallel.megatron_specs(params, mesh)
    for name, spec in specs.items():
        if "q_proj_weight" in name or "gate_proj_weight" in name or \
                name.endswith("lm_head_weight"):
            assert spec == P("tp", None), (name, spec)
        elif "o_proj_weight" in name or "down_proj_weight" in name or \
                "embed_tokens_weight" in name:
            assert spec == P(None, "tp"), (name, spec)
        elif "norm" in name:
            assert spec == P(), (name, spec)
    tensor_parallel.validate_specs(params, specs, mesh)


def test_megatron_specs_indivisible_falls_back():
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(tp=8)
    params = {"x_q_proj_weight": np.zeros((12, 6))}  # 12 % 8 != 0
    specs = tensor_parallel.specs_from_rules(
        params, tensor_parallel.MEGATRON_RULES, mesh)
    assert specs["x_q_proj_weight"] == P()


def test_specs_from_rules_pinned_template():
    """A template without 'tp' pins the spec verbatim (force-replicate)."""
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(tp=2)
    params = {"a_weight": np.zeros((4, 4)), "b_weight": np.zeros((4, 4))}
    specs = tensor_parallel.specs_from_rules(
        params, (("a_weight$", (None, None)), ("b_weight$", ("tp", None))),
        mesh)
    assert specs["a_weight"] == P(None, None)
    assert specs["b_weight"] == P("tp", None)


def test_megatron_specs_requires_axis():
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()), ("x",))  # no 'tp' axis
    with pytest.raises(mx.MXNetError):
        tensor_parallel.megatron_specs({}, mesh)


def test_validate_specs_raises_on_indivisible():
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(tp=8)
    params = {"w": np.zeros((12, 6))}
    with pytest.raises(mx.MXNetError):
        tensor_parallel.validate_specs(params, {"w": P("tp", None)}, mesh)


def _adam_step(tp):
    """A step over the tiny net from seed 0: replicated on one device, or
    over dp 2 x tp 4 under the Megatron rules."""
    mx.random.seed(0)
    np.random.seed(0)
    net = _tiny()
    net.initialize()
    net(mx.nd.zeros((1, 16), dtype="int32"))
    kw = {}
    if tp:
        kw["mesh"] = make_mesh(dp=2, tp=4)
        kw["extra_param_specs"] = tensor_parallel.megatron_specs(
            {k: p.data() for k, p in net.collect_params().items()},
            kw["mesh"])
    return TrainStep(net, _loss_fn, optimizer="adam",
                     optimizer_params={"learning_rate": 1e-3}, **kw)


def test_tp_trainstep_matches_replicated():
    """The TP-sharded train step must produce the same losses and params
    as the replicated one (GSPMD inserts the Megatron collectives)."""
    x = np.random.RandomState(0).randint(0, 64, (4, 16)).astype("int32")
    y = np.random.RandomState(1).randint(0, 64, (4, 16)).astype("int32")

    losses = {}
    final_lm_head = {}
    for mode in ("replicated", "tp"):
        step = _adam_step(tp=mode == "tp")
        if mode == "tp":
            # the q_proj weight must actually be sharded over tp
            qname = [k for k in step.train_params
                     if k.endswith("0_self_attn_q_proj_weight")][0]
            shards = {s.data.shape
                      for s in step.train_params[qname].addressable_shards}
            full = step.train_params[qname].shape
            assert shards == {(full[0] // 4, full[1])}, shards
        ls = [float(np.asarray(step(x, y))) for _ in range(3)]
        losses[mode] = ls
        lm = [k for k in step.train_params if k.endswith("lm_head_weight")][0]
        final_lm_head[mode] = np.asarray(step.train_params[lm])

    np.testing.assert_allclose(losses["replicated"], losses["tp"],
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(final_lm_head["replicated"],
                               final_lm_head["tp"], rtol=2e-3, atol=2e-4)


def test_mesh_step_returns_the_layout_it_was_placed_with():
    """Over dp 2 x tp 4 every leaf of the state comes back from a step in
    the sharding it was placed with: the plan's layout is the layout that
    runs, at step 1 as at step 3, through one executable."""
    import jax

    step = _adam_step(tp=True)

    def layout():
        return {jax.tree_util.keystr(path): leaf.sharding
                for path, leaf in jax.tree_util.tree_leaves_with_path(
                    (step.train_params, step.rest_params,
                     step.opt_state))}

    placed = layout()
    for name, leaf in step.train_params.items():
        assert leaf.sharding == step._param_shard[name]
        assert step.opt_state["m"][name].sharding == leaf.sharding
    ids = np.random.RandomState(0).randint(0, 64, (4, 16)).astype("int32")
    for n in range(3):
        step(ids, ids)
        if n in (0, 2):
            now = layout()
            assert now == placed, {k: (now[k], placed[k])
                                   for k in placed if now[k] != placed[k]}
    assert len(step._compiled) == 1


def test_moe_expert_specs_and_rank_exact_rules():
    """3-D stacked-expert weights shard over ep (not captured by the 2-D
    tp rules); routers replicate."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.parallel import tensor_parallel as tp

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("tp", "ep"))

    class A:
        def __init__(self, shape):
            self.shape = shape

    params = {
        "layers_0_mlp_gate_proj_weight": A((4, 16, 32)),   # MoE stacked
        "layers_0_mlp_router_weight": A((16, 4)),
        "layers_0_self_attn_q_proj_weight": A((32, 16)),   # dense 2-D
    }
    tspecs = tp.megatron_specs(params, mesh)
    # 3-D expert weight NOT tp-sharded by the dense rule
    assert tuple(tspecs["layers_0_mlp_gate_proj_weight"]) == ()
    assert tuple(tspecs["layers_0_self_attn_q_proj_weight"]) == ("tp", None)
    especs = tp.moe_expert_specs(params, mesh)
    assert tuple(especs["layers_0_mlp_gate_proj_weight"]) == \
        ("ep", None, None)
    assert tuple(especs["layers_0_mlp_router_weight"]) == ()
    merged = dict(tspecs)
    merged.update(especs)
    tp.validate_specs({k: v for k, v in params.items()}, merged, mesh)
