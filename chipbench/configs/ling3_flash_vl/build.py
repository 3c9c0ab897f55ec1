"""How the ``ling3_flash_vl`` configuration meets the program: the model-zoo
decoder built from ``config.json``'s keys (Kimi-delta-attention and
latent-attention layers by the published indices, the heads and experts
held here), the loss handed to the step, the host batches of next-token
training, and which reference leaf is which parameter of the net."""
from __future__ import annotations

import numpy as np

# what the configuration's kernels require, for the per-layer readers
from chipbench.configs.ling3_flash_vl import counts


def select_bias(cfg):
    """``assumed.expert_bias`` over the router's width: ``value`` on the
    experts of the shares it lists, 0 on the others."""
    pattern, held = cfg["assumed"]["expert_bias"], cfg["num_experts"]
    bias = np.zeros(cfg["router_width"], np.float32)
    for share in pattern["shares"]:
        bias[share * held:(share + 1) * held] = pattern["value"]
    return bias


def build_net(cfg, ctx):
    """An initialised ``LlamaForCausalLM`` on ``ctx``: the published layers
    ``0 .. num_hidden_layers - 1``, latent attention where ``(i + 1) %
    layer_group_size == 0`` and Kimi delta attention elsewhere, dense before
    ``first_k_dense_replace`` and an expert layer over the experts held
    after, the heads held ``heads_first ..`` of the published count, every
    router's selection bias set from ``config.json``."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.language import llama

    layers, published = cfg["num_hidden_layers"], cfg["published"]
    net = llama.LlamaForCausalLM(llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=layers, num_heads=published["num_attention_heads"],
        num_kv_heads=published["num_attention_heads"],
        attention_heads_held=(cfg["heads_first"],
                              cfg["num_attention_heads"]),
        head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        rope_base=float(cfg["rope_theta"]),
        max_seq_len=cfg["max_position_embeddings"],
        rms_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], remat=True,
        attention_types=counts.layer_kinds(cfg),
        attention_gate=cfg["gated_attention_proj_granularity_type"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_interleave=cfg["rope_interleave"],
        kda_conv_size=cfg["short_conv_kernel_size"],
        kda_lower_bound=cfg["kda_lower_bound"],
        num_dense_layers=cfg["first_k_dense_replace"],
        num_experts=cfg["router_width"], moe_capacity_factor=None,
        moe_top_k=cfg["num_experts_per_tok"],
        moe_renormalize=cfg["norm_topk_prob"], moe_renorm_eps=1e-20,
        moe_score=cfg["score_function"],
        moe_route_scale=cfg["routed_scaling_factor"],
        moe_select_bias=cfg["moe_router_enable_expert_bias"],
        moe_groups=(cfg["n_group"], cfg["topk_group"]),
        moe_experts_held=(cfg["experts_first"], cfg["num_experts"]),
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_intermediate_size=cfg[
            "moe_shared_expert_intermediate_size"]))
    # every shape is given, so nothing waits for a first forward (an eager
    # one at a short length aborts XLA:TPU, PERF.md section 6, PR 21).  The
    # net's own draws are thrown away when the driver sets every leaf;
    # zeros in their place were tried and are no cheaper (an eager program a
    # shape either way: PERF.md section 6, PR 38, review round)
    net.initialize(ctx=ctx)
    bias = mx.nd.array(select_bias(cfg), ctx=ctx)
    for name, param in net.collect_params().items():
        if name.endswith("select_bias"):
            param.set_data(bias)
    return net


def leaf_names(cfg, net):
    """Reference leaf -> name of the net's parameter, by construction
    order; the shapes are checked leaf by leaf.  The selection bias is no
    leaf: no gradient reaches it and the optimizer does not hold it."""
    from chipbench.configs.ling3_flash_vl.reference import param_shapes

    params = net.collect_params()
    trained = [n for n, p in params.items() if p.grad_req != "null"]
    leaves = param_shapes(cfg)
    if len(trained) != len(leaves):
        raise ValueError(f"{len(trained)} parameters for {len(leaves)} "
                         "leaves")
    out = dict(zip(leaves, trained))
    for leaf, name in out.items():
        if tuple(params[name].shape) != leaves[leaf][0]:
            raise ValueError(f"{leaf} {leaves[leaf][0]} is not {name} "
                             f"{tuple(params[name].shape)}")
    return out


def step_loss(logits, labels):
    """Next-token loss as a training script hands it to ``TrainStep``:
    ``labels`` (samples, L) int32 holds each position's next token; a
    sample's loss is the mean cross-entropy over its L positions."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(ce, axis=-1)


def make_batch(cfg, cell, rng):
    """One host batch: ``L + 1`` tokens a sample, uniform over the slice's
    ids; ids are the first ``L`` and labels the last ``L``, both (samples,
    L) int32."""
    drawn = rng.integers(0, cfg["vocab_size"],
                         (cell["batch"], cell["seq"] + 1), dtype=np.int32)
    return drawn[:, :-1], drawn[:, 1:]


def train_flops_per_sample(cfg, cell):
    """Operations one sample's forward and backward passes require."""
    return counts.train_flops_per_sample(cfg, cell["seq"])
