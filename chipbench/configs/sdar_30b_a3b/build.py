"""How the ``sdar_30b_a3b`` configuration meets the program: the model-zoo
decoder built from ``config.json``'s keys, the loss handed to the step, the
host batches of block diffusion, and which reference leaf is which
parameter of the net."""
from __future__ import annotations

import numpy as np

# what the configuration's kernels require, for the per-layer readers
from chipbench.configs.sdar_30b_a3b import counts


def block_length(cfg):
    return cfg["assumed"]["block_length"]


def build_net(cfg, ctx):
    """An initialised ``LlamaForCausalLM`` on ``ctx`` in the block-diffusion
    training layout, every layer an expert layer over the experts held."""
    from mxnet_tpu.gluon.model_zoo.language import llama

    net = llama.LlamaForCausalLM(llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        rope_base=float(cfg["rope_theta"]),
        max_seq_len=cfg["max_position_embeddings"],
        rms_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], qk_norm=True, remat=True,
        num_experts=cfg["router_width"], moe_capacity_factor=None,
        moe_top_k=cfg["num_experts_per_tok"],
        moe_renormalize=cfg["norm_topk_prob"],
        moe_experts_held=(cfg["experts_first"], cfg["num_experts"]),
        moe_intermediate_size=cfg["moe_intermediate_size"],
        block_diffusion=block_length(cfg)))
    # every shape is given, so nothing waits for a first forward (an eager
    # one at a short length aborts XLA:TPU, PERF.md section 6, PR 21)
    net.initialize(ctx=ctx)
    return net


def leaf_names(cfg, net):
    """Reference leaf -> name of the net's parameter, by construction
    order; the shapes are checked leaf by leaf."""
    from chipbench.configs.sdar_30b_a3b.reference import param_shapes

    params = net.collect_params()
    leaves = param_shapes(cfg)
    if len(params) != len(leaves):
        raise ValueError(f"{len(params)} parameters for {len(leaves)} leaves")
    out = dict(zip(leaves, params))
    for leaf, name in out.items():
        if tuple(params[name].shape) != leaves[leaf][0]:
            raise ValueError(f"{leaf} {leaves[leaf][0]} is not {name} "
                             f"{tuple(params[name].shape)}")
    return out


def step_loss(logits, labels):
    """The block-diffusion loss as a training script hands it to
    ``TrainStep``: ``labels`` (samples, 2, L) int32 holds the clean tokens
    and the bits of the float32 weights ``[masked] / t``; a sample's loss is
    the mean over its L positions of weight * cross-entropy."""
    import jax
    import jax.numpy as jnp

    weight = jax.lax.bitcast_convert_type(labels[:, 1], jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels[:, 0, :, None], axis=-1)[..., 0]
    return jnp.mean(weight * ce, axis=-1)


def make_batch(cfg, cell, rng):
    """One host batch: ids (samples, 2L) int32, ``[xt ; x0]``, and labels
    (samples, 2, L) int32.  Tokens are uniform over the slice's ids below
    the mask token; each block of ``B`` draws ``t ~ U(0, 1)`` and each of its
    tokens becomes the mask token with probability ``t``."""
    samples, length, block = cell["batch"], cell["seq"], block_length(cfg)
    mask_id = cfg["assumed"]["mask_token_id"]
    x0 = rng.integers(0, mask_id, (samples, length), dtype=np.int32)
    # float32 draws are in [0, 1); 1 - them is in (0, 1], so 1 / t is finite
    t = 1.0 - rng.random((samples, length // block), dtype=np.float32)
    t = np.repeat(t, block, axis=1)
    masked = rng.random((samples, length), dtype=np.float32) < t
    xt = np.where(masked, np.int32(mask_id), x0)
    weight = (masked / t).astype(np.float32)
    labels = np.stack([x0, weight.view(np.int32)], axis=1)
    return np.concatenate([xt, x0], axis=1), labels


def train_flops_per_sample(cfg, cell):
    """Operations one sample's forward and backward passes require."""
    return counts.train_flops_per_sample(cfg, cell["seq"], block_length(cfg))
