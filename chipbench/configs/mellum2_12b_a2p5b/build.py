"""How the ``mellum2_12b_a2p5b`` configuration meets the program: the
model-zoo decoder built from ``config.json``'s keys (each layer of its own
kind, RoPE by kind as published), the loss handed to the step, the host
batches of next-token training on packed documents, and which reference
leaf is which parameter of the net."""
from __future__ import annotations

import numpy as np

# what the configuration's kernels require, for the per-layer readers
from chipbench.configs.mellum2_12b_a2p5b import counts

KINDS = {"sliding_attention": "window", "full_attention": "full"}


def build_net(cfg, ctx):
    """An initialised ``LlamaForCausalLM`` on ``ctx``: the first
    ``num_hidden_layers`` of the published ``layer_types``, every layer an
    expert layer over the experts held, the published ``rope_parameters``
    under the program's names for the kinds."""
    from mxnet_tpu.gluon.model_zoo.language import llama

    layers = cfg["num_hidden_layers"]
    rope = {KINDS[kind]: dict(given, truncate=cfg["assumed"]["yarn_truncate"])
            for kind, given in cfg["rope_parameters"].items()}
    net = llama.LlamaForCausalLM(llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=layers, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rms_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], qk_norm=True, remat=True,
        attention_types=[KINDS[k] for k in cfg["layer_types"][:layers]],
        attention_window=cfg["sliding_window"], rope_parameters=rope,
        num_experts=cfg["router_width"], moe_capacity_factor=None,
        moe_top_k=cfg["num_experts_per_tok"],
        moe_renormalize=cfg["norm_topk_prob"],
        moe_experts_held=(cfg["experts_first"], cfg["num_experts"]),
        moe_intermediate_size=cfg["moe_intermediate_size"]))
    # every shape is given, so nothing waits for a first forward (an eager
    # one at a short length aborts XLA:TPU, PERF.md section 6, PR 21)
    net.initialize(ctx=ctx)
    return net


def leaf_names(cfg, net):
    """Reference leaf -> name of the net's parameter, by construction
    order; the shapes are checked leaf by leaf."""
    from chipbench.configs.mellum2_12b_a2p5b.reference import param_shapes

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
    """Next-token loss as a training script hands it to ``TrainStep``:
    ``labels`` (samples, L) int32 holds each position's next token; a
    sample's loss is the mean cross-entropy over its L positions."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(ce, axis=-1)


def make_batch(cfg, cell, rng):
    """One host batch ``((ids, segment ids), labels)``, each (samples, L)
    int32.  ``L + 1`` tokens a sample, uniform over the slice's ids: ids
    are the first ``L`` and labels the last ``L``.  Every sample is the
    cell's ``documents`` packed end to end in an order drawn anew from
    ``rng``; a document's segment id is its place in that order."""
    lengths = np.asarray(cell["documents"])
    if lengths.sum() != cell["seq"]:
        raise ValueError(f"the documents hold {lengths.sum()} tokens, a "
                         f"sample {cell['seq']}")
    drawn = rng.integers(0, cfg["vocab_size"],
                         (cell["batch"], cell["seq"] + 1), dtype=np.int32)
    segments = np.stack([
        np.repeat(np.arange(len(lengths), dtype=np.int32),
                  lengths[rng.permutation(len(lengths))])
        for _ in range(cell["batch"])])
    return (drawn[:, :-1], segments), drawn[:, 1:]


def train_flops_per_sample(cfg, cell):
    """Operations one sample's forward and backward passes require."""
    return counts.train_flops_per_sample(cfg, cell["documents"])
