"""How the ``phi4_mini_flash`` configuration meets the program: the model-zoo
decoder built from ``config.json``'s keys (a window-attention layer, a
state-space block that hands its scan output on, a full-attention layer that
hands its K and V on, a gated memory unit and a cross-attention layer, by the
published indices; differential attention, LayerNorm, tied embeddings), the
loss handed to the step, the host batches of next-token training, and which
reference leaf is which parameter of the net."""
from __future__ import annotations

import numpy as np

# what the configuration's kernels require, for the per-layer readers
from chipbench.configs.phi4_mini_flash import counts


def build_net(cfg, ctx):
    """An initialised ``LlamaForCausalLM`` on ``ctx``: the published layers
    ``layers_first .. layers_first + num_hidden_layers - 1``, each of the kind
    ``counts.layer_kinds`` gives it, no positions anywhere."""
    from mxnet_tpu.gluon.model_zoo.language import llama

    channels, state, taps, rank = counts.ssm_sizes(cfg)
    net = llama.LlamaForCausalLM(llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rms_eps=cfg["layer_norm_eps"], norm="layer",
        tie_embeddings=cfg["tie_word_embeddings"], remat=True,
        attention_types=counts.layer_kinds(cfg),
        attention_window=cfg["sliding_window"], rope_attention_types=(),
        differential=True, attention_bias=True,
        first_layer_index=cfg["layers_first"], ssm_state_size=state,
        ssm_conv_size=taps, ssm_expand=channels // cfg["hidden_size"],
        ssm_dt_rank=rank))
    # every shape is given, so nothing waits for a first forward (an eager
    # one at a short length aborts XLA:TPU, PERF.md section 6, PR 21); the
    # net's own draws are thrown away when the driver sets every leaf
    net.initialize(ctx=ctx)
    return net


def leaf_names(cfg, net):
    """Reference leaf -> name of the net's parameter, by construction
    order; the shapes are checked leaf by leaf."""
    from chipbench.configs.phi4_mini_flash.reference import param_shapes

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
