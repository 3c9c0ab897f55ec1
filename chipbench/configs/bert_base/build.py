"""How the ``bert_base`` configuration meets the program: the model-zoo
net built from ``config.json``, the loss handed to the step, the host
batches, and which reference leaf is which parameter of the net."""
from __future__ import annotations

import numpy as np


def build_net(cfg, ctx):
    """An initialised ``BertForPretraining`` on ``ctx``, shapes settled."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.language import bert

    net = bert.BertForPretraining(bert.BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        dropout=cfg["hidden_dropout_prob"],
        layer_norm_eps=cfg["layer_norm_eps"]))
    net.initialize(ctx=ctx)
    net(mx.nd.zeros((1, 8), dtype="int32", ctx=ctx))
    return net


def leaf_names(cfg, net):
    """Reference leaf -> name of the net's parameter, by construction order.
    The net's one parameter with no reference leaf is the segment
    embedding, which the ids-only training path never reads."""
    from chipbench.configs.bert_base.reference import param_shapes

    unread = net.bert.token_type_embed.weight.name
    names = [n for n in net.collect_params() if n != unread]
    leaves = list(param_shapes(cfg))
    if len(names) != len(leaves):
        raise ValueError(f"{len(names)} parameters for {len(leaves)} leaves")
    return dict(zip(leaves, names))


def step_loss(outs, labels):
    """MLM + NSP cross-entropy as a pretraining script hands it to
    ``TrainStep``: the mean over every position plus the mean over rows."""
    import jax
    import jax.numpy as jnp

    mlm, nsp = outs
    logp = jax.nn.log_softmax(mlm, axis=-1)
    mlm_l = -jnp.take_along_axis(logp, labels[:, :-1, None], axis=-1)
    nsp_logp = jax.nn.log_softmax(nsp, axis=-1)
    nsp_l = -jnp.take_along_axis(nsp_logp, labels[:, -1:], axis=-1)
    return jnp.mean(mlm_l) + jnp.mean(nsp_l)


def make_batch(cfg, cell, rng):
    """One host batch: int32 ids (rows, seq) and labels (rows, seq + 1), the
    last column the sentence label."""
    rows, seq, vocab = cell["batch"], cell["seq"], cfg["vocab_size"]
    ids = rng.integers(0, vocab, (rows, seq), dtype=np.int32)
    labels = np.concatenate(
        [rng.integers(0, vocab, (rows, seq), dtype=np.int32),
         rng.integers(0, 2, (rows, 1), dtype=np.int32)], axis=1)
    return ids, labels


def train_flops_per_sample(cfg, cell):
    """Operations one sample's forward and backward passes require."""
    from chipbench.harness import counts

    return counts.bert_train_flops_per_sequence(cfg, cell["seq"])
