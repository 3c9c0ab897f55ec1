"""BERT as Gluon HybridBlocks (BASELINE config #2).

Reference placement: BERT lived in GluonNLP (external repo) on top of this
framework's ops — `src/operator/contrib/transformer.cc` provided the fused
interleaved matmuls it used (SURVEY.md §3.2).  Here the encoder rides the
same flash-attention kernel as Llama; BERT-base dims are the default.
"""
from __future__ import annotations

import math

from ....profiler import (SCOPE_ATTENTION_PROJ, SCOPE_EMBED, SCOPE_FFN,
                          SCOPE_HEAD, SCOPE_NORM)
from ...block import HybridBlock
from ... import nn

__all__ = ["BertConfig", "BertModel", "BertForPretraining", "bert_base",
           "bert_large", "bert_tiny"]


class BertConfig:
    def __init__(self, vocab_size=30522, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=3072, max_position=512,
                 type_vocab_size=2, dropout=0.1, layer_norm_eps=1e-12):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position = max_position
        self.type_vocab_size = type_vocab_size
        self.dropout = dropout
        self.layer_norm_eps = layer_norm_eps
        self.head_dim = hidden_size // num_heads


class BertSelfAttention(HybridBlock):
    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        d = cfg.hidden_size
        self.query = nn.Dense(d, flatten=False, in_units=d)
        self.key = nn.Dense(d, flatten=False, in_units=d)
        self.value = nn.Dense(d, flatten=False, in_units=d)
        self.out = nn.Dense(d, flatten=False, in_units=d)
        self.dropout = nn.Dropout(cfg.dropout)

    def hybrid_forward(self, F, x):
        cfg = self._cfg
        b, l = x.shape[0], x.shape[1]
        hd = cfg.head_dim

        def heads(t):
            return t.reshape((b, l, cfg.num_heads, hd)).transpose((0, 2, 1, 3))

        import jax

        # the parts are named at the call sites (profiler.py): the attention
        # op between the projections names its own kernels and backward
        with jax.named_scope(SCOPE_ATTENTION_PROJ):
            q, k, v = (heads(self.query(x)), heads(self.key(x)),
                       heads(self.value(x)))
        o = F.flash_attention(q, k, v, causal=False,
                              sm_scale=1.0 / math.sqrt(hd))
        with jax.named_scope(SCOPE_ATTENTION_PROJ):
            o = o.transpose((0, 2, 1, 3)).reshape((b, l, cfg.hidden_size))
            return self.dropout(self.out(o))


class BertLayer(HybridBlock):
    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.attention = BertSelfAttention(cfg)
        self.attn_norm = nn.LayerNorm(epsilon=cfg.layer_norm_eps)
        self.intermediate = nn.Dense(cfg.intermediate_size, flatten=False,
                                     in_units=cfg.hidden_size)
        self.output = nn.Dense(cfg.hidden_size, flatten=False,
                               in_units=cfg.intermediate_size)
        self.out_norm = nn.LayerNorm(epsilon=cfg.layer_norm_eps)
        self.dropout = nn.Dropout(cfg.dropout)

    def hybrid_forward(self, F, x):
        import jax

        a = x + self.attention(x)
        with jax.named_scope(SCOPE_NORM):
            x = self.attn_norm(a)
        with jax.named_scope(SCOPE_FFN):
            h = self.dropout(self.output(F.gelu(self.intermediate(x))))
        h = x + h
        with jax.named_scope(SCOPE_NORM):
            return self.out_norm(h)


class BertModel(HybridBlock):
    def __init__(self, cfg=None, **kwargs):
        super().__init__(**kwargs)
        cfg = cfg or BertConfig()
        self._cfg = cfg
        self.word_embed = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.position_embed = nn.Embedding(cfg.max_position, cfg.hidden_size)
        self.token_type_embed = nn.Embedding(cfg.type_vocab_size,
                                             cfg.hidden_size)
        self.embed_norm = nn.LayerNorm(epsilon=cfg.layer_norm_eps)
        self.embed_dropout = nn.Dropout(cfg.dropout)
        self.encoder = nn.HybridSequential(prefix="")
        for _ in range(cfg.num_layers):
            self.encoder.add(BertLayer(cfg))
        self.pooler = nn.Dense(cfg.hidden_size, activation="tanh",
                               flatten=False, in_units=cfg.hidden_size)

    def hybrid_forward(self, F, input_ids, token_types=None):
        import jax

        b, l = input_ids.shape[0], input_ids.shape[1]
        with jax.named_scope(SCOPE_EMBED):
            pos = F.arange(0, l, dtype="int32")
            h = self.word_embed(input_ids)
            positions = self.position_embed(pos)
            h = h + positions.reshape((1, l, -1))
            if token_types is not None:
                h = h + self.token_type_embed(token_types)
        with jax.named_scope(SCOPE_NORM):
            h = self.embed_norm(h)
        h = self.encoder(self.embed_dropout(h))
        with jax.named_scope(SCOPE_HEAD):
            pooled = self.pooler(h.slice_axis(axis=1, begin=0, end=1)
                                 .reshape((b, -1)))
        return h, pooled


class BertForPretraining(HybridBlock):
    """MLM + NSP heads over BertModel (GluonNLP BERTForPretrain shape)."""

    def __init__(self, cfg=None, **kwargs):
        super().__init__(**kwargs)
        cfg = cfg or BertConfig()
        self._cfg = cfg
        self.bert = BertModel(cfg)
        self.mlm_dense = nn.Dense(cfg.hidden_size, flatten=False,
                                  in_units=cfg.hidden_size)
        self.mlm_norm = nn.LayerNorm(epsilon=cfg.layer_norm_eps)
        self.mlm_decoder = nn.Dense(cfg.vocab_size, flatten=False,
                                    in_units=cfg.hidden_size)
        self.nsp = nn.Dense(2, flatten=False, in_units=cfg.hidden_size)

    def hybrid_forward(self, F, input_ids, token_types=None):
        import jax

        seq, pooled = self.bert(input_ids, token_types)
        with jax.named_scope(SCOPE_HEAD):   # its norm is the head's
            mlm = self.mlm_decoder(self.mlm_norm(F.gelu(self.mlm_dense(seq))))
            return mlm, self.nsp(pooled)

    def pipeline_decompose(self, n_stages, train_mode=True):
        """Split BertForPretraining for TrainStep(pipeline=...): embeddings
        (pre) -> n_stages uniform encoder stages -> pooler + MLM/NSP heads
        (post).  Same contract as LlamaForCausalLM.pipeline_decompose.

        Notes: token_types input is not threaded (the bench/pretrain path
        passes ids only).  Dropout keys differ from the monolithic trace:
        the pipelined trunk folds (stage, layer) into the key — distinct
        masks per layer, shared across microbatches (the 1F1B recompute
        must reproduce forward masks exactly) — so use dropout=0 when
        bit-matching trajectories against the plain path.
        """
        from ....base import MXNetError
        from ....ops.registry import OP_TABLE
        from ....parallel.functional import functionalize

        cfg = self._cfg
        L = cfg.num_layers
        if L % n_stages:
            raise MXNetError(
                f"num_layers {L} not divisible by pipeline stages {n_stages}")
        bert = self.bert
        f = lambda blk: functionalize(blk, train_mode=train_mode)
        we, we_p = f(bert.word_embed)
        pe, pe_p = f(bert.position_embed)
        en, en_p = f(bert.embed_norm)
        do, do_p = f(bert.embed_dropout)
        lay0 = bert.encoder[0]
        lay, lay0_p = f(lay0)
        po, po_p = f(bert.pooler)
        md, md_p = f(self.mlm_dense)
        mn, mn_p = f(self.mlm_norm)
        mdec, mdec_p = f(self.mlm_decoder)
        nsp, nsp_p = f(self.nsp)
        gelu = OP_TABLE["gelu"].fn

        # construction-order mapping: identical blocks declare parameters in
        # the same order, while auto-generated name prefixes (dense7_, ...)
        # differ per instance — positional zip is the stable correspondence
        lay0_order = list(lay0.collect_params())
        layer_names = []
        for i in range(L):
            blk_order = list(bert.encoder[i].collect_params())
            layer_names.append(dict(zip(lay0_order, blk_order,
                                        strict=True)))

        def pre_fn(psub, rng, ids):
            import jax.numpy as jnp

            l = ids.shape[1]
            h = we({k: psub[k] for k in we_p}, rng, ids)
            pos = pe({k: psub[k] for k in pe_p}, rng,
                     jnp.arange(l, dtype=jnp.int32))
            h = h + pos.reshape((1, l, -1))
            h = en({k: psub[k] for k in en_p}, rng, h)
            return do({k: psub[k] for k in do_p}, rng, h)

        def layer_fn(pl, rng, h):
            return lay(pl, rng, h)

        def post_fn(psub, rng, h):
            pooled = po({k: psub[k] for k in po_p}, rng, h[:, 0, :])
            mlm = md({k: psub[k] for k in md_p}, rng, h)
            mlm = mn({k: psub[k] for k in mn_p}, rng, gelu(mlm))
            mlm = mdec({k: psub[k] for k in mdec_p}, rng, mlm)
            return mlm, nsp({k: psub[k] for k in nsp_p}, rng, pooled)

        return {
            "pre_names": list(we_p) + list(pe_p) + list(en_p) + list(do_p),
            "post_names": (list(po_p) + list(md_p) + list(mn_p)
                           + list(mdec_p) + list(nsp_p)),
            "layer_names": layer_names,
            "layer0_names": list(lay0_p),
            "pre_fn": pre_fn,
            "layer_fn": layer_fn,
            "post_fn": post_fn,
        }


def bert_base(**overrides):
    return BertModel(BertConfig(**overrides))


def bert_large(**overrides):
    kw = dict(hidden_size=1024, num_layers=24, num_heads=16,
              intermediate_size=4096)
    kw.update(overrides)
    return BertModel(BertConfig(**kw))


def bert_tiny(**overrides):
    kw = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=2,
              intermediate_size=128, max_position=128)
    kw.update(overrides)
    return BertModel(BertConfig(**kw))


