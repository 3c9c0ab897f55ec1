"""Operations and bytes an algorithm requires, from shapes alone.  A
multiply-add is two operations; a training step is the forward pass and
twice as much again for the backward pass; recomputation is not counted."""
from __future__ import annotations


def bert_forward_macs_per_token(cfg, seq):
    """Multiply-adds of BERT's forward pass for one token of a sequence of
    ``seq``: four hidden x hidden projections, the two feed-forward
    matrices and the two attention products in every layer, then the MLM
    head's transform and decoder at every position.  The pooler and the NSP
    head (once a sequence) are left out: under a millionth of the rest."""
    h, ff = cfg["hidden_size"], cfg["intermediate_size"]
    layer = 4 * h * h + 2 * h * ff + 2 * seq * h
    return cfg["num_hidden_layers"] * layer + h * h + h * cfg["vocab_size"]


def bert_train_flops_per_sequence(cfg, seq):
    return 3 * 2 * bert_forward_macs_per_token(cfg, seq) * seq


def flash_fwd_flops(batch_heads, seq_q, seq_k, head_dim):
    """QK^T and PV: two products of ``seq_q x seq_k x head_dim`` a head."""
    return 4 * batch_heads * seq_q * seq_k * head_dim


def flash_fwd_bytes(batch_heads, seq_q, seq_k, head_dim, itemsize):
    """q and o (``seq_q`` rows), k and v (``seq_k`` rows) read or written
    once, and the float32 log-sum-exp a query row."""
    return (batch_heads * head_dim * itemsize * 2 * (seq_q + seq_k)
            + batch_heads * seq_q * 4)
