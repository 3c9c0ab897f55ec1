"""BERT pretraining (Devlin et al. 2018, arXiv:1810.04805) in plain
``jax.numpy``, float32: forward, MLM + NSP loss and gradients.

Written from the paper: token + position embeddings, LayerNorm, N
post-norm encoder layers (multi-head self-attention, exact-erf GELU feed
forward), a tanh pooler over the first token, an MLM head (dense, GELU,
LayerNorm, decoder) and an NSP head.  No kernel, no cache; attention
materialises the scores.  Departures, which follow the system's model so
that both compute the same function (``config.json`` lists them): no
segment embedding is added (the training path feeds ids only), the MLM
decoder has its own weight (not tied to the word embedding), and the MLM
loss is the mean over every position, not over 15% masked ones.

A dense weight is (out, in) and multiplies as ``x @ w.T``.  Imports
nothing of the program.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def param_shapes(cfg):
    """Leaf name -> (shape, kind), in the order the paper builds them.
    kind: 'normal' (N(0, 0.02), the paper's initialiser), 'zeros', 'ones'."""
    h, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    out = {}

    def dense(name, n_out, n_in):
        out[name + ".w"] = ((n_out, n_in), "normal")
        out[name + ".b"] = ((n_out,), "zeros")

    def norm(name):
        out[name + ".scale"] = ((h,), "ones")
        out[name + ".bias"] = ((h,), "zeros")

    out["embed.word"] = ((v, h), "normal")
    out["embed.position"] = ((cfg["max_position_embeddings"], h), "normal")
    norm("embed.norm")
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}."
        for name in ("attn.q", "attn.k", "attn.v", "attn.o"):
            dense(p + name, h, h)
        norm(p + "attn_norm")
        dense(p + "ffn.in", ff, h)
        dense(p + "ffn.out", h, ff)
        norm(p + "ffn_norm")
    dense("pooler", h, h)
    dense("mlm.dense", h, h)
    norm("mlm.norm")
    dense("mlm.decoder", v, h)
    dense("nsp", 2, h)
    return out


def init_params(cfg, seed):
    """Every leaf from ``seed`` in one jitted call, float32, on the default
    device."""
    shapes = param_shapes(cfg)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(shapes.items()):
            if kind == "normal":
                out[name] = 0.02 * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            else:
                out[name] = jnp.full(shape, float(kind == "ones"), jnp.float32)
        return out

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def forward(cfg, ops, p, ids):
    """(rows, seq) ids -> MLM logits (rows, seq, vocab), NSP logits (rows, 2)."""
    rnd = ops.round
    eps = cfg["layer_norm_eps"]
    heads = cfg["num_attention_heads"]
    rows, seq = ids.shape

    def dense(x, name):
        return rnd(x) @ rnd(p[name + ".w"]).T + p[name + ".b"]

    def norm(x, name):
        return _layer_norm(x, p[name + ".scale"], p[name + ".bias"], eps)

    h = p["embed.word"][ids] + p["embed.position"][:seq]
    h = norm(h, "embed.norm")
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layer{i}."

        def split(x):
            return x.reshape(rows, seq, heads, -1).transpose(0, 2, 1, 3)

        q, k, v = (split(dense(h, pre + n)) for n in ("attn.q", "attn.k", "attn.v"))
        scores = rnd(q) @ rnd(k).transpose(0, 1, 3, 2) / math.sqrt(q.shape[-1])
        ctx = rnd(jax.nn.softmax(scores, axis=-1)) @ rnd(v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(rows, seq, -1)
        h = norm(h + dense(ctx, pre + "attn.o"), pre + "attn_norm")
        ff = dense(_gelu(dense(h, pre + "ffn.in")), pre + "ffn.out")
        h = norm(h + ff, pre + "ffn_norm")
    pooled = jnp.tanh(dense(h[:, 0], "pooler"))
    mlm = dense(norm(_gelu(dense(h, "mlm.dense")), "mlm.norm"), "mlm.decoder")
    return mlm, dense(pooled, "nsp")


def loss_fn(cfg, ops, p, ids, labels):
    """Mean MLM cross-entropy over every position plus mean NSP
    cross-entropy.  ``labels`` is (rows, seq + 1): the token labels, then the
    sentence label."""
    mlm, nsp = forward(cfg, ops, p, ids)

    def xent(logits, target):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]

    return jnp.mean(xent(mlm, labels[:, :-1])) + jnp.mean(xent(nsp, labels[:, -1]))


@partial(jax.jit, static_argnames=("cfg_items", "precision", "blocks"))
def _loss_and_grads(p, ids, labels, *, cfg_items, precision, blocks):
    from chipbench.harness.precision import ops as make_ops

    cfg, ops = dict(cfg_items), make_ops(precision)
    # rows are independent, so the batch mean is the mean over equal blocks
    # of rows: one block's activations live at a time.  Block j takes rows
    # j, j + blocks, ...: where the rows are split over chips, every block
    # then holds rows of every chip
    ids = ids.reshape(-1, blocks, ids.shape[-1]).swapaxes(0, 1)
    labels = labels.reshape(-1, blocks, labels.shape[-1]).swapaxes(0, 1)

    def one(carry, block):
        loss, grads = jax.value_and_grad(partial(loss_fn, cfg, ops))(p, *block)
        return jax.tree_util.tree_map(jnp.add, carry, (loss, grads)), None

    zero = (jnp.zeros(()), jax.tree_util.tree_map(jnp.zeros_like, p))
    with jax.default_matmul_precision(ops.matmul):
        (loss, grads), _ = jax.lax.scan(one, zero, (ids, labels))
    return loss / blocks, jax.tree_util.tree_map(lambda g: g / blocks, grads)


def loss_and_grads(cfg, precision, p, batch, block_rows):
    """Loss and gradients of one batch ``(ids, labels)``, computed
    ``block_rows`` rows at a time."""
    ids, labels = batch
    rows = ids.shape[0]
    blocks = max(1, rows // block_rows)
    if rows % blocks:
        raise ValueError(f"{rows} rows do not split into blocks of {block_rows}")
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float))))
    return _loss_and_grads(p, jnp.asarray(ids), jnp.asarray(labels),
                           cfg_items=items, precision=precision, blocks=blocks)
