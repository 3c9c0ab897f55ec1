"""One chip's share of SDAR-30B-A3B-Chat trained by block diffusion, in
plain ``jax.numpy``, float32: forward, loss and gradients.

Written from the model's ``config.json`` (``sdar_moe``), the SDAR paper
(arXiv:2510.06303) and the block-diffusion training of Arriola et al. 2025
(arXiv:2503.09573): an embedding, N pre-norm decoder layers and a final
RMSNorm and output head.  A layer is

- ``a = RMSNorm(x)``; ``q, k, v = a Wq, a Wk, a Wv`` (32 query heads over 4
  key-value heads of 128); ``q = RoPE(RMSNorm_128(q), pos)``, the same for
  ``k``; ``o = softmax(q k^T / sqrt(128) + M) v`` with each key-value head
  serving 8 query heads; ``x = x + o Wo``;
- ``h = RMSNorm(x)``; ``p = softmax(h Wr)`` over all the router's outputs;
  ``S`` the 8 largest; ``g_e = p_e / sum_S p``; ``x = x + sum over e in S
  held here of g_e Wd_e (silu(Wg_e h) * (Wu_e h))``.

The network sees rows ``[xt ; x0]``: a noised copy of the sample's ``L``
tokens, then the clean copy, both at positions ``0 .. L-1``.  ``M`` is the
block-diffusion mask: with ``b(i) = (i mod L) // B``, a noised query sees
the noised keys of its own block and the clean keys of earlier blocks; a
clean query sees the clean keys of its own and earlier blocks.  Logits are
taken over the noised half, and the loss is the mean over the sample's
``L`` positions of ``weight_i * CE(logits_i, x0_i)`` with ``weight_i =
[xt_i is the mask token] / t_b(i)``, which the batch carries.

No kernel, no cache, no sorting.  Departures, each so that the program and
this file compute the same function (``config.json`` lists them): the
experts held here are ``experts_first ..`` of the router's width, taken by
plain indexing, and what the absent ones would add is left out; logits and
loss are over the vocabulary slice; no auxiliary loss.  Blocks that change
no arithmetic, so that the real size fits one chip: a sample at a time,
attention a block of query rows at a time, experts one at a time, each
under ``jax.checkpoint``.

A dense weight is (out, in) and multiplies as ``x @ w.T``; the router is
(hidden, width) and the experts' matrices are stacked (held, in, out), as
the program keeps them.  Imports nothing of the program.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

QUERY_ROWS = 512   # query rows of one attention block


def param_shapes(cfg):
    """Leaf name -> (shape, kind), in the order the model builds them.
    kind: 'normal' (N(0, 0.02)), 'ones', 'shares' (a router: N(0, 0.02)
    columns for one share's experts, the same for every share)."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, width = cfg["num_experts"], cfg["moe_intermediate_size"]
    out = {"embed": ((cfg["vocab_size"], h), "normal")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}."
        out[p + "attn_norm"] = ((h,), "ones")
        out[p + "attn.q"] = ((nq * hd, h), "normal")
        out[p + "attn.k"] = ((nkv * hd, h), "normal")
        out[p + "attn.v"] = ((nkv * hd, h), "normal")
        out[p + "attn.o"] = ((h, nq * hd), "normal")
        out[p + "attn.q_norm"] = ((hd,), "ones")
        out[p + "attn.k_norm"] = ((hd,), "ones")
        out[p + "ffn_norm"] = ((h,), "ones")
        out[p + "moe.router"] = ((h, cfg["router_width"]), "shares")
        out[p + "moe.gate"] = ((held, h, width), "normal")
        out[p + "moe.up"] = ((held, h, width), "normal")
        out[p + "moe.down"] = ((held, width, h), "normal")
    out["final_norm"] = ((h,), "ones")
    out["head"] = ((cfg["vocab_size"], h), "normal")
    return out


def init_params(cfg, seed):
    """Every leaf from ``seed`` in one jitted call, float32, on the default
    device.

    A router's column ``e`` is that of expert ``e mod held``: every share of
    the deployment has the same columns, so a token's largest logits are the
    copies of one column, one on each share, and each share is routed one
    pair a token whatever the seed and the batch (``config.json``,
    ``assumed.router``, says why)."""
    shapes, held = param_shapes(cfg), cfg["num_experts"]

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(shapes.items()):
            if kind == "normal":
                out[name] = 0.02 * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            elif kind == "shares":
                out[name] = jnp.tile(0.02 * jax.random.normal(
                    jax.random.fold_in(key, i), (shape[0], held),
                    jnp.float32), (1, shape[1] // held))
            else:
                out[name] = jnp.ones(shape, jnp.float32)
        return out

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, base):
    """Rotary embedding, half-split convention: x (..., rows, head) at
    integer positions ``pos`` (rows,)."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block_diffusion_mask(q_rows, length, block):
    """Boolean (len(q_rows), 2 * length): may the query at row ``q_rows[i]``
    of ``[noised ; clean]`` see the key at each row?"""
    k_rows = jnp.arange(2 * length)
    qb, kb = (q_rows % length) // block, (k_rows % length) // block
    q_noised, k_noised = q_rows < length, k_rows < length
    own_noised = q_noised[:, None] & k_noised[None, :] \
        & (qb[:, None] == kb[None, :])
    clean_before = q_noised[:, None] & ~k_noised[None, :] \
        & (kb[None, :] < qb[:, None])
    clean_upto = ~q_noised[:, None] & ~k_noised[None, :] \
        & (kb[None, :] <= qb[:, None])
    return own_noised | clean_before | clean_upto


def _attention(rnd, q, k, v, length, block):
    """q (heads, rows, head), k and v (kv heads, rows, head) of one sample,
    rows = 2 * length: masked softmax attention, a block of query rows at a
    time, every key-value head serving ``heads / kv heads`` query heads."""
    heads, rows, hd = q.shape
    group = heads // k.shape[0]
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    step = min(QUERY_ROWS, rows)

    @jax.checkpoint
    def block_of_rows(start):
        q_rows = start + jnp.arange(step)
        qb = jax.lax.dynamic_slice_in_dim(q, start, step, axis=1)
        scores = jnp.einsum("hqd,hkd->hqk", rnd(qb), rnd(k)) / math.sqrt(hd)
        seen = block_diffusion_mask(q_rows, length, block)
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", rnd(jax.nn.softmax(scores, -1)),
                          rnd(v))

    out = jax.lax.map(block_of_rows, jnp.arange(0, rows, step))
    return out.transpose(1, 0, 2, 3).reshape(heads, rows, hd)


def _experts(cfg, rnd, h, p, pre):
    """The held experts' part of the expert layer for tokens h (rows,
    hidden): router over its whole width in float32, the 8 largest
    renormalised, then each held expert on every token, weighed by its gate
    (0 where it was not chosen).  Returns that and the experts chosen
    (rows, 8)."""
    probs = jax.nn.softmax(rnd(h) @ rnd(p[pre + "moe.router"]), axis=-1)
    gates, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)

    @jax.checkpoint
    def one(h, e, w_gate, w_up, w_down):
        weight = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        hidden = jax.nn.silu(rnd(h) @ rnd(w_gate)) * (rnd(h) @ rnd(w_up))
        return weight[:, None] * (rnd(hidden) @ rnd(w_down))

    def add(y, expert):
        return y + one(h, *expert), None

    held = cfg["num_experts"]
    y, _ = jax.lax.scan(add, jnp.zeros_like(h), (
        cfg["experts_first"] + jnp.arange(held), p[pre + "moe.gate"],
        p[pre + "moe.up"], p[pre + "moe.down"]))
    return y, chosen


def forward(cfg, ops, p, ids, block, with_routes=False):
    """ids (2 * L,) of one sample, ``[xt ; x0]`` -> logits (L, vocab) of the
    noised half; ``with_routes`` the experts every layer chose too (layers,
    2 * L, 8)."""
    rnd = ops.round
    eps, base = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    rows = ids.shape[0]
    length = rows // 2
    pos = jnp.arange(rows) % length

    def heads_of(x, n):
        return x.reshape(rows, n, hd).transpose(1, 0, 2)

    @jax.checkpoint
    def layer(x, lp):
        a = _rms_norm(x, lp["attn_norm"], eps)
        q = heads_of(rnd(a) @ rnd(lp["attn.q"]).T, nq)
        k = heads_of(rnd(a) @ rnd(lp["attn.k"]).T, nkv)
        v = heads_of(rnd(a) @ rnd(lp["attn.v"]).T, nkv)
        q = _rope(_rms_norm(q, lp["attn.q_norm"], eps), pos, base)
        k = _rope(_rms_norm(k, lp["attn.k_norm"], eps), pos, base)
        o = _attention(rnd, q, k, v, length, block)
        o = o.transpose(1, 0, 2).reshape(rows, nq * hd)
        x = x + rnd(o) @ rnd(lp["attn.o"]).T
        h = _rms_norm(x, lp["ffn_norm"], eps)
        y, chosen = _experts(cfg, rnd, h, lp, "")
        return x + y, chosen

    x = p["embed"][ids]
    routes = []
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layer{i}."
        x, chosen = layer(x, {k[len(pre):]: v for k, v in p.items()
                              if k.startswith(pre)})
        routes.append(chosen)
    x = _rms_norm(x[:length], p["final_norm"], eps)
    logits = rnd(x) @ rnd(p["head"]).T
    return (logits, jnp.stack(routes)) if with_routes else logits


def loss_fn(cfg, ops, block, p, ids, labels):
    """One sample: ``ids`` (2L,), ``labels`` (2, L) int32, the clean tokens
    and the bits of the float32 weights.  Mean over the L positions of
    weight * cross-entropy."""
    logits = forward(cfg, ops, p, ids, block)
    target = labels[0]
    weight = jax.lax.bitcast_convert_type(labels[1], jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]
    return jnp.mean(weight * ce)


@partial(jax.jit, static_argnames=("cfg_items", "precision", "block"))
def _loss_and_grads(p, ids, labels, *, cfg_items, precision, block):
    from chipbench.harness.precision import ops as make_ops

    cfg, ops = dict(cfg_items), make_ops(precision)

    # samples are independent (routing is a token's own), so the batch mean
    # is the mean over samples: one sample's activations live at a time
    def one(carry, sample):
        loss, grads = jax.value_and_grad(
            partial(loss_fn, cfg, ops, block))(p, *sample)
        return jax.tree_util.tree_map(jnp.add, carry, (loss, grads)), None

    zero = (jnp.zeros(()), jax.tree_util.tree_map(jnp.zeros_like, p))
    with jax.default_matmul_precision(ops.matmul):
        (loss, grads), _ = jax.lax.scan(one, zero, (ids, labels))
    n = ids.shape[0]
    return loss / n, jax.tree_util.tree_map(lambda g: g / n, grads)


def loss_and_grads(cfg, precision, p, batch, block_rows):
    """Loss and gradients of one batch ``(ids (samples, 2L), labels
    (samples, 2, L))``, a sample at a time (``block_rows`` is not needed:
    a sample is the block)."""
    ids, labels = batch
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float))))
    return _loss_and_grads(p, jnp.asarray(ids), jnp.asarray(labels),
                           cfg_items=items, precision=precision,
                           block=cfg["assumed"]["block_length"])


def routes(cfg, precision, p, ids):
    """The experts each layer's router chose for every token of a batch
    ``ids (samples, 2L)``: (samples, layers, 2L, 8), with every matrix
    product's operands rounded to ``precision`` (``flips.py`` counts the
    tokens whose set of experts differs between two precisions)."""
    from chipbench.harness.precision import ops as make_ops

    ops, block = make_ops(precision), cfg["assumed"]["block_length"]
    with jax.default_matmul_precision(ops.matmul):
        return jax.lax.map(
            lambda row: forward(cfg, ops, p, row, block, True)[1],
            jnp.asarray(ids))
