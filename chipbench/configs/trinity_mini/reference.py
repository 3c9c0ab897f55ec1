"""One chip's share of Trinity-Mini's training step, in plain ``jax.numpy``,
float32: forward, next-token loss and gradients.

Written from the model's ``config.json`` (``afmoe``) and the family's public
modelling code as ``config.json``'s ``assumed`` lists it.  ``x0 =
sqrt(hidden) * E[ids]``, then the layers, a final RMSNorm and an untied
output head.  Layer ``i``:

- ``a = RMSNorm(x)``; ``q, k, v, z = a Wq, a Wk, a Wv, a Wz`` (32 query
  heads over 4 key-value heads of 128; ``z`` as wide as ``q``); ``q =
  RMSNorm_128(q)``, the same for ``k``; on a ``sliding_attention`` layer
  both are turned by RoPE, on a ``full_attention`` layer neither; ``o =
  softmax(q k^T / sqrt(128) + M) v`` with each key-value head serving 8
  query heads, ``M`` causal and, on a sliding layer, key ``j`` seen by
  query ``i`` iff ``i - W < j <= i``; ``x = x + RMSNorm((o * sigmoid(z))
  Wo)``;
- ``h = RMSNorm(x)``.  Dense (``i < num_dense_layers``): ``m = Wd (silu(Wg
  h) * (Wu h))``.  Sparse: ``s = sigmoid(h Wr)`` over all the router's
  outputs; ``S`` the 8 largest of ``s + b``; ``g_e = route_scale * s_e /
  (sum over S of s + 1e-20)``; ``m = shared(h) + sum over e in S held here
  of g_e expert_e(h)``, each a SwiGLU.  ``x = x + RMSNorm(m)``.

The loss is the mean over a sample's ``L`` positions of the cross-entropy of
the next token.

No kernel, no cache, no sorting.  Departures, each so that the program and
this file compute the same function (``config.json`` lists them): the
experts held here are ``experts_first ..`` of the router's width, taken by
plain indexing, and what the absent ones would add is left out; the bias
``b`` is a constant from ``config.json``; logits and loss are over the
vocabulary slice.  Blocks that change no arithmetic, so that the real size
fits one chip: a sample at a time, attention a block of query rows at a
time, experts one at a time, the head a block of rows at a time, each under
``jax.checkpoint``.

A dense weight is (out, in) and multiplies as ``x @ w.T``; the router is
(hidden, width) and the experts' matrices are stacked (held, in, out), as
the program keeps them.  Imports nothing of the program.
"""
from __future__ import annotations

import json
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp

QUERY_ROWS = 512   # query rows of one attention block
HEAD_ROWS = 1024   # rows of one block of the output head
RENORM_EPS = 1e-20


def layer_kinds(cfg):
    """``[(sliding, sparse)]`` of the layers that are here: the first of
    the published ``layer_types``, dense before ``num_dense_layers``."""
    return [(kind == "sliding_attention", i >= cfg["num_dense_layers"])
            for i, kind in enumerate(
                cfg["layer_types"][:cfg["num_hidden_layers"]])]


def expert_bias(cfg):
    """The selection bias over the router's width (``assumed.expert_bias``):
    ``value`` on the experts of the shares listed, 0 elsewhere."""
    pattern = cfg["assumed"]["expert_bias"]
    share = jnp.arange(cfg["router_width"]) // cfg["num_experts"]
    return jnp.where(jnp.isin(share, jnp.asarray(pattern["shares"])),
                     pattern["value"], 0.0).astype(jnp.float32)


def param_shapes(cfg):
    """Leaf name -> (shape, kind), in the order the model builds them.
    kind: 'normal' (N(0, 0.02)), 'ones', 'shares' (a router: N(0, 0.02)
    columns for one share's experts, the same for every share)."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, width = cfg["num_experts"], cfg["moe_intermediate_size"]
    shared = cfg["num_shared_experts"] * width
    out = {"embed": ((cfg["vocab_size"], h), "normal")}
    for i, (_, sparse) in enumerate(layer_kinds(cfg)):
        p = f"layer{i}."
        out[p + "attn_norm"] = ((h,), "ones")
        out[p + "attn.q"] = ((nq * hd, h), "normal")
        out[p + "attn.k"] = ((nkv * hd, h), "normal")
        out[p + "attn.v"] = ((nkv * hd, h), "normal")
        out[p + "attn.o"] = ((h, nq * hd), "normal")
        out[p + "attn.q_norm"] = ((hd,), "ones")
        out[p + "attn.k_norm"] = ((hd,), "ones")
        out[p + "attn.z"] = ((nq * hd, h), "normal")
        out[p + "ffn_norm"] = ((h,), "ones")
        if sparse:
            out[p + "moe.router"] = ((h, cfg["router_width"]), "shares")
            out[p + "moe.gate"] = ((held, h, width), "normal")
            out[p + "moe.up"] = ((held, h, width), "normal")
            out[p + "moe.down"] = ((held, width, h), "normal")
            out[p + "shared.gate"] = ((shared, h), "normal")
            out[p + "shared.up"] = ((shared, h), "normal")
            out[p + "shared.down"] = ((h, shared), "normal")
        else:
            ff = cfg["intermediate_size"]
            out[p + "ffn.gate"] = ((ff, h), "normal")
            out[p + "ffn.up"] = ((ff, h), "normal")
            out[p + "ffn.down"] = ((h, ff), "normal")
        out[p + "attn_out_norm"] = ((h,), "ones")
        out[p + "ffn_out_norm"] = ((h,), "ones")
    out["final_norm"] = ((h,), "ones")
    out["head"] = ((cfg["vocab_size"], h), "normal")
    return out


def init_params(cfg, seed):
    """Every leaf from ``seed`` in one jitted call, float32, on the default
    device.  A router's column ``e`` is that of expert ``e mod held``: every
    share of the deployment has the same columns (``config.json``,
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


def _rope(x, base):
    """Rotary embedding, half-split convention: x (..., rows, head) at
    positions 0 .. rows - 1."""
    rows, half = x.shape[-2], x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(rows, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def visible(q_rows, k_rows, window):
    """Boolean (len(q_rows), len(k_rows)): may the query at each row see
    the key at each row?  Causal; with ``window`` the last ``window`` keys
    up to the query's own alone."""
    seen = k_rows[None, :] <= q_rows[:, None]
    if window:
        seen &= k_rows[None, :] > q_rows[:, None] - window
    return seen


def _attention(rnd, q, k, v, window):
    """q (heads, rows, head), k and v (kv heads, rows, head) of one sample:
    masked softmax attention, a block of query rows at a time, every
    key-value head serving ``heads / kv heads`` query heads."""
    heads, rows, hd = q.shape
    group = heads // k.shape[0]
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    step = min(QUERY_ROWS, rows)
    while rows % step:
        step -= 1

    @jax.checkpoint
    def block_of_rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, step, axis=1)
        scores = jnp.einsum("hqd,hkd->hqk", rnd(qb), rnd(k)) / math.sqrt(hd)
        seen = visible(start + jnp.arange(step), jnp.arange(rows), window)
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", rnd(jax.nn.softmax(scores, -1)),
                          rnd(v))

    out = jax.lax.map(block_of_rows, jnp.arange(0, rows, step))
    return out.transpose(1, 0, 2, 3).reshape(heads, rows, hd)


def _swiglu(rnd, h, w_gate, w_up, w_down):
    """A dense SwiGLU, weights (out, in)."""
    hidden = jax.nn.silu(rnd(h) @ rnd(w_gate).T) * (rnd(h) @ rnd(w_up).T)
    return rnd(hidden) @ rnd(w_down).T


def gates_and_choice(cfg, scores, bias):
    """``(gates, chosen)`` (rows, 8) from the router's scores (rows,
    width): the experts are the largest of ``scores + bias``, the gates
    their scores, renormalised and scaled."""
    _, chosen = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["route_norm"]:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + RENORM_EPS)
    return gates * cfg["route_scale"], chosen


def routed_experts(cfg, rnd, h, p, bias, first, count):
    """The part that experts ``first .. first + count - 1`` add for tokens
    h (rows, hidden), their stacked matrices in ``p``: router over its
    whole width in float32, then each of them on every token, weighed by
    its gate (0 where it was not chosen)."""
    if cfg["score_func"] != "sigmoid":
        raise ValueError(f"score_func {cfg['score_func']!r} is not written")
    scores = jax.nn.sigmoid(rnd(h) @ rnd(p["moe.router"]))
    gates, chosen = gates_and_choice(cfg, scores, bias)

    @jax.checkpoint
    def one(h, e, w_gate, w_up, w_down):
        weight = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        hidden = jax.nn.silu(rnd(h) @ rnd(w_gate)) * (rnd(h) @ rnd(w_up))
        return weight[:, None] * (rnd(hidden) @ rnd(w_down))

    def add(y, expert):
        return y + one(h, *expert), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), (
        first + jnp.arange(count), p["moe.gate"], p["moe.up"],
        p["moe.down"]))
    return y


def shared_expert(rnd, h, p):
    return _swiglu(rnd, h, p["shared.gate"], p["shared.up"], p["shared.down"])


def forward(cfg, ops, p, ids):
    """ids (L,) of one sample -> the final norm's output (L, hidden)."""
    rnd = ops.round
    eps, base = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    rows = ids.shape[0]
    bias = expert_bias(cfg)

    def heads_of(x, n):
        return x.reshape(rows, n, hd).transpose(1, 0, 2)

    @partial(jax.checkpoint, static_argnums=(2, 3))
    def layer(x, lp, sliding, sparse):
        a = _rms_norm(x, lp["attn_norm"], eps)
        q = heads_of(rnd(a) @ rnd(lp["attn.q"]).T, nq)
        k = heads_of(rnd(a) @ rnd(lp["attn.k"]).T, nkv)
        v = heads_of(rnd(a) @ rnd(lp["attn.v"]).T, nkv)
        z = rnd(a) @ rnd(lp["attn.z"]).T
        q = _rms_norm(q, lp["attn.q_norm"], eps)
        k = _rms_norm(k, lp["attn.k_norm"], eps)
        if sliding:
            q, k = _rope(q, base), _rope(k, base)
        o = _attention(rnd, q, k, v, cfg["sliding_window"] if sliding else 0)
        o = o.transpose(1, 0, 2).reshape(rows, nq * hd) * jax.nn.sigmoid(z)
        x = x + _rms_norm(rnd(o) @ rnd(lp["attn.o"]).T, lp["attn_out_norm"],
                          eps)
        h = _rms_norm(x, lp["ffn_norm"], eps)
        if sparse:
            m = shared_expert(rnd, h, lp) + routed_experts(
                cfg, rnd, h, lp, bias, cfg["experts_first"],
                cfg["num_experts"])
        else:
            m = _swiglu(rnd, h, lp["ffn.gate"], lp["ffn.up"], lp["ffn.down"])
        return x + _rms_norm(m, lp["ffn_out_norm"], eps)

    x = p["embed"][ids]
    if cfg["mup_enabled"]:
        x = x * math.sqrt(cfg["hidden_size"])
    for i, (sliding, sparse) in enumerate(layer_kinds(cfg)):
        pre = f"layer{i}."
        x = layer(x, {k[len(pre):]: v for k, v in p.items()
                      if k.startswith(pre)}, sliding, sparse)
    return _rms_norm(x, p["final_norm"], eps)


def loss_fn(cfg, ops, p, ids, labels):
    """One sample: ``ids`` (L,), ``labels`` (L,) the next tokens.  Mean
    cross-entropy over the L positions, the head a block of rows at a
    time."""
    rnd = ops.round
    x = forward(cfg, ops, p, ids)
    rows = x.shape[0]
    step = min(HEAD_ROWS, rows)
    while rows % step:
        step -= 1

    @jax.checkpoint
    def block_of_rows(x, target):
        logp = jax.nn.log_softmax(rnd(x) @ rnd(p["head"]).T, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, target[:, None], axis=-1))

    sums = jax.lax.map(lambda xt: block_of_rows(*xt), (
        x.reshape(rows // step, step, -1), labels.reshape(-1, step)))
    return jnp.sum(sums) / rows


@lru_cache(maxsize=4)
def _compiled(cfg_json, precision):
    """The jitted loss and gradients of a batch for one configuration (its
    JSON text, so that it is a key) and one precision."""
    from chipbench.harness.precision import ops as make_ops

    cfg, ops = json.loads(cfg_json), make_ops(precision)

    # samples are independent (routing is a token's own), so the batch mean
    # is the mean over samples: one sample's activations live at a time
    @jax.jit
    def loss_and_grads(p, ids, labels):
        def one(carry, sample):
            loss, grads = jax.value_and_grad(
                partial(loss_fn, cfg, ops))(p, *sample)
            return jax.tree_util.tree_map(jnp.add, carry, (loss, grads)), None

        zero = (jnp.zeros(()), jax.tree_util.tree_map(jnp.zeros_like, p))
        with jax.default_matmul_precision(ops.matmul):
            (loss, grads), _ = jax.lax.scan(one, zero, (ids, labels))
        n = ids.shape[0]
        return loss / n, jax.tree_util.tree_map(lambda g: g / n, grads)

    return loss_and_grads


def loss_and_grads(cfg, precision, p, batch, block_rows):
    """Loss and gradients of one batch ``(ids (samples, L), labels (samples,
    L))``, a sample at a time (``block_rows`` is not needed: a sample is
    the block)."""
    ids, labels = batch
    return _compiled(json.dumps(cfg, sort_keys=True), precision)(
        p, jnp.asarray(ids), jnp.asarray(labels))
