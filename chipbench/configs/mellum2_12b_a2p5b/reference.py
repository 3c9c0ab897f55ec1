"""One chip's share of Mellum2-12B-A2.5B's training step on packed documents,
in plain ``jax.numpy``, float32: forward, next-token loss and gradients.

Written from the model's ``config.json`` (``model_type: mellum``) and, where
it has no key, from what ``config.json``'s ``assumed`` lists.  ``x0 =
E[ids]``, then the layers, a final RMSNorm and an untied output head.  A row
is documents packed end to end; ``s`` are its segment ids (a document a run
of equal ids) and ``pos[i] = i - (index of the first token of i's
document)``.  Layer ``i``, of the kind ``layer_types[i]``:

- ``a = RMSNorm(x)``; ``q, k, v = a Wq, a Wk, a Wv`` (32 query heads over 4
  key-value heads of 128); ``q = RMSNorm_128(q)``, the same for ``k``; both
  turned by RoPE at ``pos`` with the kind's inverse frequencies and
  magnitude (``rope_parameters``: the sliding layers' plain ``theta^(-2n /
  128)``, the full layers' YaRN blend with cos and sin scaled by
  ``attention_factor``); ``o = softmax(q k^T / sqrt(128) + M) v`` with each
  key-value head serving 8 query heads, ``M`` letting query ``i`` see key
  ``j`` iff ``j <= i`` and ``s[i] == s[j]`` and, on a sliding layer, ``i -
  W < j``; ``x = x + o Wo``;
- ``h = RMSNorm(x)``; ``p = softmax(h Wr)`` over all the router's outputs;
  ``S`` the 8 largest; ``g_e = p_e / (sum over S of p)``; ``x = x + sum over
  e in S held here of g_e Wd_e (silu(Wg_e h) * (Wu_e h))``.

The loss is the mean over a sample's ``L`` positions of the cross-entropy of
the next token.

No kernel, no cache, no sorting.  Departures, each so that the program and
this file compute the same function (``config.json`` lists them): the
experts held here are ``experts_first ..`` of the router's width, taken by
plain indexing, and what the absent ones would add is left out; logits and
loss are over the vocabulary slice.  Blocks that change no arithmetic, so
that the real size fits one chip: a sample at a time, attention
``block_rows`` query rows at a time, experts one at a time, the head
``block_rows`` rows at a time, each under ``jax.checkpoint`` (``block_rows``
0: a whole sample at once).

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


def layer_kinds(cfg):
    """The published kinds of the layers that are here."""
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


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


def yarn_range(given, head):
    """``(low, high)`` of YaRN's ramp among the head's rotated dimensions:
    the dimension that turns ``beta_fast`` times over the original context
    and the one that turns ``beta_slow`` times, rounded outwards when
    ``truncate``, held to the head."""
    def dim_of(rotations):
        return (head * math.log(given["original_max_position_embeddings"]
                                / (rotations * 2 * math.pi))
                / (2 * math.log(given["rope_theta"])))

    low, high = dim_of(given["beta_fast"]), dim_of(given["beta_slow"])
    if given.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    return max(low, 0), min(high, head - 1)


def rope_of(cfg, kind):
    """``(inv_freq (head / 2,), magnitude)`` of a layer of ``kind`` from the
    published ``rope_parameters``: ``theta^(-2n / head)``, or YaRN's blend
    of those divided by ``factor`` and those themselves by a ramp from
    ``low`` to ``high`` (``transformers``' ``_compute_yarn_parameters``),
    cos and sin then scaled by ``attention_factor``."""
    given, head = cfg["rope_parameters"][kind], cfg["head_dim"]
    n = jnp.arange(head // 2, dtype=jnp.float32)
    extra = 1.0 / float(given["rope_theta"]) ** (2.0 * n / head)
    if given["rope_type"] == "default":
        return extra, 1.0
    if given["rope_type"] != "yarn":
        raise ValueError(f"rope_type {given['rope_type']!r} is not written")
    given = dict(given, truncate=cfg["assumed"]["yarn_truncate"])
    low, high = yarn_range(given, head)
    if low == high:
        high += 0.001
    ramp = jnp.clip((n - low) / (high - low), 0.0, 1.0)
    inter = extra / given["factor"]
    return inter * ramp + extra * (1.0 - ramp), given["attention_factor"]


def _rope(x, pos, inv_freq, magnitude):
    """Rotary embedding, half-split convention: x (..., rows, head) at
    positions ``pos`` (rows,)."""
    half = x.shape[-1] // 2
    angles = pos.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles) * magnitude, jnp.sin(angles) * magnitude
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def positions(segments):
    """``pos[i] = i - (index of the first token of i's document)`` for one
    row's segment ids (L,): the first index of a document is the largest
    index up to ``i`` at which the id changed."""
    index = jnp.arange(segments.shape[0])
    changed = jnp.concatenate([jnp.ones((1,), bool),
                               segments[1:] != segments[:-1]])
    first = jax.lax.associative_scan(jnp.maximum,
                                     jnp.where(changed, index, 0))
    return index - first


def visible(q_rows, k_rows, q_seg, k_seg, window):
    """Boolean (len(q_rows), len(k_rows)): may the query at each row see
    the key at each row?  Causal, inside one document (equal segment ids);
    with ``window`` the last ``window`` keys up to the query's own alone."""
    seen = (k_rows[None, :] <= q_rows[:, None]) \
        & (k_seg[None, :] == q_seg[:, None])
    if window:
        seen &= k_rows[None, :] > q_rows[:, None] - window
    return seen


def _block(rows, block_rows):
    """The largest block of at most ``block_rows`` rows that divides
    ``rows`` (``rows`` itself for 0)."""
    step = min(block_rows or rows, rows)
    while rows % step:
        step -= 1
    return step


def _attention(rnd, q, k, v, segments, window, block_rows):
    """q (heads, rows, head), k and v (kv heads, rows, head) of one sample:
    masked softmax attention, a block of query rows at a time, every
    key-value head serving ``heads / kv heads`` query heads."""
    heads, rows, hd = q.shape
    group = heads // k.shape[0]
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    step = _block(rows, block_rows)

    @jax.checkpoint
    def block_of_rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, step, axis=1)
        scores = jnp.einsum("hqd,hkd->hqk", rnd(qb), rnd(k)) / math.sqrt(hd)
        seen = visible(start + jnp.arange(step), jnp.arange(rows),
                       jax.lax.dynamic_slice_in_dim(segments, start, step),
                       segments, window)
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", rnd(jax.nn.softmax(scores, -1)),
                          rnd(v))

    out = jax.lax.map(block_of_rows, jnp.arange(0, rows, step))
    return out.transpose(1, 0, 2, 3).reshape(heads, rows, hd)


def routed_experts(cfg, rnd, h, p, first, count):
    """The part that experts ``first .. first + count - 1`` add for tokens
    h (rows, hidden), their stacked matrices in ``p``: router over its
    whole width in float32, the 8 largest renormalised, then each of them
    on every token, weighed by its gate (0 where it was not chosen)."""
    probs = jax.nn.softmax(rnd(h) @ rnd(p["moe.router"]), axis=-1)
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

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), (
        first + jnp.arange(count), p["moe.gate"], p["moe.up"],
        p["moe.down"]))
    return y


def forward(cfg, ops, p, ids, segments, block_rows=0):
    """ids and segments (L,) of one sample -> the final norm's output (L,
    hidden)."""
    rnd = ops.round
    eps = cfg["rms_norm_eps"]
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    rows = ids.shape[0]
    pos = positions(segments)

    def heads_of(x, n):
        return x.reshape(rows, n, hd).transpose(1, 0, 2)

    @partial(jax.checkpoint, static_argnums=(2,))
    def layer(x, lp, kind):
        turn = partial(_rope, pos=pos, **dict(zip(
            ("inv_freq", "magnitude"), rope_of(cfg, kind))))
        a = _rms_norm(x, lp["attn_norm"], eps)
        q = heads_of(rnd(a) @ rnd(lp["attn.q"]).T, nq)
        k = heads_of(rnd(a) @ rnd(lp["attn.k"]).T, nkv)
        v = heads_of(rnd(a) @ rnd(lp["attn.v"]).T, nkv)
        q = turn(_rms_norm(q, lp["attn.q_norm"], eps))
        k = turn(_rms_norm(k, lp["attn.k_norm"], eps))
        o = _attention(rnd, q, k, v, segments, cfg["sliding_window"]
                       if kind == "sliding_attention" else 0, block_rows)
        o = o.transpose(1, 0, 2).reshape(rows, nq * hd)
        x = x + rnd(o) @ rnd(lp["attn.o"]).T
        h = _rms_norm(x, lp["ffn_norm"], eps)
        return x + routed_experts(cfg, rnd, h, lp, cfg["experts_first"],
                                  cfg["num_experts"])

    x = p["embed"][ids]
    for i, kind in enumerate(layer_kinds(cfg)):
        pre = f"layer{i}."
        x = layer(x, {k[len(pre):]: v for k, v in p.items()
                      if k.startswith(pre)}, kind)
    return _rms_norm(x, p["final_norm"], eps)


def logits(cfg, ops, p, ids, segments):
    """One sample's logits (L, vocabulary slice), whole: for the tests."""
    x = forward(cfg, ops, p, ids, segments)
    return ops.round(x) @ ops.round(p["head"]).T


def loss_fn(cfg, ops, block_rows, p, ids, segments, labels):
    """One sample: ``ids``, ``segments`` and ``labels`` (L,), the labels the
    next tokens.  Mean cross-entropy over the L positions, the head a block
    of rows at a time."""
    rnd = ops.round
    x = forward(cfg, ops, p, ids, segments, block_rows)
    rows = x.shape[0]
    step = _block(rows, block_rows)

    @jax.checkpoint
    def block_of_rows(x, target):
        logp = jax.nn.log_softmax(rnd(x) @ rnd(p["head"]).T, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, target[:, None], axis=-1))

    sums = jax.lax.map(lambda xt: block_of_rows(*xt), (
        x.reshape(rows // step, step, -1), labels.reshape(-1, step)))
    return jnp.sum(sums) / rows


@lru_cache(maxsize=4)
def _compiled(cfg_json, precision, block_rows):
    """The jitted loss and gradients of a batch for one configuration (its
    JSON text, so that it is a key), one precision and one block size."""
    from chipbench.harness.precision import ops as make_ops

    cfg, ops = json.loads(cfg_json), make_ops(precision)

    # samples are independent (routing is a token's own), so the batch mean
    # is the mean over samples: one sample's activations live at a time
    @jax.jit
    def loss_and_grads(p, ids, segments, labels):
        def one(carry, sample):
            loss, grads = jax.value_and_grad(
                partial(loss_fn, cfg, ops, block_rows))(p, *sample)
            return jax.tree_util.tree_map(jnp.add, carry, (loss, grads)), None

        zero = (jnp.zeros(()), jax.tree_util.tree_map(jnp.zeros_like, p))
        with jax.default_matmul_precision(ops.matmul):
            (loss, grads), _ = jax.lax.scan(one, zero,
                                            (ids, segments, labels))
        n = ids.shape[0]
        return loss / n, jax.tree_util.tree_map(lambda g: g / n, grads)

    return loss_and_grads


def loss_and_grads(cfg, precision, p, batch, block_rows):
    """Loss and gradients of one batch ``((ids, segment ids), labels)``, each
    (samples, L), a sample at a time, attention and the head ``block_rows``
    rows at a time."""
    (ids, segments), labels = batch
    return _compiled(json.dumps(cfg, sort_keys=True), precision,
                     int(block_rows))(
        p, jnp.asarray(ids), jnp.asarray(segments), jnp.asarray(labels))
