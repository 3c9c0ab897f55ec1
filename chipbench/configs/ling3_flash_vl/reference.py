"""One chip's share of the language model of Ling-3.0-flash-VL's training
step, in plain ``jax.numpy``, float32: forward, next-token loss and
gradients.

Written from the model's ``config.json`` (``bailing_hybrid``), Kimi Linear
(arXiv:2510.26692) for the delta-attention layers and DeepSeek-V2's
description of latent attention, as ``config.json``'s ``assumed`` lists what
the keys leave open.  ``x0 = E[ids]``, then the layers, a final RMSNorm and
an untied output head.  Layer ``i`` is ``x = x + mixer(RMSNorm(x))``, ``x = x
+ ffn(RMSNorm(x))``, with ``a`` the normed input and ``h`` a head held here:

- **KDA** (every layer but those where ``(i + 1) % layer_group_size == 0``):
  ``q', k', v' = SiLU(conv(a Wq)), SiLU(conv(a Wk)), SiLU(conv(a Wv))``, a
  causal depthwise convolution of 4 taps over time; ``q = l2norm_h(q') /
  sqrt(128)``, ``k = l2norm_h(k')``; ``g_t = kda_lower_bound *
  sigmoid(exp(A_log_h) (a_t Wf + dt_bias))`` a channel, ``alpha_t =
  exp(g_t)``; ``beta_t = sigmoid(a_t Wb)`` a head; the state ``S`` (128 x
  128 a head, zero at the row's start) goes **a row at a time**: ``S_t = (I
  - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t =
  S_t^T q_t``; ``y = [RMSNorm_128(o_t) * sigmoid(a_t Wg)] Wo``.
- **MLA**: ``q_h = a Wq_h`` (128 + 64); ``[c ; kr] = a Wkva`` (512 + 64),
  ``c = RMSNorm_512(c)``; ``[k_h ; v_h] = c Wkvb_h`` (128 + 128); RoPE at
  ``rope_theta`` on ``q_h``'s last 64 and on ``kr`` (pairs of neighbours),
  ``kr`` shared by the heads; ``softmax(([q_nope ; q_rope] . [k_h ; kr]) /
  sqrt(192) + causal) v_h``; ``o_h = o_h * sigmoid(a w_h)``; ``y = o Wo``.
- FFN.  Dense (``i < first_k_dense_replace``): ``Wd (silu(Wg h) * (Wu
  h))``.  Sparse: ``s = sigmoid(h Wr)`` over the router's 512 outputs; on
  ``s + b`` a group of 64 scores the sum of its two largest, the 4 best of
  the 8 groups stay, and ``S`` is the 8 largest of theirs; ``g_e =
  routed_scaling_factor * s_e / (sum over S of s + 1e-20)``; ``m =
  shared(h) + sum over e in S held here of g_e expert_e(h)``, each a SwiGLU.

The loss is the mean over a sample's ``L`` positions of the cross-entropy of
the next token.

No kernel, no chunked form, no cache, no sorting.  Departures, each so that
the program and this file compute the same function (``config.json`` lists
them): the heads held here are ``heads_first ..`` of the published 32 and
the experts ``experts_first ..`` of the router's width, and what the absent
ones would add is left out; the bias ``b`` is a constant from
``config.json``; logits and loss are over the vocabulary slice.  Blocks that
change no arithmetic, so that the real size fits one chip: a sample at a
time, the recurrence's rows in runs of ``STATE_ROWS`` (each run under
``jax.checkpoint``, still a row at a time), attention a block of query rows
at a time, experts one at a time, the dense FFN, the shared expert and the
head a block of rows at a time.

A dense weight is (out, in) and multiplies as ``x @ w.T``; the router is
(hidden, width), the experts' matrices are stacked (held, in, out) and a
convolution's taps are (taps, channels), as the program keeps them.  Imports
nothing of the program.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

QUERY_ROWS = 512   # query rows of one attention block
HEAD_ROWS = 1024   # rows of one block of the output head and of a dense FFN
STATE_ROWS = 64    # rows of one checkpointed run of the recurrence
RENORM_EPS = 1e-20
L2_EPS = 1e-6


def _block(rows, most):
    """The largest block of at most ``most`` rows that divides ``rows``."""
    step = min(most, rows)
    while rows % step:
        step -= 1
    return step


def layer_kinds(cfg):
    """``[(latent, sparse)]`` of the layers that are here: the published
    indices ``0 .. num_hidden_layers - 1``."""
    return [((i + 1) % cfg["layer_group_size"] == 0,
             i >= cfg["first_k_dense_replace"])
            for i in range(cfg["num_hidden_layers"])]


def expert_bias(cfg):
    """The selection bias over the router's width (``assumed.expert_bias``):
    ``value`` on the experts of the shares listed, 0 elsewhere."""
    pattern = cfg["assumed"]["expert_bias"]
    share = jnp.arange(cfg["router_width"]) // cfg["num_experts"]
    return jnp.where(jnp.isin(share, jnp.asarray(pattern["shares"])),
                     pattern["value"], 0.0).astype(jnp.float32)


def param_shapes(cfg):
    """Leaf name -> (shape, kind), in the order the model builds them.
    kind: 'normal' (N(0, 0.02); a router's columns too, each output its
    own), 'ones', 'taps' (U(-1/2, 1/2)), 'a_log' (log U(1, 16)), 'dt_bias'
    (the inverse softplus of a step drawn log-uniformly from 0.001 to
    0.1)."""
    h, hd, heads = (cfg["hidden_size"], cfg["head_dim"],
                    cfg["num_attention_heads"])
    nope, turned, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
    latent, taps = cfg["kv_lora_rank"], cfg["short_conv_kernel_size"]
    held, width = cfg["num_experts"], cfg["moe_intermediate_size"]
    shared = cfg["moe_shared_expert_intermediate_size"]
    out = {"embed": ((cfg["vocab_size"], h), "normal")}
    for i, (is_latent, sparse) in enumerate(layer_kinds(cfg)):
        p = f"layer{i}."
        out[p + "mixer_norm"] = ((h,), "ones")
        if is_latent:
            out[p + "mla.q"] = ((heads * (nope + turned), h), "normal")
            out[p + "mla.kv_a"] = ((latent + turned, h), "normal")
            out[p + "mla.kv_a_norm"] = ((latent,), "ones")
            out[p + "mla.kv_b"] = ((heads * (nope + dv), latent), "normal")
            out[p + "mla.o"] = ((h, heads * dv), "normal")
            out[p + "mla.gate"] = ((heads, h), "normal")
        else:
            for name in ("q", "k", "v"):
                out[p + f"kda.{name}_conv"] = ((taps, heads * hd), "taps")
            out[p + "kda.a_log"] = ((heads,), "a_log")
            out[p + "kda.dt_bias"] = ((heads * hd,), "dt_bias")
            for name in ("q", "k", "v", "f"):
                out[p + f"kda.{name}"] = ((heads * hd, h), "normal")
            out[p + "kda.b"] = ((heads, h), "normal")
            out[p + "kda.gate"] = ((heads * hd, h), "normal")
            out[p + "kda.o_norm"] = ((hd,), "ones")
            out[p + "kda.o"] = ((h, heads * hd), "normal")
        out[p + "ffn_norm"] = ((h,), "ones")
        if sparse:
            out[p + "moe.router"] = ((h, cfg["router_width"]), "normal")
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
    out["final_norm"] = ((h,), "ones")
    out["head"] = ((cfg["vocab_size"], h), "normal")
    return out


def init_params(cfg, seed):
    """Every leaf from ``seed`` in one jitted call, float32, on the default
    device."""
    shapes = param_shapes(cfg)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if kind == "normal":
                out[name] = 0.02 * jax.random.normal(k, shape, jnp.float32)
            elif kind == "taps":
                out[name] = jax.random.uniform(k, shape, jnp.float32,
                                               -0.5, 0.5)
            elif kind == "a_log":
                out[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                                       1.0, 16.0))
            elif kind == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            else:
                out[name] = jnp.ones(shape, jnp.float32)
        return out

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope_of_neighbours(x, base):
    """Rotary embedding over pairs of neighbours ``(x[2n], x[2n + 1])``: x
    (..., rows, width) at positions 0 .. rows - 1, pair ``n`` turned at
    ``base ** (-2n / width)``."""
    rows, half = x.shape[-2], x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(rows, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def short_conv(x, taps):
    """A causal depthwise convolution over time with SiLU: x (rows,
    channels), taps (n, channels); ``y_t = sum_i taps[i] x_{t - (n - 1) +
    i}``, rows before the first as zeros."""
    n, rows = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((n - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(padded[i:i + rows] * taps[i] for i in range(n)))


def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def delta_rule(rnd, q, k, v, g, beta, drop=()):
    """The gated delta rule, a row at a time: q, k, g (heads, rows, K), v
    (heads, rows, V), beta (heads, rows); the state (heads, K, V) starts at
    zero.  The rows go in runs of ``STATE_ROWS`` under ``jax.checkpoint``,
    so that the backward keeps a state a run and not a state a row.
    ``drop`` plants a fault for a test of the check (``faults.py``):
    ``"decay"`` sets ``alpha = 1``, ``"delta"`` leaves ``beta k k^T`` out."""
    heads, rows, kd = k.shape

    def row(s, x):
        q, k, v, g, beta = x
        if "decay" not in drop:
            s = jnp.exp(g)[:, :, None] * s
        seen = jnp.einsum("hk,hkv->hv", rnd(k), rnd(s))
        if "delta" in drop:
            seen = jnp.zeros_like(seen)
        s = s + (beta[:, None] * k)[:, :, None] * (v - seen)[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", rnd(q), rnd(s))

    @jax.checkpoint
    def run(s, xs):
        return jax.lax.scan(row, s, xs)

    size = _block(rows, STATE_ROWS)
    runs = tuple(jnp.moveaxis(x, 1, 0).reshape(
        (rows // size, size) + x.shape[:1] + x.shape[2:])
        for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(run, jnp.zeros((heads, kd, v.shape[-1]),
                                       jnp.float32), runs)
    return jnp.moveaxis(o.reshape((rows,) + o.shape[2:]), 0, 1)


def kda_mixer(cfg, rnd, a, p, drop=()):
    """a (rows, hidden) -> (rows, hidden): the heads held here."""
    rows, hd = a.shape[0], cfg["head_dim"]
    heads = p["kda.a_log"].shape[0]

    def of_heads(x):
        return x.reshape(rows, heads, -1).transpose(1, 0, 2)

    def through_conv(name):
        return of_heads(short_conv(rnd(a) @ rnd(p[f"kda.{name}"]).T,
                                   p[f"kda.{name}_conv"]))

    q = _l2_norm(through_conv("q")) / math.sqrt(hd)
    k, v = _l2_norm(through_conv("k")), through_conv("v")
    f = of_heads(rnd(a) @ rnd(p["kda.f"]).T + p["kda.dt_bias"])
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["kda.a_log"])[:, None, None] * f)
    beta = jax.nn.sigmoid(rnd(a) @ rnd(p["kda.b"]).T).T
    o = delta_rule(rnd, q, k, v, g, beta, drop)
    o = _rms_norm(o, p["kda.o_norm"], cfg["rms_norm_eps"])
    o = o.transpose(1, 0, 2).reshape(rows, heads * hd) * jax.nn.sigmoid(
        rnd(a) @ rnd(p["kda.gate"]).T)
    return rnd(o) @ rnd(p["kda.o"]).T


def _attention(rnd, q, k, v):
    """q, k (heads, rows, 192), v (heads, rows, 128) of one sample: causal
    softmax attention, a block of query rows at a time."""
    heads, rows, width = q.shape
    step = _block(rows, QUERY_ROWS)

    @jax.checkpoint
    def block_of_rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, step, axis=1)
        scores = jnp.einsum("hqd,hkd->hqk", rnd(qb), rnd(k)) \
            / math.sqrt(width)
        seen = jnp.arange(rows)[None, :] <= (start + jnp.arange(step))[:, None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", rnd(jax.nn.softmax(scores, -1)),
                          rnd(v))

    out = jax.lax.map(block_of_rows, jnp.arange(0, rows, step))
    return out.transpose(1, 0, 2, 3).reshape(heads, rows, v.shape[-1])


def mla_mixer(cfg, rnd, a, p):
    """a (rows, hidden) -> (rows, hidden): the heads held here."""
    rows = a.shape[0]
    heads = p["mla.gate"].shape[0]
    nope, turned, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
    latent, base = cfg["kv_lora_rank"], float(cfg["rope_theta"])

    def of_heads(x):
        return x.reshape(rows, heads, -1).transpose(1, 0, 2)

    q = of_heads(rnd(a) @ rnd(p["mla.q"]).T)
    ckr = rnd(a) @ rnd(p["mla.kv_a"]).T
    c = _rms_norm(ckr[:, :latent], p["mla.kv_a_norm"], cfg["rms_norm_eps"])
    kv = of_heads(rnd(c) @ rnd(p["mla.kv_b"]).T)
    kr = rope_of_neighbours(ckr[:, latent:], base)
    q = jnp.concatenate([q[..., :nope],
                         rope_of_neighbours(q[..., nope:], base)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        kr, (heads, rows, turned))], -1)
    o = _attention(rnd, q, k, kv[..., nope:])
    o = o * jax.nn.sigmoid(rnd(a) @ rnd(p["mla.gate"]).T).T[:, :, None]
    return rnd(o.transpose(1, 0, 2).reshape(rows, heads * dv)) \
        @ rnd(p["mla.o"]).T


def _swiglu(rnd, h, w_gate, w_up, w_down):
    """A dense SwiGLU, weights (out, in), a block of rows at a time (the
    float8 control's rounding scales a tensor by its largest entry, so it
    rounds a block by its own)."""
    rows = h.shape[0]
    step = _block(rows, HEAD_ROWS)

    @jax.checkpoint
    def block_of_rows(h):
        hidden = jax.nn.silu(rnd(h) @ rnd(w_gate).T) * (rnd(h) @ rnd(w_up).T)
        return rnd(hidden) @ rnd(w_down).T

    return jax.lax.map(block_of_rows, h.reshape(rows // step, step, -1)) \
        .reshape(rows, -1)


def chosen_in_groups(cfg, biased):
    """The ``num_experts_per_tok`` outputs a token chooses (rows, 8) from
    its biased scores (rows, width): a group scores the sum of its two
    largest, the ``topk_group`` best of ``n_group`` groups stay, the largest
    of theirs are chosen; ties to the lower index."""
    rows, width = biased.shape
    groups = biased.reshape(rows, cfg["n_group"], width // cfg["n_group"])
    score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
    _, kept = jax.lax.top_k(score, cfg["topk_group"])
    stays = jnp.zeros((rows, cfg["n_group"]), bool).at[
        jnp.arange(rows)[:, None], kept].set(True)
    limited = jnp.where(stays[:, :, None], groups, -jnp.inf)
    return jax.lax.top_k(limited.reshape(rows, width),
                         cfg["num_experts_per_tok"])[1]


def gates_and_choice(cfg, scores, bias):
    """``(gates, chosen)`` (rows, 8) from the router's scores (rows,
    width): chosen on ``scores + bias`` inside the best groups, the gates
    their scores, renormalised and scaled."""
    chosen = chosen_in_groups(cfg, scores + bias)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + RENORM_EPS)
    return gates * cfg["routed_scaling_factor"], chosen


def routed_experts(cfg, rnd, h, p, bias, first, count):
    """The part that experts ``first .. first + count - 1`` add for tokens
    h (rows, hidden), their stacked matrices in ``p``: router over its
    whole width in float32, then each of them on every token, weighed by
    its gate (0 where it was not chosen)."""
    if cfg["score_function"] != "sigmoid":
        raise ValueError(f"score_function {cfg['score_function']!r} is not "
                         "written")
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


def forward(cfg, ops, p, ids, drop=()):
    """ids (L,) of one sample -> the final norm's output (L, hidden)."""
    rnd, eps = ops.round, cfg["rms_norm_eps"]
    bias = expert_bias(cfg)

    @partial(jax.checkpoint, static_argnums=(2, 3))
    def layer(x, lp, is_latent, sparse):
        a = _rms_norm(x, lp["mixer_norm"], eps)
        x = x + (mla_mixer(cfg, rnd, a, lp) if is_latent
                 else kda_mixer(cfg, rnd, a, lp, drop))
        h = _rms_norm(x, lp["ffn_norm"], eps)
        if sparse:
            return x + shared_expert(rnd, h, lp) + routed_experts(
                cfg, rnd, h, lp, bias, cfg["experts_first"],
                cfg["num_experts"])
        return x + _swiglu(rnd, h, lp["ffn.gate"], lp["ffn.up"],
                           lp["ffn.down"])

    x = p["embed"][ids]
    for i, (is_latent, sparse) in enumerate(layer_kinds(cfg)):
        pre = f"layer{i}."
        x = layer(x, {k[len(pre):]: v for k, v in p.items()
                      if k.startswith(pre)}, is_latent, sparse)
    return _rms_norm(x, p["final_norm"], eps)


def loss_fn(cfg, ops, p, ids, labels, drop=()):
    """One sample: ``ids`` (L,), ``labels`` (L,) the next tokens.  Mean
    cross-entropy over the L positions, the head a block of rows at a
    time."""
    rnd = ops.round
    x = forward(cfg, ops, p, ids, drop)
    rows = x.shape[0]
    step = _block(rows, HEAD_ROWS)

    @jax.checkpoint
    def block_of_rows(x, target):
        logp = jax.nn.log_softmax(rnd(x) @ rnd(p["head"]).T, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, target[:, None], axis=-1))

    sums = jax.lax.map(lambda xt: block_of_rows(*xt), (
        x.reshape(rows // step, step, -1), labels.reshape(-1, step)))
    return jnp.sum(sums) / rows


def _jitted(cfg, precision):
    """The jitted loss and gradients of a batch for one precision."""
    from chipbench.harness.precision import ops as make_ops

    ops = make_ops(precision)

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
    the block).  The program is built for the call and let go after it: a
    loaded program keeps its scratch (2.3 GiB at the cell's size), and the
    optimizer's step that follows holds six copies of every leaf (12.9 GiB)
    and has no room beside that."""
    ids, labels = batch
    out = _jitted(cfg, precision)(p, jnp.asarray(ids), jnp.asarray(labels))
    return jax.block_until_ready(out)
