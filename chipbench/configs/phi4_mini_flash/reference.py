"""Published layers 15-19 of Phi-4-mini-flash-reasoning's training step over a
slice of its vocabulary, in plain ``jax.numpy``, float32: forward, next-token
loss and gradients.

Written from the model's ``config.json`` (``phi4flash``), the SambaY paper
(arXiv:2507.06607) for the decoder-hybrid-decoder and its gated memory units,
Mamba (arXiv:2312.00752) for the state-space block and Differential
Transformer (arXiv:2410.05258) for the attention, as ``config.json``'s
``assumed`` lists what the keys leave open.  ``x0 = E[ids]``, then the layers,
a final LayerNorm and the logits ``x E^T`` (tied).  Layer ``i`` (published
index) is ``x = x + mixer(LayerNorm(x))``, ``x = x + Wd (silu(Wg h) * (Wu
h))`` with ``h = LayerNorm(x)``, and ``u`` the mixer's normed input:

- **State-space block** (even ``i <= 16``): ``[xs, z] = u Win``; ``xc =
  silu(conv(xs) + b)``, a causal depthwise convolution of 4 taps; ``[r, B_t,
  C_t] = xc Wx``; ``delta = softplus(r Wdt + b_dt)``; ``A = -exp(A_log)``;
  the state ``h`` (channels x 16, zero at the row's start) goes **a row at a
  time**: ``h_t = exp(delta_t A) * h_{t-1} + (delta_t * xc_t) B_t^T``, ``y_t =
  h_t C_t + D * xc_t``; ``(y * silu(z)) Wout``.  Layer 16 hands on ``M = y``.
- **Gated memory unit** (even ``i >= 18``): ``(silu(u W1) * M) W2``.
- **Differential attention** (odd ``i``: a window of 512 up to 15, full at
  17): ``q = u Wq + b`` as 40 heads of 64, ``k``, ``v`` as 20; query pair
  ``p`` reads key-value pair ``g = p // 2``: ``A1 = softmax(q_2p k_2g^T /
  8)``, ``A2 = softmax(q_2p+1 k_2g+1^T / 8)`` under the mask, ``o_p = (A1 -
  lambda A2) [v_2g ; v_2g+1]``, ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 i)``; ``RMSNorm_128(o_p)
  * (1 - lambda_init)``, the pairs side by side through ``Wo + b``.  Layer 17
  hands on its ``k`` and ``v``.
- **Cross attention** (odd ``i >= 19``): ``Wq`` and ``Wo`` alone, over layer
  17's ``k`` and ``v``, causal, its own four vectors, norm and
  ``lambda_init``.

The loss is the mean over a sample's ``L`` positions of the cross-entropy of
the next token.

No kernel, no chunked form, no cache.  Departures (``config.json`` lists
them): logits and loss are over the vocabulary slice.  Blocks that change no
arithmetic, so that the real size fits one chip: a sample at a time, the
recurrence's rows in runs of ``STATE_ROWS`` (each run under
``jax.checkpoint``, still a row at a time; the state is held ``(16,
channels)``, the channels along the lanes), attention a block of query rows
at a time, the SwiGLU and the head a block of rows at a time.

``cfg["drop"]`` (absent in ``config.json``) plants a fault for a test of the
check (``faults.py``): ``"decay"`` sets ``A = 0``, ``"memory_gate"`` leaves
``silu(u W1)`` out of the memory unit, ``"lambda"`` sets ``lambda = 0``,
``"cross_kv"`` makes the cross layer read K and V of its own normed input
through the full layer's weights, ``"memory_after_gate"`` hands on ``y *
silu(z)``.

A dense weight is (out, in) and multiplies as ``x @ w.T``; a convolution's
taps are (taps, channels), as the program keeps them.  Imports nothing of the
program.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from chipbench.configs.phi4_mini_flash.counts import layer_kinds, ssm_sizes

QUERY_ROWS = 512   # query rows of one attention block
HEAD_ROWS = 1024   # rows of one block of the output head and of a SwiGLU
STATE_ROWS = 64    # rows of one checkpointed run of the recurrence


def _block(rows, most):
    """The largest block of at most ``most`` rows that divides ``rows``."""
    step = min(most, rows)
    while rows % step:
        step -= 1
    return step


def lambda_init(cfg, i):
    """Of the layer ``i`` that is here: by its published index."""
    return 0.8 - 0.6 * math.exp(-0.3 * (cfg["layers_first"] + i))


def param_shapes(cfg):
    """Leaf name -> (shape, kind), in the order the model builds them.
    kind: 'normal' (N(0, 0.02)), 'lambda' (N(0, 0.1)), 'ones', 'zeros',
    'taps' (U(-1/2, 1/2)), 'a_log' (log 1 .. log 16 a channel), 'dt_bias'
    (the inverse softplus of a step drawn log-uniformly from 0.001 to
    0.1)."""
    h = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = h // heads
    channels, state, taps, rank = ssm_sizes(cfg)
    out = {"embed": ((cfg["vocab_size"], h), "normal")}

    def norm(name):
        out[name + ".g"] = ((h,), "ones")
        out[name + ".b"] = ((h,), "zeros")

    def dense(name, rows, cols, bias):
        out[name] = ((rows, cols), "normal")
        if bias:
            out[name + "_b"] = ((rows,), "zeros")

    for i, kind in enumerate(layer_kinds(cfg)):
        p = f"layer{i}."
        norm(p + "mixer_norm")
        if kind == "ssm":
            out[p + "ssm.conv"] = ((taps, channels), "taps")
            out[p + "ssm.conv_b"] = ((channels,), "zeros")
            out[p + "ssm.a_log"] = ((channels, state), "a_log")
            out[p + "ssm.d"] = ((channels,), "ones")
            dense(p + "ssm.in", 2 * channels, h, False)
            dense(p + "ssm.x", rank + 2 * state, channels, False)
            dense(p + "ssm.dt", channels, rank, False)
            out[p + "ssm.dt_b"] = ((channels,), "dt_bias")
            dense(p + "ssm.out", h, channels, False)
        elif kind == "gmu":
            dense(p + "gmu.in", channels, h, False)
            dense(p + "gmu.out", h, channels, False)
        else:
            dense(p + "attn.q", heads * hd, h, True)
            if kind != "cross":
                dense(p + "attn.k", kv * hd, h, True)
                dense(p + "attn.v", kv * hd, h, True)
            dense(p + "attn.o", h, heads * hd, True)
            for name in ("lq1", "lk1", "lq2", "lk2"):
                out[p + "attn." + name] = ((hd,), "lambda")
            out[p + "attn.subln"] = ((2 * hd,), "ones")
        norm(p + "ffn_norm")
        ff = cfg["intermediate_size"]
        dense(p + "ffn.gate", ff, h, False)
        dense(p + "ffn.up", ff, h, False)
        dense(p + "ffn.down", h, ff, False)
    norm("final_norm")
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
            elif kind == "lambda":
                out[name] = 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif kind == "taps":
                out[name] = jax.random.uniform(k, shape, jnp.float32,
                                               -0.5, 0.5)
            elif kind == "a_log":
                out[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[1] + 1, dtype=jnp.float32)), shape)
            elif kind == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif kind == "zeros":
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                out[name] = jnp.ones(shape, jnp.float32)
        return out

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def _layer_norm(x, p, name, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p[name + ".g"] \
        + p[name + ".b"]


def short_conv(x, taps, bias):
    """A causal depthwise convolution over time with a bias and SiLU: x
    (rows, channels), taps (n, channels); ``y_t = sum_i taps[i] x_{t - (n -
    1) + i}``, rows before the first as zeros."""
    n, rows = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((n - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(padded[i:i + rows] * taps[i] for i in range(n))
                       + bias)


def selective_scan(x, delta, a, b, c, skip):
    """The recurrence, a row at a time: x, delta (rows, channels), a
    (channels, state), b, c (rows, state), skip (channels,); the state
    (state, channels) starts at zero.  The rows go in runs of ``STATE_ROWS``
    under ``jax.checkpoint``, so that the backward keeps a state a run and
    not a state a row."""
    rows, channels = x.shape
    at = a.T

    def row(h, r):
        x, delta, b, c = r
        h = jnp.exp(delta * at) * h + (delta * x) * b[:, None]
        return h, jnp.sum(h * c[:, None], axis=0) + skip * x

    @jax.checkpoint
    def run(h, rs):
        return jax.lax.scan(row, h, rs)

    size = _block(rows, STATE_ROWS)
    runs = tuple(v.reshape((rows // size, size) + v.shape[1:])
                 for v in (x, delta, b, c))
    _, y = jax.lax.scan(run, jnp.zeros(at.shape, jnp.float32), runs)
    return y.reshape(rows, channels)


def ssm_mixer(cfg, rnd, u, p, drop=()):
    """u (rows, hidden) -> ``(output (rows, hidden), memory (rows,
    channels))``."""
    channels, state, _, rank = ssm_sizes(cfg)
    xz = rnd(u) @ rnd(p["ssm.in"]).T
    z = xz[:, channels:]
    xc = short_conv(xz[:, :channels], p["ssm.conv"], p["ssm.conv_b"])
    rbc = rnd(xc) @ rnd(p["ssm.x"]).T
    delta = jax.nn.softplus(rnd(rbc[:, :rank]) @ rnd(p["ssm.dt"]).T
                            + p["ssm.dt_b"])
    a = -jnp.exp(p["ssm.a_log"])
    if "decay" in drop:
        a = jnp.zeros_like(a)
    y = selective_scan(xc, delta, a, rbc[:, rank:rank + state],
                       rbc[:, rank + state:], p["ssm.d"])
    gated = y * jax.nn.silu(z)
    return rnd(gated) @ rnd(p["ssm.out"]).T, \
        gated if "memory_after_gate" in drop else y


def gmu_mixer(rnd, u, p, memory, drop=()):
    gate = jax.nn.silu(rnd(u) @ rnd(p["gmu.in"]).T)
    if "memory_gate" in drop:
        gate = jnp.ones_like(gate)
    return rnd(gate * memory) @ rnd(p["gmu.out"]).T


def _maps(rnd, q, k, v, window):
    """``softmax(q k^T / sqrt(width) + mask) v`` a head: q, k (heads, rows,
    width), v (heads, rows, any), causal, over the last ``window`` keys where
    ``window`` is not None; a block of query rows at a time."""
    heads, rows, width = q.shape
    step = _block(rows, QUERY_ROWS)

    @jax.checkpoint
    def block_of_rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, step, axis=1)
        scores = jnp.einsum("hqd,hkd->hqk", rnd(qb), rnd(k)) \
            / math.sqrt(width)
        at, keys = (start + jnp.arange(step))[:, None], jnp.arange(rows)[None]
        seen = keys <= at
        if window is not None:
            seen &= keys > at - window
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", rnd(jax.nn.softmax(scores, -1)),
                          rnd(v))

    out = jax.lax.map(block_of_rows, jnp.arange(0, rows, step))
    return out.transpose(1, 0, 2, 3).reshape(heads, rows, v.shape[-1])


def keys_and_values(cfg, rnd, u, p):
    """``(k, v)`` (key-value heads, rows, head size) of ``u`` through an
    attention layer's ``Wk`` and ``Wv``."""
    rows, kv = u.shape[0], cfg["num_key_value_heads"]
    return tuple(
        (rnd(u) @ rnd(p[f"attn.{name}"]).T + p[f"attn.{name}_b"])
        .reshape(rows, kv, -1).transpose(1, 0, 2) for name in ("k", "v"))


def diff_attention(cfg, rnd, u, p, k, v, init, window, drop=()):
    """u (rows, hidden) with k, v (key-value heads, rows, head size) ->
    (rows, hidden): differential attention of every query pair."""
    rows, heads = u.shape[0], cfg["num_attention_heads"]
    q = (rnd(u) @ rnd(p["attn.q"]).T + p["attn.q_b"]) \
        .reshape(rows, heads, -1).transpose(1, 0, 2)
    # query pair p reads key-value pair p // (query pairs a key-value pair)
    share = heads // cfg["num_key_value_heads"]
    values = jnp.repeat(jnp.concatenate([v[0::2], v[1::2]], -1), share, 0)
    first = _maps(rnd, q[0::2], jnp.repeat(k[0::2], share, 0), values, window)
    second = _maps(rnd, q[1::2], jnp.repeat(k[1::2], share, 0), values,
                   window)
    lam = (jnp.exp(jnp.sum(p["attn.lq1"] * p["attn.lk1"]))
           - jnp.exp(jnp.sum(p["attn.lq2"] * p["attn.lk2"])) + init)
    if "lambda" in drop:
        lam = 0.0
    o = first - lam * second
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg["layer_norm_eps"])
    o = o * p["attn.subln"] * (1.0 - init)
    return rnd(o.transpose(1, 0, 2).reshape(rows, -1)) @ rnd(p["attn.o"]).T \
        + p["attn.o_b"]


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


def forward(cfg, ops, p, ids):
    """ids (L,) of one sample -> the final norm's output (L, hidden)."""
    rnd, eps = ops.round, cfg["layer_norm_eps"]
    drop = tuple(cfg.get("drop", ()))

    @partial(jax.checkpoint, static_argnums=(3, 4))
    def layer(x, lp, given, i, kind):
        """``given``: what earlier layers handed on, ``{"memory": M, "kv": (k,
        v), "kv_weights": the full layer's leaves}``; returns the residual
        stream and what this layer hands on."""
        u = _layer_norm(x, lp, "mixer_norm", eps)
        handed = {}
        if kind == "ssm":
            mixed, handed["memory"] = ssm_mixer(cfg, rnd, u, lp, drop)
        elif kind == "gmu":
            mixed = gmu_mixer(rnd, u, lp, given["memory"], drop)
        else:
            if kind == "cross":
                k, v = given["kv"]
                if "cross_kv" in drop:
                    k, v = keys_and_values(cfg, rnd, u, given["kv_weights"])
            else:
                k, v = keys_and_values(cfg, rnd, u, lp)
                handed = {"kv": (k, v), "kv_weights": {
                    name: lp[name] for name in
                    ("attn.k", "attn.k_b", "attn.v", "attn.v_b")}}
            mixed = diff_attention(
                cfg, rnd, u, lp, k, v, lambda_init(cfg, i),
                cfg["sliding_window"] if kind == "window" else None, drop)
        x = x + mixed
        h = _layer_norm(x, lp, "ffn_norm", eps)
        return x + _swiglu(rnd, h, lp["ffn.gate"], lp["ffn.up"],
                           lp["ffn.down"]), handed

    x, given = p["embed"][ids], {}
    for i, kind in enumerate(layer_kinds(cfg)):
        pre = f"layer{i}."
        x, handed = layer(x, {k[len(pre):]: v for k, v in p.items()
                              if k.startswith(pre)}, given, i, kind)
        # the last state-space block's memory and the full layer's pair are
        # what the later layers read
        if kind in ("ssm", "full"):
            given = {**given, **handed}
    return _layer_norm(x, p, "final_norm", eps)


def loss_fn(cfg, ops, p, ids, labels):
    """One sample: ``ids`` (L,), ``labels`` (L,) the next tokens.  Mean
    cross-entropy over the L positions, the tied head a block of rows at a
    time."""
    rnd = ops.round
    x = forward(cfg, ops, p, ids)
    rows = x.shape[0]
    step = _block(rows, HEAD_ROWS)

    @jax.checkpoint
    def block_of_rows(x, target):
        logp = jax.nn.log_softmax(rnd(x) @ rnd(p["embed"]).T, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, target[:, None], axis=-1))

    sums = jax.lax.map(lambda xt: block_of_rows(*xt), (
        x.reshape(rows // step, step, -1), labels.reshape(-1, step)))
    return jnp.sum(sums) / rows


def _jitted(cfg, precision):
    """The jitted loss and gradients of a batch for one precision."""
    from chipbench.harness.precision import ops as make_ops

    ops = make_ops(precision)

    # samples are independent, so the batch mean is the mean over samples:
    # one sample's activations live at a time
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
    loaded program keeps its scratch, and the optimizer's step that follows
    holds six copies of every leaf (12.9 GiB) and has no room beside that."""
    ids, labels = batch
    out = _jitted(cfg, precision)(p, jnp.asarray(ids), jnp.asarray(labels))
    return jax.block_until_ready(out)
