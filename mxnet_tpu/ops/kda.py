"""Kimi delta attention's core: the gated delta rule with a decay a channel,
computed a chunk of rows at a time, and the small ops that stand around it.

A head keeps a state ``S (K, V)``, zero at the row's start.  At row ``t``,
with ``alpha_t = exp(g_t)`` a decay a channel of ``k`` and ``beta_t`` one
number::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``kda`` computes this in chunks of ``chunk`` rows (the WY form of Kimi
Linear, arXiv:2510.26692): with ``b`` the running sum of ``g`` inside a
chunk and ``P(x)[i, j] = sum_d x_id k_jd exp(b_id - b_jd)`` for ``j <= i``::

    A = strictly_lower(Diag(beta) P(k))          T = (I + A)^-1 Diag(beta)
    W = T (k e^b)    U = T v                     V~ = U - W S_0
    o = (q e^b) S_0 + lower(P(q)) V~
    S_next = Diag(e^{b_last}) S_0 + (k e^{b_last - b})^T V~

in three passes: everything of a chunk that does not need its state, for all
chunks at once (``_prepare``: the decays, ``P``, and ``T`` by doubling from
blocks of one row, element-wise over every chunk and head at once
(``_inverse``): no row-at-a-time triangular solve, whose 64 dependent rows
cost the TPU 0.69 ms a call whatever its batch, 24 calls a step of six
layers); the
states from chunk to chunk, the one sequential part (``_states``: a
``lax.scan`` of two products a chunk; a Pallas kernel with the states of
eight heads in VMEM was measured beside it on a v5e and took the same time,
PERF.md section 6, PR 38, so there is none); and the outputs, for all chunks
at once.  ``exp(-b)``
over a chunk of 64 rows at a decay of -5 a row would be ``e^320``: ``P`` is
formed a sub-block of ``_SUB`` rows at a time against the sub-block's own
origin, its middle row, so that no exponent passes ``_SUB / 2`` times the
decay's bound (40 at -5, well inside float32).  ``g``, ``b``, the
exponentials and ``T`` with its product (``_solve``, at ``HIGHEST``) are
float32; the other products take their operands in the inputs' dtype and
accumulate in float32, as the attention kernels do.

The backward is written by hand (``jax.custom_vjp``): it keeps the op's
inputs and the chunk states ``S_0`` of every chunk (``K x V`` float32 a
chunk a head), computes ``V~`` again from them for all chunks at once, walks
the chunks backwards for the states' cotangents (a scan of two products a
chunk again), and takes what is a chunk's own back through ``jax.vjp`` of
``_prepare``, a run of chunks at a time so that what that keeps stays
small.  The token-by-token recurrence above is ``kda_recurrent``: the
definition, for tests and for lengths under a chunk; never a TPU's path at a
training length.
"""
from __future__ import annotations

import functools
import types

from ..base import MXNetError
from ..profiler import KERNEL_KDA_BWD, KERNEL_KDA_FWD
from .flash_attention import _operand_precision, keeping, kept
from .registry import register

_SUB = 16     # rows of a sub-block of a chunk, for the decays between rows
# chunks (of every head) whose way back through ``_prepare`` is taken at once:
# as few runs as memory allows: every run builds its matrices' ``T`` again
# (``_prepare`` runs once more inside ``jax.vjp``, one more call of
# ``_inverse`` a run), and two runs of 64 chunks keep 0.1 GiB at the Ling
# cell's shape, so nothing asks for shorter ones
_BACK_CHUNKS = 64
# the names of the op's output and of its chunk states for a checkpoint that
# keeps them (``flash_attention.checkpoint_keeps``)
KEPT_O = "mxnet_kda_o"
KEPT_STATES = "mxnet_kda_states"


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def _pairwise(xs, k, b, mm):
    """``P(x)[i, j] = sum_d x_id k_jd exp(b_id - b_jd)`` for every ``x`` of
    ``xs`` (``(..., C, K)`` float32, ``b`` the running log-decay, falling
    along the rows), right where ``j``'s sub-block is not after ``i``'s and
    0 after it; the caller keeps ``j <= i``.  Sub-block ``I`` measures
    decays from ``r_I``, ``b`` at its middle row: a row of it takes
    ``exp(b_i - r_I)`` and a key ``exp(r_I - b_j)`` (at most 0 before the
    sub-block), both exponents within ``_SUB / 2`` rows' decay inside it
    (40 at a bound of -5), so that neither factor nears float32's ends: a
    factor of ``e^-80`` on a small ``x`` would be a denormal."""
    import jax.numpy as jnp

    *lead, c, kd = b.shape
    nb = c // _SUB
    bs = b.reshape(*lead, nb, _SUB, kd)
    origin = bs[..., _SUB // 2, :]                       # (..., nb, K)
    rows = jnp.exp(bs - origin[..., None, :])            # (..., nb, SUB, K)
    seen = (jnp.arange(c) // _SUB)[None, :] <= jnp.arange(nb)[:, None]
    seen = seen[..., None]                               # (nb, C, 1)
    lift = jnp.where(seen, origin[..., None, :] - b[..., None, :, :], 0.0)
    keys = jnp.where(seen, k[..., None, :, :] * jnp.exp(lift), 0.0)
    keys = keys.astype(mm)                               # (..., nb, C, K)
    dot = functools.partial(jnp.einsum, "...id,...jd->...ij",
                            precision=_operand_precision(mm),
                            preferred_element_type=jnp.float32)
    return [dot((x.reshape(bs.shape) * rows).astype(mm), keys)
            .reshape(*lead, c, c) for x in xs]


def _inverse(a):
    """``(I + strictly_lower(a))^-1`` for ``a (..., C, C)`` float32, ``C`` a
    multiple of ``_SUB``, by doubling: the inverse of one row is 1, and a
    level joins the inverses of neighbouring diagonal blocks of ``h`` rows
    into those of ``2h``: a pair ``[[top, 0], [under, low]]`` has the
    inverse ``[[top^-1, 0], [-low^-1 under top^-1, low^-1]]``.  Nothing
    waits on more than ``log2 C`` levels in sequence and nothing is a loop
    on the device.  It is block forward substitution in another order:
    against float64 it is as exact as the row-at-a-time solve on keys that
    lie near one direction too (``tests/test_kda_mla_layers.py``), where the
    series ``(I - N)(I + N^2)(I + N^4)...`` loses three digits.

    All of it is element-wise float32 with the matrices along the last
    axis, reached by one 2-D transpose of ``(matrices, C * C)`` and left by
    one: an entry ``(i, j)`` of every chunk and head at once is then a row
    of whole vectors.  Products of matrices of 1 to 32 rows by the MXU in
    float32 (six passes) with the layout changes around them took three
    times as long on a v5e (PERF.md section 6, PR 47).

    Every level is a few whole-array ops over ``(pairs, h, h, matrices)``:
    a product is one broadcast multiply and one sum over the inner axis,
    and the blocks under the diagonal come by halving from the top (a
    level's are a slice of the diagonal blocks one level up; a gather a
    level cost 0.1 ms a call more).  The only Python loops are the two over
    the levels, and the whole is 159 equations at a chunk of 64.  With a
    multiply-add an inner index and a slice a pair it is 1,210, three sites
    a layer, for the same time on the device, and the Ling cell's set-up
    took 23 s longer (PERF.md section 6, PRs 47 and 48): a tier-1 test
    holds the count.  A size that is no power of two is padded with rows of
    the identity, whose inverse is theirs, and cut back."""
    import jax.numpy as jnp

    *lead, c, _ = a.shape
    size = _SUB
    while size < c:
        size *= 2
    if size != c:
        a = jnp.pad(a, [(0, 0)] * len(lead) + [(0, size - c)] * 2)
    blocks = a.reshape(-1, size * size).T.reshape(1, size, size, -1)
    unders = []         # (pairs, h, h, matrices) for h = size / 2, ..., 1
    h = size // 2
    while h:
        unders.append(blocks[:, h:, :h])
        blocks = jnp.stack([blocks[:, :h, :h], blocks[:, h:, h:]], 1) \
            .reshape(-1, h, h, blocks.shape[-1])
        h //= 2

    def product(x, y):
        return jnp.sum(x[:, :, :, None] * y[:, None], axis=2)

    d = jnp.ones_like(blocks)           # (size, 1, 1, matrices)
    while unders:
        d = d.reshape(-1, 2, *d.shape[1:])
        top, low = d[:, 0], d[:, 1]
        corner = -product(low, product(unders.pop(), top))
        d = jnp.concatenate([
            jnp.concatenate([top, jnp.zeros_like(top)], 2),
            jnp.concatenate([corner, low], 2)], 1)
    return d[0, :c, :c].reshape(c * c, -1).T.reshape(*lead, c, c)


def _dot_f32(spec, a, b):
    """A batched product of float32 operands to the last bit the MXU gives
    (``HIGHEST``: six bf16 passes)."""
    import jax
    import jax.numpy as jnp

    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


@functools.lru_cache(maxsize=None)
def _entries():
    """``inverse``, ``solve``: ``_inverse`` behind a ``jax.jit`` entry made
    once a process (``grouped_matmul._entries`` says why: a site is then one
    equation, traced once a shape and lowered as one function that a step's
    18 sites call), and the solve over it with its own way back."""
    import jax
    import jax.numpy as jnp

    inverse = jax.jit(_inverse)

    def fwd(a, rhs):
        t = inverse(a)
        x = _dot_f32("...ij,...jm->...im", t, rhs)
        return x, (t, x)

    @jax.custom_vjp
    def solve(a, rhs):
        return fwd(a, rhs)[0]

    # two products with the inverse at hand (``x = T rhs``, ``T = (I +
    # A)^-1``: ``d rhs = T^T dx``, ``dA = -d rhs x^T`` where ``A`` counts),
    # not the way back through ``_inverse``'s levels: several times the ops
    # for the same numbers (0.6 ms more a call of the op at the Ling cell's
    # shape: PERF.md section 6, PR 47)
    def bwd(res, dx):
        t, x = res
        d_rhs = _dot_f32("...ji,...jm->...im", t, dx)
        return (jnp.tril(-_dot_f32("...im,...jm->...ij", d_rhs, x), -1),
                d_rhs)

    solve.defvjp(fwd, bwd)
    return types.SimpleNamespace(inverse=inverse, solve=solve)


def _solve(a, rhs):
    """``(I + strictly_lower(a))^-1 rhs`` for ``a (..., C, C)`` and ``rhs
    (..., C, M)`` float32: the explicit inverse (``_inverse``) and one
    product at ``HIGHEST``, float32 throughout."""
    import jax

    with jax.named_scope("solve"):
        return _entries().solve(a, rhs)


def _prepare(q, k, v, g, beta):
    """What a chunk gives without its state, for every chunk at once: inputs
    ``(..., C, K | V)`` with ``g`` float32 and ``beta (..., C)``; returns
    ``(qg, kl, w, u, aqk, decay)``: ``q e^b``, ``k e^{b_last - b}``, ``W``,
    ``U``, ``lower(P(q))`` and ``e^{b_last} (..., K)``, float32.  ``W | U =
    (I + A)^-1 [beta k e^b | beta v]`` is one ``_solve``: of ``P(k)`` only
    what lies under the diagonal counts."""
    import jax.numpy as jnp

    mm = q.dtype
    q, k, v, beta = _f32(q), _f32(k), _f32(v), _f32(beta)[..., None]
    b = jnp.cumsum(_f32(g), axis=-2)
    last = b[..., -1:, :]
    grow = jnp.exp(b)
    pk, pq = _pairwise([k * beta, q], k, b, mm)
    both = _solve(pk, jnp.concatenate([k * grow * beta, v * beta], -1))
    kd = k.shape[-1]
    return (q * grow, k * jnp.exp(last - b), both[..., :kd], both[..., kd:],
            jnp.tril(pq), jnp.exp(last[..., 0, :]))


def _products(mm):
    """``dot(spec, a, b)``: an einsum on operands in ``mm`` that accumulates
    in float32."""
    import jax.numpy as jnp

    def dot(spec, a, b):
        return jnp.einsum(spec, a.astype(mm), b.astype(mm),
                          precision=_operand_precision(mm),
                          preferred_element_type=jnp.float32)

    return dot


def _states(kl, w, u, decay, mm):
    """The state at the start of every chunk, ``(chunks, N, V, K)`` float32
    (held transposed, so that the decay a channel is a row over it), from a
    scan over the chunks."""
    import jax
    import jax.numpy as jnp

    dot = _products(mm)

    def step(s, chunk):
        kl, w, u, decay = chunk
        vt = u - dot("nck,nvk->ncv", w, s)
        return decay[:, None] * s + dot("ncv,nck->nvk", vt, kl), s

    _, n, _, kd = kl.shape
    first = jnp.zeros((n, u.shape[-1], kd), jnp.float32)
    return jax.lax.scan(step, first, (kl, w, u, decay))[1]


def _state_cotangents(own, kl, w, decay, mm):
    """``r (chunks, N, V, K)``: the cotangent of every chunk's next state,
    from the chunk after it (``next = decay s + (u - w s)^T kl``, transposed
    as the states are)."""
    import jax
    import jax.numpy as jnp

    dot = _products(mm)

    def step(r, chunk):
        own, kl, w, decay = chunk
        through = dot("nck,nvk->ncv", kl, r)
        return (own + decay[:, None] * r
                - dot("ncv,nck->nvk", through, w)), r

    return jax.lax.scan(step, jnp.zeros_like(own[0]), (own, kl, w, decay),
                        reverse=True)[1]


def _outputs(qg, w, u, aqk, s, mm):
    """``(o, V~)`` of every chunk from its state ``s``."""
    dot = _products(mm)
    vt = u - dot("...ck,...vk->...cv", w, s)
    return (dot("...ck,...vk->...cv", qg, s)
            + dot("...ij,...jv->...iv", aqk, vt)), vt


def _forward(q, k, v, g, beta):
    """Chunked inputs ``(chunks, N, C, .)`` -> ``(o float32, states)``."""
    import jax

    with jax.named_scope(KERNEL_KDA_FWD):
        qg, kl, w, u, aqk, decay = _prepare(q, k, v, g, beta)
        s = _states(kl, w, u, decay, q.dtype)
        return _outputs(qg, w, u, aqk, s, q.dtype)[0], s


def _backward(q, k, v, g, beta, s, do):
    """Cotangents of the five chunked inputs from ``do`` (float32) and the
    saved chunk states: the outputs' pass backwards for every chunk at once,
    one pass from the last chunk to the first for the states' cotangents,
    the rest for every chunk at once again, and ``_prepare``'s own by
    ``jax.vjp``, ``_BACK_CHUNKS`` chunks at a time."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope(KERNEL_KDA_BWD):
        mm = q.dtype
        dot = _products(mm)
        qg, kl, w, u, aqk, decay = _prepare(q, k, v, g, beta)
        _, vt = _outputs(qg, w, u, aqk, s, mm)
        # o = qg s + aqk vt, vt = u - w s
        d_qg = dot("...cv,...vk->...ck", do, s)
        d_aqk = jnp.tril(dot("...iv,...jv->...ij", do, vt))
        d_vt = dot("...ij,...iv->...jv", aqk, do)
        own = dot("...cv,...ck->...vk", do, qg) \
            - dot("...cv,...ck->...vk", d_vt, w)
        r = _state_cotangents(own, kl, w, decay, mm)
        d_vt = d_vt + dot("...ck,...vk->...cv", kl, r)
        d_kl = dot("...cv,...vk->...ck", vt, r)
        d_decay = jnp.sum(r * s, axis=-2)
        d_w = -dot("...cv,...vk->...ck", d_vt, s)

        # a run of chunks at a time, so that what ``_prepare`` keeps for
        # its way back (the decayed keys of every sub-block, ``T`` and ``W |
        # U`` of the solve) is that run's at once
        def back(run):
            inputs, cotangents = run
            return jax.vjp(_prepare, *inputs)[1](cotangents)

        chunks = q.shape[0]
        size = _BACK_CHUNKS if chunks % _BACK_CHUNKS == 0 else chunks
        runs = jax.tree_util.tree_map(
            lambda x: x.reshape(chunks // size, size, *x.shape[1:]),
            ((q, k, v, g, beta), (d_qg, d_kl, d_w, d_vt, d_aqk, d_decay)))
        return tuple(d.reshape(chunks, *d.shape[2:])
                     for d in jax.lax.map(back, runs))


@functools.lru_cache(maxsize=None)
def _make_kda(chunk, keeps=False):
    import jax

    import jax.numpy as jnp

    # the chunks lead, so that the scans over them walk the first axis
    def chunked(x):
        b, h, l = x.shape[:3]
        return jnp.moveaxis(
            x.reshape(b * h, l // chunk, chunk, *x.shape[3:]), 1, 0)

    def whole(x, like):
        return jnp.moveaxis(x, 0, 1).reshape(like.shape).astype(like.dtype)

    def forward(q, k, v, g, beta):
        o, s = _forward(*map(chunked, (q, k, v, g, beta)))
        return whole(o, v), s

    @jax.custom_vjp
    def op(q, k, v, g, beta):
        return forward(q, k, v, g, beta)[0]

    def fwd(q, k, v, g, beta):
        o, s = forward(q, k, v, g, beta)
        if keeps:
            # named inside the rule, as the attention op's: a checkpoint
            # that keeps both (``checkpoint_keeps``) runs no second forward
            o, s = kept(KEPT_O, o), kept(KEPT_STATES, s)
            q, k, v, g, beta, o, s = jax.lax.optimization_barrier(
                (q, k, v, g, beta, o, s))
        return o, (q, k, v, g, beta, s)

    def bwd(res, do):
        *inputs, s = res
        grads = _backward(*map(chunked, inputs), s, _f32(chunked(do)))
        return tuple(map(whole, grads, inputs))

    op.defvjp(fwd, bwd)
    return op


def kda_recurrent(q, k, v, g, beta):
    """The definition, a row at a time in float32: the recurrence at the top
    of this file by ``lax.scan`` over the rows.  Shapes as ``kda``'s."""
    import jax
    import jax.numpy as jnp

    q, k, v, g, beta = map(_f32, (q, k, v, g, beta))

    def step(s, row):
        q, k, v, g, beta = row
        s = jnp.exp(g)[..., None] * s
        s = s + (beta[..., None] * k)[..., None] * (
            v - jnp.einsum("...k,...kv->...v", k, s))[..., None, :]
        return s, jnp.einsum("...k,...kv->...v", q, s)

    first = jnp.zeros(k.shape[:2] + (k.shape[-1], v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, first, tuple(
        jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 2)


@register("_contrib_kda", aliases=("kda",))
def kda(q, k, v, g, beta, chunk=64):
    """The gated delta rule with a decay a channel (Kimi delta attention's
    core), chunked.

    ``q``, ``k (B, H, L, K)``, ``v (B, H, L, V)``, ``g (B, H, L, K)`` the
    log-decay (float32, at most 0; ``_SUB / 2 * |g|`` has to stay well
    under 88, which a bound of -5 keeps), ``beta (B, H, L)``.  Returns ``o (B, H, L,
    V)`` in ``v``'s dtype.  ``q`` comes scaled and ``q`` and ``k``
    normalised as the caller's mixer has them.  ``L`` is a whole number of
    chunks of ``chunk`` rows (a multiple of ``_SUB``); any other length is
    refused by name.  A call is counted once a trace in
    ``mxnet_kda_calls_total{path}`` (one path, ``"scan"``: the state pass
    has no kernel), its chunks in ``mxnet_kda_chunks_total``."""
    from .. import telemetry

    chunk = int(chunk)
    l = q.shape[2]
    if chunk % _SUB or l % chunk:
        raise MXNetError(
            f"kda: {l} rows are no whole number of chunks of {chunk} (a "
            f"multiple of {_SUB}); pad the row or pass a chunk that divides "
            "it")
    if not (q.shape == k.shape == g.shape and v.shape[:3] == q.shape[:3]
            and beta.shape == q.shape[:3]):
        raise MXNetError(
            "kda: q, k and g are (B, H, L, K), v (B, H, L, V) and beta "
            f"(B, H, L); got q {q.shape}, k {k.shape}, v {v.shape}, g "
            f"{g.shape}, beta {beta.shape}")
    telemetry.KDA_CALLS.labels(path="scan").inc()
    telemetry.KDA_CHUNKS.inc(l // chunk)
    return _make_kda(chunk, keeping())(q, k, v, g, beta)


@register("_contrib_short_conv", aliases=("short_conv",))
def short_conv(x, weight, bias=None, activation="silu"):
    """A causal depthwise convolution over time: ``x (B, L, C)``, ``weight
    (taps, C)``; ``y_t = sum_i weight[i] x_{t - (taps - 1) + i}`` (rows
    before the first are zeros), plus ``bias (C,)`` where one is given (a
    state-space block's), then SiLU where ``activation`` is ``"silu"``.
    Computed in float32, returned in ``x``'s dtype."""
    import jax
    import jax.numpy as jnp

    if activation not in ("silu", "", None):
        raise MXNetError(f"short_conv: unknown activation {activation!r}")

    @jax.checkpoint     # the backward keeps x and the taps, no float32 copy
    def conv(x, weight, *bias):
        taps, l = weight.shape[0], x.shape[1]
        padded = jnp.pad(_f32(x), ((0, 0), (taps - 1, 0), (0, 0)))
        y = sum(padded[:, i:i + l] * _f32(weight[i]) for i in range(taps))
        for term in bias:
            y = y + _f32(term)
        return (jax.nn.silu(y) if activation else y).astype(x.dtype)

    return conv(x, weight) if bias is None else conv(x, weight, bias)


@register("_contrib_l2_norm_heads", aliases=("l2_norm_heads",))
def l2_norm_heads(x, heads=1, scale=1.0, eps=1e-6):
    """``x (B, L, heads * D)`` -> ``(B, heads, L, D)``, every head's vector
    divided by ``sqrt(its sum of squares + eps)`` and multiplied by
    ``scale``; float32 inside, ``x``'s dtype out."""
    import jax
    import jax.numpy as jnp

    @jax.checkpoint
    def norm(x):
        b, l, width = x.shape
        y = _f32(x).reshape(b, l, heads, width // heads).transpose(0, 2, 1, 3)
        y = y * (jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + eps)
                 * scale)
        return y.astype(x.dtype)

    return norm(x)


@register("_contrib_kda_decay", aliases=("kda_decay",))
def kda_decay(f, a_log, dt_bias, heads=1, lower_bound=-5.0):
    """The bounded log-decay of Kimi delta attention (its safe gate): ``f
    (B, L, heads * K)`` the decay projection, ``a_log (heads,)``, ``dt_bias
    (heads * K,)``; ``g = lower_bound * sigmoid(exp(a_log) * (f + dt_bias))``
    as ``(B, heads, L, K)`` float32, in ``(lower_bound, 0)``."""
    import jax
    import jax.numpy as jnp

    @jax.checkpoint
    def decay(f, a_log, dt_bias):
        b, l, width = f.shape
        x = (_f32(f) + _f32(dt_bias)).reshape(b, l, heads, width // heads)
        g = lower_bound * jax.nn.sigmoid(x * jnp.exp(_f32(a_log))[:, None])
        return g.transpose(0, 2, 1, 3)

    return decay(f, a_log, dt_bias)
