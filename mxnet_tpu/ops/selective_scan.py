"""The selective state-space scan (Mamba-1, arXiv:2312.00752): a state of
``N`` numbers a channel that decays by an input-dependent step and reads the
row through input-dependent vectors.

A sample keeps a state ``h (D, N)``, zero at the row's start.  At row ``t``,
with ``delta_t (D,)`` the step (positive: the caller's softplus), ``A (D, N)``
negative, ``B_t``, ``C_t (N,)`` and the skip ``D (D,)``::

    h_t = exp(delta_t A) * h_{t-1} + (delta_t * x_t) B_t^T
    y_t = h_t C_t + D * x_t

``selective_scan`` computes this a chunk of ``chunk`` rows at a time: the
state lives on chip while a chunk's rows are walked, and what leaves is ``y``
and the state at the start of every chunk (``D x N`` float32 a chunk).  No
``rows x D x N`` array is ever held: XLA's plain form of the recurrence (an
associative scan, or a scan that keeps its carries) would hold 2.7 GB of them
at 8,192 rows of 5120 x 16.

Two walks of a chunk, one arithmetic: on a TPU, for channels in whole blocks
of ``_BLOCK`` (an ``(8, 128)`` register a state index), a Pallas kernel each
way (``mxnet_selective_scan_fwd`` / ``_bwd``): a block's 16 registers of state
stay in registers over a chunk's rows, ``B_t`` and ``C_t`` are scalars from
SMEM, and the sums over the channels that ``dB`` and ``dC`` need are made over
a register's sublanes in the kernel and over its lanes outside it.  Everywhere
else a ``lax.scan`` over the chunks of a ``lax.scan`` over a chunk's rows.
PERF.md section 6 (PR 45) has both walks' times on a v5e.

The backward is written by hand (``jax.custom_vjp``): it keeps the op's
inputs, ``y`` and the chunk states; from the last chunk to the first it walks
a chunk's rows forward again from the chunk's state (``chunk x D x N`` float32
at a time, in VMEM in the kernel) and then backward.  ``delta``, ``exp(delta
A)``, the state and every sum are float32; ``x``, ``B`` and ``C`` arrive in the
step's dtype.  The token-by-token recurrence above is
``selective_scan_recurrent``: the definition, for tests.
"""
from __future__ import annotations

import functools

from ..base import MXNetError
from ..profiler import KERNEL_SSM_SCAN_BWD, KERNEL_SSM_SCAN_FWD
from .flash_attention import keeping, kept
from .registry import register

# channels of one block of the kernels: an (8, 128) register a state index
_BLOCK = 1024
# the scoped VMEM the kernels state: the backward holds a chunk's states again
# (chunk + 1 times 16 registers: 4.2 MB at 64 rows) beside two buffers of
# every operand and result tile, some 10 MB in all at the cell's shape
_VMEM_LIMIT = 48 << 20
# the names of the op's output and of its chunk states for a checkpoint that
# keeps them (``flash_attention.checkpoint_keeps``)
KEPT_Y = "mxnet_selective_scan_y"
KEPT_STATES = "mxnet_selective_scan_states"


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def use_pallas(x):
    """Static gate for the kernels, read from the call: a TPU to compile for
    (JAX's default backend, as the attention kernels ask) and channels in
    whole blocks of ``_BLOCK``."""
    import jax

    return x.shape[-1] % _BLOCK == 0 and jax.default_backend() == "tpu"


# --------------------------------------------------------------------------
# the walk as XLA scans: chunks outside, a chunk's rows inside
# --------------------------------------------------------------------------
def _rows_first(x, chunk):
    """``(B, L, W) -> (L / chunk, chunk, B, W)``."""
    import jax.numpy as jnp

    b, l, w = x.shape
    return jnp.moveaxis(x.reshape(b, l // chunk, chunk, w), 0, 2)


def _rows_last(x):
    """``(chunks, chunk, B, W) -> (B, L, W)``."""
    import jax.numpy as jnp

    c, t, b, w = x.shape
    return jnp.moveaxis(x, 2, 0).reshape(b, c * t, w)


def _row(at, skip):
    """``step(h, (x, delta, b, c)) -> (h, y)`` of one row: ``h (B, N, D)``,
    the state held with the channels last (the lanes), ``at`` ``A``
    transposed ``(N, D)``."""
    import jax.numpy as jnp

    def step(h, row):
        x, delta, b, c = row
        h = (jnp.exp(delta[:, None, :] * at) * h
             + (delta * x)[:, None, :] * b[:, :, None])
        return h, jnp.sum(h * c[:, :, None], axis=1) + skip * x

    return step


def _forward_scan(x, delta, at, b, c, skip, chunk):
    """``(y (B, L, D) float32, states (chunks, B, N, D))``."""
    import jax
    import jax.numpy as jnp

    step = _row(at, skip)

    def walk(h, rows):
        after, y = jax.lax.scan(step, h, rows)
        return after, (y, h)

    first = jnp.zeros((x.shape[0],) + at.shape, jnp.float32)
    _, (y, states) = jax.lax.scan(walk, first, tuple(
        _rows_first(_f32(v), chunk) for v in (x, delta, b, c)))
    return _rows_last(y), states


def _backward_scan(x, delta, at, b, c, skip, states, dy, chunk):
    """Cotangents ``(dx, ddelta, dat (N, D), db, dc, dskip)``, float32."""
    import jax
    import jax.numpy as jnp

    step = _row(at, skip)

    def back(carry, row):
        g, d_at = carry
        x, delta, b, c, dy, before, h = row
        decay = jnp.exp(delta[:, None, :] * at)
        g = g + c[:, :, None] * dy[:, None, :]
        u = delta * x
        du = jnp.sum(g * b[:, :, None], axis=1)
        through = g * before * decay
        d_delta = jnp.sum(through * at, axis=1) + du * x
        d_at = d_at + jnp.sum(through * delta[:, None, :], axis=0)
        return (decay * g, d_at), (
            du * delta + skip * dy, d_delta, jnp.sum(g * u[:, None, :], -1),
            jnp.sum(h * dy[:, None, :], -1))

    def walk(carry, chunk_of):
        *rows, start = chunk_of
        # the chunk's states again, a row at a time from its first
        _, after = jax.lax.scan(
            lambda h, row: (step(h, row)[0],) * 2, start, tuple(rows[:4]))
        before = jnp.concatenate([start[None], after[:-1]])
        return jax.lax.scan(back, carry, (*rows, before, after),
                            reverse=True)

    zero = (jnp.zeros((x.shape[0],) + at.shape, jnp.float32),
            jnp.zeros(at.shape, jnp.float32))
    (_, d_at), (dx, d_delta, db, dc) = jax.lax.scan(
        walk, zero, tuple(_rows_first(_f32(v), chunk)
                          for v in (x, delta, b, c, dy)) + (states,),
        reverse=True)
    d_skip = jnp.sum(_f32(dy) * _f32(x), axis=(0, 1))
    return tuple(map(_rows_last, (dx, d_delta))) + (d_at,) + tuple(
        map(_rows_last, (db, dc))) + (d_skip,)


# --------------------------------------------------------------------------
# the walk as Pallas kernels
# --------------------------------------------------------------------------
def _blocked(x):
    """``(..., D) -> (..., D / _BLOCK, 8, 128)`` float32: a block of channels
    a register."""
    return _f32(x).reshape(x.shape[:-1] + (x.shape[-1] // _BLOCK, 8, 128))


def _fwd_kernel(b_ref, c_ref, x_ref, dl_ref, at_ref, skip_ref, y_ref, s_ref,
                h_ref, *, chunk, states):
    """One chunk of one block of channels: ``x_ref``, ``dl_ref``, ``y_ref``
    ``(chunk, 8, 128)``, ``at_ref``, ``s_ref`` ``(N, 8, 128)``, ``b_ref``,
    ``c_ref`` ``(chunk, N)`` in SMEM; ``h_ref (blocks, N, 8, 128)`` carries
    every block's state from chunk to chunk."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _():
        h_ref[j] = jnp.zeros(h_ref.shape[1:], jnp.float32)

    s_ref[...] = h_ref[j]
    skip = skip_ref[...]

    def row(t, h):
        x, dl = x_ref[t], dl_ref[t]
        u, y = dl * x, skip * x
        after = []
        for n in range(states):
            hn = jnp.exp(dl * at_ref[n]) * h[n] + u * b_ref[t, n]
            y = y + hn * c_ref[t, n]
            after.append(hn)
        y_ref[t] = y
        return tuple(after)

    h = jax.lax.fori_loop(0, chunk, row,
                          tuple(h_ref[j, n] for n in range(states)))
    for n in range(states):
        h_ref[j, n] = h[n]


def _bwd_kernel(b_ref, c_ref, x_ref, dl_ref, dy_ref, at_ref, skip_ref, s_ref,
                dx_ref, ddl_ref, db_ref, dc_ref, dat_ref, dskip_ref,
                g_ref, hist_ref, *, chunk, states):
    """One chunk (the grid walks them from the last) of one block of
    channels.  ``hist_ref (chunk + 1, N, 8, 128)``: the chunk's states again,
    row by row from ``s_ref``; ``g_ref (blocks, N, 8, 128)`` carries every
    block's state cotangent from chunk to chunk.  ``db_ref`` / ``dc_ref``
    ``(chunk, N, 128)``: the sums over a register's sublanes, added over the
    blocks (the grid's last axis); ``dat_ref (blocks, N, 8, 128)`` and
    ``dskip_ref (blocks, 8, 128)`` stay in VMEM for the whole grid."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i, j = pl.program_id(1), pl.program_id(2)
    zeros = jnp.zeros(g_ref.shape[1:], jnp.float32)

    @pl.when((pl.program_id(0) == 0) & (i == 0))
    def _():
        dat_ref[j] = zeros
        dskip_ref[j] = zeros[0]

    @pl.when(i == 0)
    def _():
        g_ref[j] = zeros

    @pl.when(j == 0)
    def _():
        db_ref[...] = jnp.zeros(db_ref.shape, jnp.float32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, jnp.float32)

    hist_ref[0] = s_ref[...]

    def again(t, h):
        dl = dl_ref[t]
        u = dl * x_ref[t]
        after = tuple(jnp.exp(dl * at_ref[n]) * h[n] + u * b_ref[t, n]
                      for n in range(states))
        for n in range(states):
            hist_ref[t + 1, n] = after[n]
        return after

    jax.lax.fori_loop(0, chunk, again,
                      tuple(s_ref[n] for n in range(states)))
    skip = skip_ref[...]

    def back(k, carry):
        g, d_skip = carry
        t = chunk - 1 - k
        x, dl, dy = x_ref[t], dl_ref[t], dy_ref[t]
        u = dl * x
        du = d_delta = jnp.zeros_like(x)
        before, db, dc = [], [], []
        for n in range(states):
            at = at_ref[n]
            decay = jnp.exp(dl * at)
            gn = g[n] + dy * c_ref[t, n]
            dc.append(jnp.sum(hist_ref[t + 1, n] * dy, axis=0, keepdims=True))
            db.append(jnp.sum(gn * u, axis=0, keepdims=True))
            du = du + gn * b_ref[t, n]
            through = gn * hist_ref[t, n] * decay
            d_delta = d_delta + through * at
            dat_ref[j, n] += through * dl
            before.append(decay * gn)
        db_ref[t] += jnp.concatenate(db, axis=0)
        dc_ref[t] += jnp.concatenate(dc, axis=0)
        ddl_ref[t] = d_delta + du * x
        dx_ref[t] = du * dl + skip * dy
        return tuple(before), d_skip + dy * x

    g, d_skip = jax.lax.fori_loop(
        0, chunk, back, (tuple(g_ref[j, n] for n in range(states)),
                         jnp.zeros(skip.shape, jnp.float32)))
    for n in range(states):
        g_ref[j, n] = g[n]
    dskip_ref[j] += d_skip


@functools.lru_cache(maxsize=None)
def _entries(chunk):
    """The two kernels behind ``jax.jit`` entries made once a chunk size: a
    step's module holds one kernel a shape however many layers call it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * 3, vmem_limit_bytes=_VMEM_LIMIT)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)

    def specs(states, at_chunk):
        """Block specs by what they hold: a chunk's rows of a block, a
        chunk's ``B_t`` / ``C_t``, a block's ``A``, skip and chunk state.
        ``at_chunk(i)``: the chunk the grid's step ``i`` walks."""
        rows = pl.BlockSpec((None, chunk, None, 8, 128),
                            lambda s, i, j: (s, at_chunk(i), j, 0, 0))
        scalars = smem((None, chunk, states),
                       lambda s, i, j: (s, at_chunk(i), 0))
        at = pl.BlockSpec((states, None, 8, 128), lambda s, i, j: (0, j, 0, 0))
        skip = pl.BlockSpec((None, 8, 128), lambda s, i, j: (j, 0, 0))
        state = pl.BlockSpec((None, None, None, states, 8, 128),
                             lambda s, i, j: (s, at_chunk(i), j, 0, 0, 0))
        return rows, scalars, at, skip, state

    @jax.jit
    def forward(x, delta, at, b, c, skip):
        samples, length, blocks = x.shape[:3]
        states, chunks = at.shape[0], length // chunk
        rows, scalars, of_at, of_skip, state = specs(states, lambda i: i)
        return pl.pallas_call(
            functools.partial(_fwd_kernel, chunk=chunk, states=states),
            grid=(samples, chunks, blocks),
            in_specs=[scalars, scalars, rows, rows, of_at, of_skip],
            out_specs=[rows, state],
            out_shape=[
                jax.ShapeDtypeStruct(x.shape, jnp.float32),
                jax.ShapeDtypeStruct(
                    (samples, chunks, blocks, states, 8, 128), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((blocks, states, 8, 128),
                                       jnp.float32)],
            compiler_params=params, name=KERNEL_SSM_SCAN_FWD,
        )(b, c, x, delta, at, skip)

    @jax.jit
    def backward(x, delta, at, b, c, skip, s, dy):
        samples, length, blocks = x.shape[:3]
        states, chunks = at.shape[0], length // chunk
        rows, scalars, of_at, of_skip, state = specs(
            states, lambda i: chunks - 1 - i)
        sums = pl.BlockSpec((None, chunk, states, 128),
                            lambda s, i, j: (s, chunks - 1 - i, 0, 0))
        whole_at = pl.BlockSpec((blocks, states, 8, 128),
                                lambda s, i, j: (0, 0, 0, 0))
        whole_skip = pl.BlockSpec((blocks, 8, 128), lambda s, i, j: (0, 0, 0))
        by_lane = jax.ShapeDtypeStruct((samples, length, states, 128),
                                       jnp.float32)
        return pl.pallas_call(
            functools.partial(_bwd_kernel, chunk=chunk, states=states),
            grid=(samples, chunks, blocks),
            in_specs=[scalars, scalars, rows, rows, rows, of_at, of_skip,
                      state],
            out_specs=[rows, rows, sums, sums, whole_at, whole_skip],
            out_shape=[
                jax.ShapeDtypeStruct(x.shape, jnp.float32),
                jax.ShapeDtypeStruct(x.shape, jnp.float32), by_lane, by_lane,
                jax.ShapeDtypeStruct((blocks, states, 8, 128), jnp.float32),
                jax.ShapeDtypeStruct((blocks, 8, 128), jnp.float32)],
            scratch_shapes=[
                pltpu.VMEM((blocks, states, 8, 128), jnp.float32),
                pltpu.VMEM((chunk + 1, states, 8, 128), jnp.float32)],
            compiler_params=params, name=KERNEL_SSM_SCAN_BWD,
        )(b, c, x, delta, dy, at, skip, s)

    return forward, backward


def _forward_pallas(x, delta, at, b, c, skip, chunk):
    """As ``_forward_scan``; the states as the kernels hold them, ``(B,
    chunks, blocks, N, 8, 128)``."""
    y, states = _entries(chunk)[0](
        _blocked(x), _blocked(delta), _blocked(at), _f32(b), _f32(c),
        _blocked(skip))
    return y.reshape(x.shape), states


def _backward_pallas(x, delta, at, b, c, skip, states, dy, chunk):
    """As ``_backward_scan``."""
    import jax.numpy as jnp

    dx, d_delta, db, dc, d_at, d_skip = _entries(chunk)[1](
        _blocked(x), _blocked(delta), _blocked(at), _f32(b), _f32(c),
        _blocked(skip), states, _blocked(dy))
    return (dx.reshape(x.shape), d_delta.reshape(x.shape),
            jnp.moveaxis(d_at, 1, 0).reshape(at.shape), jnp.sum(db, -1),
            jnp.sum(dc, -1), d_skip.reshape(skip.shape))


# --------------------------------------------------------------------------
# public op with custom vjp
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _make_scan(chunk, pallas, keeps=False):
    import jax

    forward = _forward_pallas if pallas else _forward_scan
    backward = _backward_pallas if pallas else _backward_scan

    def run(x, delta, a, b, c, skip):
        with jax.named_scope(KERNEL_SSM_SCAN_FWD):
            y, states = forward(x, _f32(delta), _f32(a).T, b, c, _f32(skip),
                                chunk)
            return y.astype(x.dtype), states

    @jax.custom_vjp
    def op(x, delta, a, b, c, skip):
        return run(x, delta, a, b, c, skip)[0]

    def fwd(x, delta, a, b, c, skip):
        y, states = run(x, delta, a, b, c, skip)
        if keeps:
            # named inside the rule, as the attention op's: a checkpoint
            # that keeps both (``checkpoint_keeps``) runs no second scan
            y, states = kept(KEPT_Y, y), kept(KEPT_STATES, states)
            x, delta, b, c, y, states = jax.lax.optimization_barrier(
                (x, delta, b, c, y, states))
        return y, (x, delta, a, b, c, skip, states)

    def bwd(res, dy):
        x, delta, a, b, c, skip, states = res
        with jax.named_scope(KERNEL_SSM_SCAN_BWD):
            grads = backward(x, _f32(delta), _f32(a).T, b, c, _f32(skip),
                             states, dy, chunk)
            dx, d_delta, d_at, db, dc, d_skip = grads
            return (dx.astype(x.dtype), d_delta.astype(delta.dtype),
                    d_at.T.astype(a.dtype), db.astype(b.dtype),
                    dc.astype(c.dtype), d_skip.astype(skip.dtype))

    op.defvjp(fwd, bwd)
    return op


def selective_scan_recurrent(x, delta, a, b, c, skip):
    """The definition, a row at a time in float32: the recurrence at the top
    of this file by one ``lax.scan`` over the rows.  Shapes as
    ``selective_scan``'s; returns float32."""
    import jax
    import jax.numpy as jnp

    x, delta, a, b, c, skip = map(_f32, (x, delta, a, b, c, skip))

    def step(h, row):
        x, delta, b, c = row
        h = (jnp.exp(delta[..., None] * a) * h
             + (delta * x)[..., None] * b[:, None, :])
        return h, jnp.einsum("bdn,bn->bd", h, c) + skip * x

    first = jnp.zeros((x.shape[0],) + a.shape, jnp.float32)
    _, y = jax.lax.scan(step, first, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, delta, b, c)))
    return jnp.moveaxis(y, 0, 1)


@register("_contrib_selective_scan", aliases=("selective_scan",))
def selective_scan(x, delta, a, b, c, skip, segment_ids=None, chunk=64):
    """The selective state-space scan, chunked.

    ``x (B, L, D)``, ``delta (B, L, D)`` the step (after its softplus; taken
    to float32), ``a (D, N)`` negative, ``b``, ``c (B, L, N)``, ``skip (D,)``.
    Returns ``y (B, L, D)`` in ``x``'s dtype.  ``L`` is a whole number of
    chunks of ``chunk`` rows; any other length, and ``segment_ids`` (a state
    that starts again at a document's boundary is not written), are refused by
    name.  A call is counted once a trace in
    ``mxnet_selective_scan_fwd_calls_total{path}`` (``"pallas"`` or
    ``"scan"``), its chunks in ``mxnet_selective_scan_chunks_total``."""
    from .. import telemetry

    chunk = int(chunk)
    if segment_ids is not None:
        raise MXNetError(
            "selective_scan does not take segment_ids yet: a state that "
            "starts again at a document's boundary is not written")
    if x.ndim != 3 or x.shape[1] % chunk:
        raise MXNetError(
            f"selective_scan: x {x.shape} is (B, L, D) with L a whole number "
            f"of chunks of {chunk} rows; pad the row or pass a chunk that "
            "divides it")
    n = a.shape[-1]
    if not (delta.shape == x.shape and a.shape == (x.shape[2], n)
            and b.shape == c.shape == x.shape[:2] + (n,)
            and skip.shape == x.shape[2:]):
        raise MXNetError(
            "selective_scan: x and delta are (B, L, D), a (D, N), b and c "
            f"(B, L, N) and skip (D,); got x {x.shape}, delta {delta.shape}, "
            f"a {a.shape}, b {b.shape}, c {c.shape}, skip {skip.shape}")
    pallas = use_pallas(x)
    telemetry.SSM_SCAN_CALLS.labels(
        path="pallas" if pallas else "scan").inc()
    telemetry.SSM_SCAN_CHUNKS.inc(x.shape[1] // chunk)
    return _make_scan(chunk, pallas, keeping())(x, delta, a, b, c, skip)


@register("_contrib_ssm_delta", aliases=("ssm_delta",))
def ssm_delta(dt):
    """A state-space block's step: ``softplus(dt)`` of the step projection's
    output (its bias added), computed and returned in float32."""
    import jax

    return jax.nn.softplus(_f32(dt))


@register("_contrib_ssm_rate", aliases=("ssm_rate",))
def ssm_rate(a_log):
    """A state-space block's ``A = -exp(A_log)``, float32."""
    import jax.numpy as jnp

    return -jnp.exp(_f32(a_log))


@register("_contrib_diff_attn_combine", aliases=("diff_attn_combine",))
def diff_attn_combine(o, lq1, lk1, lq2, lk2, scale, lambda_init=0.8,
                      eps=1e-5):
    """What differential attention (arXiv:2410.05258) does with its two
    softmax maps' outputs: ``o (B, 2 P, L, V)``, the first ``P`` heads the
    pairs' first maps times the pairs' values and the last ``P`` their second
    maps times the same values.  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
    lambda_init``; ``(o_1 - lambda o_2)`` a pair, an RMSNorm over its ``V``
    (``scale`` the learned vector), times ``1 - lambda_init``; returns ``(B,
    L, P * V)`` in ``o``'s dtype, float32 inside."""
    import jax
    import jax.numpy as jnp

    @jax.checkpoint     # the backward keeps o, no float32 copy of it
    def combine(o, lq1, lk1, lq2, lk2, scale):
        lam = (jnp.exp(jnp.sum(_f32(lq1) * _f32(lk1)))
               - jnp.exp(jnp.sum(_f32(lq2) * _f32(lk2))) + lambda_init)
        b, heads, l, v = o.shape
        first, second = jnp.split(_f32(o), 2, axis=1)
        d = first - lam * second
        d = d * jax.lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + eps)
        d = d * (_f32(scale) * (1.0 - lambda_init))
        return d.transpose(0, 2, 1, 3).reshape(
            b, l, heads // 2 * v).astype(o.dtype)

    return combine(o, lq1, lk1, lq2, lk2, scale)
