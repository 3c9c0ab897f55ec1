"""Flash attention: Pallas TPU kernel + jax fallback.

Reference scope: MXNet 1.x has NO fused attention — GluonNLP ran full O(L²)
softmax(QKᵀ)V through `src/operator/contrib/transformer.cc`'s interleaved
matmuls (SURVEY.md §6.7).  This module is the net-new TPU capability the
BASELINE Llama config requires: an online-softmax blocked kernel that keeps
the L×L score matrix out of HBM, tiled to the MXU (128-lane blocks), with a
memory-efficient blockwise backward (lax.scan recompute — O(L) memory).

Layout: (batch, heads, seq, head_dim) — q_heads may be a multiple of
kv_heads (GQA).
"""
from __future__ import annotations

import contextlib
import functools
import threading

import numpy as _np

from ..profiler import SCOPE_ATTENTION_BWD, SCOPE_ATTENTION_PLAIN_FWD

NEG_INF = -1e30

_SCOPE = threading.local()   # .value: (mesh, batch_axes) while a sharded
#                              step is being traced, see batch_sharded


@contextlib.contextmanager
def batch_sharded(mesh, batch_axes):
    """Trace-time scope of a step that GSPMD partitions over ``mesh``.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so inside this scope the Pallas forward
    runs under a ``shard_map`` that splits the batch dim over
    ``batch_axes``, the way the step's batch is sharded.  Heads are not
    split: under ``tp`` every rank of that axis computes all heads."""
    prev = getattr(_SCOPE, "value", None)
    _SCOPE.value = (mesh, tuple(batch_axes))
    try:
        yield
    finally:
        _SCOPE.value = prev


def _use_pallas(q):
    """Static gate for the Pallas forward: a head size the kernel tiles, a
    sequence long enough to pay for it, and a TPU to compile it for.  The
    platform is JAX's default backend, not where ``q`` lives (a tracer
    lives nowhere), so a CPU-context call on a TPU host is not covered."""
    import jax

    if q.shape[-1] % 128 != 0 and q.shape[-1] not in (64, 128, 256):
        return False
    return jax.default_backend() == "tpu" and q.shape[-2] >= 256


# --------------------------------------------------------------------------
# jax reference path (CPU tests, short sequences, fallback)
# --------------------------------------------------------------------------
def _mha_with_lse(q, k, v, causal, sm_scale):
    import jax
    import jax.numpy as jnp

    with jax.named_scope(SCOPE_ATTENTION_PLAIN_FWD):
        b, hq, lq, d = q.shape
        hkv = k.shape[1]
        if hq != hkv:
            rep = hq // hkv
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * sm_scale
        if causal:
            lk = k.shape[2]
            mask = jnp.tril(jnp.ones((lq, lk), dtype=bool), k=lk - lq)
            scores = jnp.where(mask, scores, NEG_INF)
        m = scores.max(axis=-1, keepdims=True)
        e = jnp.exp(scores - m)
        denom = e.sum(axis=-1, keepdims=True)
        p = e / denom
        o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
        lse = (m + jnp.log(denom))[..., 0]
    return o, lse


def _mha_reference(q, k, v, causal, sm_scale):
    return _mha_with_lse(q, k, v, causal, sm_scale)[0]


# --------------------------------------------------------------------------
# Pallas forward kernel
# --------------------------------------------------------------------------
def _fa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, causal,
                   sm_scale, seq_k, diag_offset=0):
    """One (q-block × full-K sweep): online softmax accumulation.

    Grid: (batch*heads, num_q_blocks).  Block shapes:
      q_ref (block_q, d) VMEM; k_ref/v_ref (seq_k, d) VMEM (whole K/V row
      for this head — fine at the seq lengths VMEM allows; longer sequences
      ring through context parallelism instead).
    """
    import jax
    import jax.numpy as jnp

    block_q, d = q_ref.shape
    qi = pl_program_id(1)

    q = q_ref[:].astype(jnp.float32) * sm_scale

    m = jnp.full((block_q,), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((block_q,), dtype=jnp.float32)
    acc = jnp.zeros((block_q, d), dtype=jnp.float32)

    num_kb = seq_k // block_k

    def body(kb, carry):
        m, l, acc = carry
        k_blk = pl_load(k_ref, kb, block_k).astype(jnp.float32)
        v_blk = pl_load(v_ref, kb, block_k).astype(jnp.float32)
        s = q @ k_blk.T                                     # (bq, bk)
        if causal:
            q_pos = diag_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + p @ v_blk
        return m_new, l_new, acc_new

    if causal:
        # skip fully-masked K blocks beyond this q block (offset-aware)
        max_kb = jnp.minimum(
            ((qi + 1) * block_q + diag_offset + block_k - 1) // block_k,
            num_kb)
    else:
        max_kb = num_kb
    m, l, acc = jax.lax.fori_loop(0, max_kb, body, (m, l, acc))

    l = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l[:, None]).astype(o_ref.dtype)
    # lse tile is (8, block_q) to satisfy TPU (sublane, lane) tiling; the
    # vector is broadcast across the 8 sublanes and row 0 is read back
    lse = (m + jnp.log(l)).astype(lse_ref.dtype)
    lse_ref[:] = jnp.broadcast_to(lse[None, :], lse_ref.shape)


def pl_program_id(axis):
    from jax.experimental import pallas as pl

    return pl.program_id(axis)


def pl_load(ref, block_idx, block_size):
    from jax.experimental import pallas as pl

    return ref[pl.ds(block_idx * block_size, block_size), :]


def _fa_block_sizes():
    """Forward kernel tile sizes, resolved through the tuning funnel
    (MXNET_FLASH_BLOCK_Q / MXNET_FLASH_BLOCK_KV pins > MXNET_TUNE=1
    stored winners > 128 = one MXU lane tile).  Re-read per call on
    purpose — the op is jit_safe=False exactly so sweeps/trials can
    vary the tile between calls.  Values must divide the padded
    sequence length."""
    try:
        from .. import tuning as _tuning

        return (int(_tuning.resolve("flash_block_q")),
                int(_tuning.resolve("flash_block_kv")))
    except Exception:
        import os

        return (int(os.environ.get("MXNET_FLASH_BLOCK_Q", 128)),
                int(os.environ.get("MXNET_FLASH_BLOCK_KV", 128)))


def _fa_forward_pallas(q, k, v, causal, sm_scale, block_q=None, block_k=None):
    if block_q is None or block_k is None:
        bq, bk = _fa_block_sizes()
        block_q = bq if block_q is None else block_q
        block_k = bk if block_k is None else block_k
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b, h, lq, d = q.shape
    lk = k.shape[2]
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    assert lq % block_q == 0 and lk % block_k == 0, (
        "sequence must be padded to the attention block size")

    grid = (b * h, lq // block_q)
    qf = q.reshape(b * h, lq, d)
    kf = k.reshape(b * h, lk, d)
    vf = v.reshape(b * h, lk, d)

    kernel = functools.partial(_fa_fwd_kernel, block_k=block_k,
                               causal=causal, sm_scale=sm_scale, seq_k=lk,
                               diag_offset=lk - lq)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((None, lk, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((None, lk, d), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((None, 8, block_q), lambda bh, qi: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, lq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 8, lq), jnp.float32),
        ],
        name="mxnet_flash_attention_fwd",
    )(qf, kf, vf)
    return o.reshape(b, h, lq, d), lse[:, 0, :].reshape(b, h, lq)


def _fa_forward(q, k, v, causal, sm_scale):
    """The Pallas forward, per batch shard when a ``batch_sharded`` step
    is being traced."""
    fwd = functools.partial(_fa_forward_pallas, causal=causal,
                            sm_scale=sm_scale)
    scope = getattr(_SCOPE, "value", None)
    if scope is not None:
        from ..parallel.collectives import shard_map_over_batch

        fwd = shard_map_over_batch(fwd, *scope)
    return fwd(q, k, v)


# --------------------------------------------------------------------------
# blockwise backward (jax, O(L) memory via scan recompute)
# --------------------------------------------------------------------------
def _fa_backward_blockwise(q, k, v, o, lse, g, causal, sm_scale,
                           block_k=512):
    import jax
    import jax.numpy as jnp

    with jax.named_scope(SCOPE_ATTENTION_BWD):
        b, h, lq, d = q.shape
        lk = k.shape[2]
        block_k = min(block_k, lk)
        if lk % block_k != 0:
            block_k = lk
        nkb = lk // block_k

        acc_t = jnp.result_type(q.dtype, jnp.float32)
        qf = q.astype(acc_t)
        gf = g.astype(acc_t)
        of = o.astype(acc_t)
        delta = jnp.sum(of * gf, axis=-1)                      # (b,h,lq)

        kb = k.reshape(b, h, nkb, block_k, d).astype(acc_t)
        vb = v.reshape(b, h, nkb, block_k, d).astype(acc_t)

        q_pos = jnp.arange(lq)

        def step(dq, idx):
            kblk = kb[:, :, idx]                               # (b,h,bk,d)
            vblk = vb[:, :, idx]
            s = jnp.einsum("bhqd,bhkd->bhqk", qf, kblk) * sm_scale
            if causal:
                # same diagonal offset as the forward (q_i attends keys up to
                # i + lk - lq when lengths differ, e.g. decode)
                k_pos = idx * block_k + jnp.arange(block_k)
                mask = (q_pos[:, None] + (lk - lq)) >= k_pos[None, :]
                s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp(s - lse[..., None])                    # (b,h,q,bk)
            dv = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
            dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vblk)
            ds = p * (dp - delta[..., None]) * sm_scale
            dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
            dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kblk)
            return dq, (dk, dv)

        dq0 = jnp.zeros_like(qf)
        dq, (dks, dvs) = jax.lax.scan(step, dq0, jnp.arange(nkb))
        dk = jnp.moveaxis(dks, 0, 2).reshape(b, h, lk, d)
        dv = jnp.moveaxis(dvs, 0, 2).reshape(b, h, lk, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# --------------------------------------------------------------------------
# public op with custom vjp
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _make_flash(causal, sm_scale_key):
    import jax
    import jax.numpy as jnp

    sm_scale = float(sm_scale_key)

    @jax.custom_vjp
    def flash(q, k, v):
        return _dispatch_fwd(q, k, v)[0]

    def _dispatch_fwd(q, k, v):
        if _use_pallas(q):
            o, lse = _fa_forward(q, k, v, causal, sm_scale)
        else:
            o, lse = _mha_with_lse(q, k, v, causal, sm_scale)
        return o, (q, k, v, o, lse)

    def fwd(q, k, v):
        o, res = _dispatch_fwd(q, k, v)
        return o, res

    def bwd(res, g):
        q, k, v, o, lse = res
        return _fa_backward_blockwise(q, k, v, o, lse, g, causal, sm_scale)

    flash.defvjp(fwd, bwd)
    return flash


def flash_attention(q, k, v, causal=False, sm_scale=None):
    """q (B,Hq,Lq,D); k,v (B,Hkv,Lk,D) with Hq % Hkv == 0 (GQA)."""
    import jax.numpy as jnp

    d = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / _np.sqrt(d)
    hq, hkv = q.shape[1], k.shape[1]
    if hq != hkv:
        # GQA expansion OUTSIDE the custom_vjp: jnp.repeat's own vjp folds
        # the expanded-head grads back onto the kv heads
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    fn = _make_flash(bool(causal), float(sm_scale))
    return fn(q, k, v)


# registry entry --------------------------------------------------------------
from .registry import register


# jit_safe=False: the op re-reads MXNET_FLASH_BLOCK_{Q,KV} per call (the
# bench block sweep depends on that), so it must not be frozen into a cached
# eager executable; per-call overhead is irrelevant at attention sizes
@register("_contrib_flash_attention", aliases=("flash_attention",),
          jit_safe=False)
def flash_attention_op(q, k, v, causal=False, sm_scale=None):
    """Fused scaled-dot-product attention (net-new vs reference; the TPU
    answer to contrib/transformer.cc's unfused attention path)."""
    return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale)
