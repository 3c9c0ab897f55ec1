"""Flash attention: Pallas TPU kernel + jax fallback.

Reference scope: MXNet 1.x has NO fused attention — GluonNLP ran full O(L²)
softmax(QKᵀ)V through `src/operator/contrib/transformer.cc`'s interleaved
matmuls (SURVEY.md §6.7).  This module is the net-new TPU capability the
BASELINE Llama config requires: a blocked softmax kernel that keeps the L×L
score matrix out of HBM, fed to the MXU in the input's dtype with tiles
chosen from the call's shape (`_fa_block_sizes`), and a backward kernel that
recomputes the probabilities a tile at a time in VMEM from the saved
log-sum-exp (`_fa_bwd_kernel`: the live tile pairs alone, five products a
pair on operands in the input's dtype, float32 accumulation).  Under
segment ids both kernels walk the tiles the batch's documents show, by a
table built on the device from the ids (`_segment_tiles`).  Where the
kernels do not apply (no TPU, rows under 256, a length that does not divide
into tiles) the forward is plain jax and the backward a blockwise lax.scan
over the same tiles on the same operands (`_fa_backward_blockwise`, O(L)
memory).

Layout: (batch, heads, seq, head_dim) — q_heads may be a multiple of
kv_heads (GQA): K and V are never repeated in HBM for the kernels, whose
block maps send a group of query heads to its one key-value head.
"""
from __future__ import annotations

import contextlib
import functools
import threading

import numpy as _np

from ..profiler import (KERNEL_ATTENTION_FWD, KERNEL_SUFFIX_SEGMENTS,
                        KERNEL_SUFFIX_WINDOW, SCOPE_ATTENTION_BWD,
                        SCOPE_ATTENTION_PLAIN_FWD)

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


def _padded_width(d):
    """The width the kernels' q and k tiles take for heads of ``d``: ``d``
    itself where it is 64 or whole lane tiles, the next whole lane tile for
    a width of whole half tiles above one (192 -> 256: latent attention's
    128 + 64; the pad is zeros, which add nothing to a score), else None."""
    if d == 64 or d % 128 == 0:
        return d
    return d + 64 if d > 128 and d % 64 == 0 else None


def _use_pallas(q, v=None):
    """Static gate for the Pallas forward, and the first half of the
    backward's (``_use_pallas_bwd``): head sizes the kernels tile (q and
    k's, which may be padded to one, ``_padded_width``, and ``v``'s, which
    is never padded; ``v`` None: as wide as ``q``), a sequence long enough
    to pay for them, and a TPU to compile them for.  The platform is JAX's
    default backend, not where ``q`` lives (a tracer lives nowhere), so a
    CPU-context call on a TPU host is not covered."""
    import jax

    dv = q.shape[-1] if v is None else v.shape[-1]
    if _padded_width(q.shape[-1]) is None or _padded_width(dv) != dv:
        return False
    return jax.default_backend() == "tpu" and q.shape[-2] >= 256


def _pad_width(x, width):
    """``x`` with zeros after its last axis up to ``width``."""
    import jax.numpy as jnp

    more = width - x.shape[-1]
    return x if not more else jnp.pad(
        x, [(0, 0)] * (x.ndim - 1) + [(0, more)])


# --------------------------------------------------------------------------
# masks: one predicate over positions, shared by the kernel, the plain path
# and the backward; no (lq, lk) array leaves the trace of the first and last
# --------------------------------------------------------------------------
BLOCK_DIFFUSION = "block_diffusion"
WINDOW = "window"


def _mask_key(mask, mask_block, causal, window=0):
    """The static mask of a call: None, ``(BLOCK_DIFFUSION, B)`` or
    ``(WINDOW, W)``."""
    from ..base import MXNetError

    if mask is None:
        return None
    if mask not in (BLOCK_DIFFUSION, WINDOW):
        raise MXNetError(f"flash_attention: unknown mask {mask!r}; known: "
                         f"{BLOCK_DIFFUSION!r}, {WINDOW!r}")
    if causal:
        raise MXNetError("flash_attention: a mask takes the place of causal")
    if mask == WINDOW:
        if int(window) < 1:
            raise MXNetError("flash_attention: mask='window' needs window, "
                             "the number of keys a query sees")
        return (WINDOW, int(window))
    if int(mask_block) < 1:
        raise MXNetError("flash_attention: mask='block_diffusion' needs "
                         "mask_block, the block length")
    return (BLOCK_DIFFUSION, int(mask_block))


def _block_of(xp, pos, half, block):
    """Diffusion block of a position of the row ``[noised ; clean]``, and
    whether it lies in the noised half."""
    noised = pos < half
    return xp.where(noised, pos, pos - half) // block, noised


def _visible(xp, q_pos, k_pos, causal, mask, lq, lk, ids=None):
    """May query row ``q_pos`` see key column ``k_pos``?  Broadcasts; None
    where every pair is visible.  ``xp`` is numpy or jax.numpy.  With
    ``ids`` = (the queries' segment ids, the keys'), shaped as the
    positions are, a pair is seen only inside one document: the mask's
    predicate and the ids' equality.

    ``causal``: keys up to the query's own position, the diagonal moved by
    ``lk - lq`` (decode).  ``(BLOCK_DIFFUSION, B)``: the training mask of
    block diffusion (Arriola et al. 2025) over a row of a noised copy
    followed by the clean copy, blocks of ``B``: a noised query sees the
    noised keys of its own block and the clean keys of earlier blocks; a
    clean query sees the clean keys of its own and earlier blocks.
    ``(WINDOW, W)``: causal, and of the keys up to its own position a query
    sees the last ``W`` alone (``i - W < j <= i``, the diagonal moved as
    ``causal`` moves it)."""
    if ids is not None:
        return _visible(xp, q_pos, k_pos, causal, mask, lq, lk) \
            & (ids[0] == ids[1])
    if mask is not None and mask[0] == WINDOW:
        last = q_pos + (lk - lq)
        return (k_pos <= last) & (k_pos > last - mask[1])
    if mask is not None:
        half = lk // 2
        qb, q_noised = _block_of(xp, q_pos, half, mask[1])
        kb, k_noised = _block_of(xp, k_pos, half, mask[1])
        # as two comparisons of integers (Mosaic selects no booleans): a
        # noised key is seen by the noised queries of its block, a clean
        # key by the queries of later blocks and by the clean ones of its own
        far = 1 << 30
        return ((xp.where(k_noised, kb, -2) == xp.where(q_noised, qb, -1))
                | (xp.where(k_noised, far, kb)
                   < xp.where(q_noised, qb, qb + 1)))
    if causal:
        return q_pos + (lk - lq) >= k_pos
    return None


@functools.lru_cache(maxsize=64)
def _tile_visibility(causal, mask, lq, lk, block_q, block_k):
    """numpy bool ``(some, every)``, each ``(lq / block_q, lk / block_k)``:
    tiles in which some pair is visible, and in which every pair is.  From
    the predicate itself, a strip of query rows at a time; static per
    shape, so computed once."""
    nq, nk = lq // block_q, lk // block_k
    if mask is None and not causal:
        return _np.ones((nq, nk), bool), _np.ones((nq, nk), bool)
    k_pos = _np.arange(lk)[None, :]
    some = _np.empty((nq, nk), bool)
    every = _np.empty((nq, nk), bool)
    for i in range(nq):
        q_pos = _np.arange(i * block_q, (i + 1) * block_q)[:, None]
        seen = _visible(_np, q_pos, k_pos, causal, mask, lq, lk)
        seen = seen.reshape(block_q, nk, block_k)
        some[i] = seen.any(axis=(0, 2))
        every[i] = seen.all(axis=(0, 2))
    return some, every


def _live_tiles(causal, mask, lq, lk, block_q, block_k):
    """numpy bool ``(lq / block_q, lk / block_k)``: tiles in which some pair
    is visible."""
    return _tile_visibility(causal, mask, lq, lk, block_q, block_k)[0]


def _sample_of(bh, heads):
    """The sample of row ``bh`` of the flattened (batch x heads): a
    truncating divide (``bh`` is never negative), one instruction where
    ``//`` lowers to a floor divide's compares and selects, which Mosaic
    lowers anew in every block spec that holds one (PERF.md section 6,
    PR 33)."""
    import jax

    return jax.lax.div(bh, heads)


def _kv_row(bh, rep):
    """The row of the flattened (batch x key-value heads) that row ``bh`` of
    the flattened (batch x query heads) reads, ``rep`` query heads to a
    key-value head: ``b * h + head`` over ``rep`` is ``b * hkv + head //
    rep``.  The group lives in the kernels' block maps, not in HBM:
    consecutive heads of a group name one block of K and of V.  Truncating,
    as ``_sample_of``; a head of its own (``rep`` 1) keeps ``bh`` itself and
    its kernels the maps they had."""
    import jax

    return bh if rep == 1 else jax.lax.div(bh, rep)


def _per_query_head(x, heads):
    """``x`` (b, hkv, l, d) with every key-value head repeated for the query
    heads that share it, ``(b, heads, l, d)``: what the paths off the
    kernels compute on."""
    import jax.numpy as jnp

    hkv = x.shape[1]
    return x if hkv == heads else jnp.repeat(x, heads // hkv, axis=1)


def _fold_group(dx, hkv):
    """Gradients a query head ``(b, h, l, d)`` summed onto the ``hkv``
    key-value heads their groups share: the ``reduce_sum`` that
    ``_per_query_head``'s transpose is, in ``dx``'s dtype, so that a call
    on ``hkv`` heads and the same call on K and V repeated by hand give
    one ``dk`` and ``dv``, bit for bit."""
    import jax

    b, h, l, d = dx.shape
    if h == hkv:
        return dx
    return jax.lax.reduce_sum(dx.reshape(b, hkv, h // hkv, l, d), axes=(2,))


def _segment_tiles(seg, causal, mask, lq, lk, block_q, block_k):
    """The live tiles of a call under segment ids, from that batch's ids on
    the device: bool ``(batch, lq / block_q, lk / block_k)``.  A tile is
    live iff the static mask shows some pair in it (``_live_tiles``) and the
    id ranges of its queries and of its keys meet.  Where the ids are runs
    in rising order that is exactly "some pair of one document is seen";
    for any other ids (runs out of order, a document in two places) it
    holds more, never less: no tile with a visible pair is dropped, and
    ``_visible`` alone decides a pair.  ``seg`` is the ``(batch, lk)``
    operand, so one program serves every batch."""
    import jax.numpy as jnp

    b = seg.shape[0]

    def ends(ids, block):
        tiles = ids.reshape(b, -1, block)
        return tiles.min(axis=-1), tiles.max(axis=-1)

    (qmin, qmax), (kmin, kmax) = ends(seg[:, lk - lq:], block_q), ends(seg,
                                                                      block_k)
    meet = (jnp.maximum(qmin[:, :, None], kmin[:, None, :])
            <= jnp.minimum(qmax[:, :, None], kmax[:, None, :]))
    return meet & _live_tiles(causal, mask, lq, lk, block_q, block_k)


def _fa_fwd_bounds(live):
    """int32 ``(2, batch * q tiles)``: the first live K tile of every q
    tile's row of ``live`` (``_segment_tiles``) and one past the last, a
    sample after the other: what the forward kernel under ids walks.  The
    hull holds every live tile (and, where the ids are not rising runs, dead
    ones between them); a row without a live tile walks none."""
    import jax.numpy as jnp

    nk = live.shape[2]
    lo = jnp.argmax(live, axis=2)
    hi = jnp.where(live.any(axis=2), nk - jnp.argmax(live[:, :, ::-1], axis=2),
                   lo)
    return jnp.stack([lo, hi]).astype(jnp.int32).reshape(2, -1)


class _Mask:
    """A call's mask as every path takes it, built once in
    ``flash_attention`` and handed on whole: ``key``, the static part
    (``_mask_key``: None, ``(BLOCK_DIFFUSION, B)`` or ``(WINDOW, W)``), and
    ``seg``, the operand a packed row brings or None: (batch, lk) int32
    segment ids, a document a run of equal ids, the queries being the last
    ``lq`` keys.  ``_visible`` is the one predicate over both.  A path
    called on its own (tests, ``context_parallel``) may be given the key
    alone."""

    __slots__ = ("key", "seg")

    def __init__(self, key=None, seg=None):
        self.key, self.seg = key, seg

    @classmethod
    def of(cls, mask):
        return mask if isinstance(mask, cls) else cls(mask)

    @property
    def operands(self):
        """What of the description is traced: ``()`` or ``(seg,)``."""
        return () if self.seg is None else (self.seg,)

    def over(self, *operands):
        """The description over ``operands`` in place of its own: a batch
        shard's, or those a ``custom_vjp`` hands back."""
        return _Mask(self.key, *operands)

    def ids(self, lq):
        """``(queries' ids (b, 1, lq, 1), keys' ids (b, 1, 1, lk))``, shaped
        to broadcast against the scores, or None without ``seg``."""
        if self.seg is None:
            return None
        seg = self.seg
        return seg[:, None, seg.shape[1] - lq:, None], seg[:, None, None, :]

    def label(self, causal):
        """The value of a counter's ``mask`` label."""
        return (self.key[0] if self.key else "causal" if causal else "none") \
            + (KERNEL_SUFFIX_SEGMENTS if self.seg is not None else "")

    def check(self, causal, batch, lk):
        """Segment ids go with ``causal`` and with the window (every query
        then sees itself, so no row is empty): one id a key."""
        from ..base import MXNetError

        if self.seg is None:
            return
        if self.key is not None and self.key[0] != WINDOW:
            raise MXNetError(f"flash_attention: segment_ids do not go with "
                             f"mask {self.key[0]!r}; they confine causal=True "
                             f"and mask={WINDOW!r}")
        if self.key is None and not causal:
            raise MXNetError("flash_attention: segment_ids confine "
                             f"causal=True and mask={WINDOW!r}; a call that "
                             "is neither has no diagonal for a query to see "
                             "itself on")
        if tuple(self.seg.shape) != (batch, lk):
            raise MXNetError(f"flash_attention: segment_ids are (batch, lk) "
                             f"= ({batch}, {lk}); got "
                             f"{tuple(self.seg.shape)}")


def _check_mask_shape(mask, lq, lk):
    from ..base import MXNetError

    if mask is not None and mask[0] == WINDOW:
        if lq > lk:
            raise MXNetError(f"flash_attention: mask {mask} wants the "
                             f"queries to be the last of the keys; got lq "
                             f"{lq}, lk {lk}")
    elif mask is not None and (lq != lk or lk % (2 * mask[1])):
        raise MXNetError(
            f"flash_attention: mask {mask} wants a row of a noised and a "
            f"clean copy, each a whole number of blocks; got lq {lq}, lk {lk}")


# --------------------------------------------------------------------------
# jax reference path (CPU tests, short sequences, fallback)
# --------------------------------------------------------------------------
def _mha_with_lse(q, k, v, causal, sm_scale, mask=None):
    import jax
    import jax.numpy as jnp

    mask = _Mask.of(mask)
    with jax.named_scope(SCOPE_ATTENTION_PLAIN_FWD):
        b, hq, lq, d = q.shape
        k, v = _per_query_head(k, hq), _per_query_head(v, hq)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * sm_scale
        lk = k.shape[2]
        seen = _visible(jnp, jnp.arange(lq)[:, None], jnp.arange(lk)[None, :],
                        causal, mask.key, lq, lk, mask.ids(lq))
        if seen is not None:
            scores = jnp.where(seen, scores, NEG_INF)
        m = scores.max(axis=-1, keepdims=True)
        e = jnp.exp(scores - m)
        denom = e.sum(axis=-1, keepdims=True)
        p = e / denom
        o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
        lse = (m + jnp.log(denom))[..., 0]
    return o, lse


def _mha_reference(q, k, v, causal, sm_scale, mask=None):
    return _mha_with_lse(q, k, v, causal, sm_scale, mask)[0]


# --------------------------------------------------------------------------
# Pallas forward kernel
# --------------------------------------------------------------------------
# q @ k.T as one dot_general contracting both last dims: no transposed tile
_NT_DIMS = (((1,), (1,)), ((), ()))
_NN_DIMS = (((1,), (0,)), ((), ()))


def _operand_precision(dtype):
    """The ``precision`` of a product whose operands arrive in ``dtype``.
    float32 operands follow the process's matmul precision as they always
    have (None); narrower ones are one exact MXU pass, and Mosaic refuses
    them the float32 contraction that the process default (highest) asks
    for ("Bad lhs type")."""
    import jax
    import jax.numpy as jnp

    return None if dtype == jnp.float32 else jax.lax.Precision.DEFAULT


# what the default tile choice may spend on one grid step's float32 score
# tile and its q / k / v operand tiles; the exp'd copy, the buffers' second
# halves and the resident K/V row come on top and stay under Mosaic's
# scoped VMEM limit (16 MiB on a v5e) with this
_TILE_VMEM_BUDGET = 4 << 20


def _bd_tile_ranges(r0, r1, half, block, block_k):
    """K tiles a q tile of rows ``[r0, r1)`` can see under the
    block-diffusion mask: ``(a_lo, a_hi, c_lo, c_hi)``, tiles ``[a_lo,
    a_hi)`` among the noised keys (its own blocks) and ``[c_lo, c_hi)``
    among the clean ones (from the clean half's start up to its last row's
    block), the second range starting where the first ended if they meet.
    Integer arithmetic that holds for Python ints and traced scalars."""
    import jax.numpy as jnp

    def ceil_div(a, b):
        return (a + b - 1) // b

    n_end = jnp.minimum(r1, half)
    has_noised = r0 < half
    a_lo = jnp.where(has_noised, (r0 // block) * block // block_k, 0)
    a_hi = jnp.where(has_noised,
                     ceil_div(ceil_div(n_end, block) * block, block_k), 0)
    # clean columns seen: by the noised rows the blocks before their last
    # row's, by the clean rows their last row's block too
    seen = jnp.maximum(
        jnp.where(has_noised, (n_end - 1) // block * block, 0),
        jnp.where(r1 > half, ((r1 - 1 - half) // block + 1) * block, 0))
    c_lo = half // block_k
    c_hi = jnp.where(seen > 0, ceil_div(half + seen, block_k), c_lo)
    return a_lo, a_hi, jnp.maximum(c_lo, a_hi), c_hi


def _window_tile_range(r0, r1, offset, window, block_k, num_kb):
    """K tiles ``[lo, hi)`` a q tile of rows ``[r0, r1)`` can see under a
    window of ``window`` keys, the diagonal moved by ``offset``: from the
    tile of its first row's first key to that of its last row's own key.
    Holds for Python ints and traced scalars."""
    import jax.numpy as jnp

    lo = jnp.maximum(r0 + offset - window + 1, 0) // block_k
    hi = jnp.minimum((r1 + offset + block_k - 1) // block_k, num_kb)
    return lo, hi


def _kernel_name(base, mask):
    """A kernel's name in a trace: a window call's tells it from a full
    call's, a call's under segment ids from one without; the others keep
    ``base``."""
    mask = _Mask.of(mask)
    if mask.key is not None and mask.key[0] == WINDOW:
        base += KERNEL_SUFFIX_WINDOW
    return base + KERNEL_SUFFIX_SEGMENTS if mask.seg is not None else base


def _fa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, causal,
                   sm_scale, seq_k, diag_offset=0, mask=None, qseg_ref=None,
                   kseg_ref=None, bounds_ref=None, heads=None):
    """One q block against its head's whole K/V row.  Under segment ids
    (``_fa_fwd_kernel_segments``) ``qseg_ref`` (block_q, 1) holds the q
    block's ids as a column and ``kseg_ref`` (8, seq_k) the row's along the
    lanes (8 sublanes alike): every tile walked is then masked by
    ``_visible`` with them.  Which K tiles are walked comes from that
    batch's ids too: ``bounds_ref`` (2, samples x q blocks) in SMEM
    (``_fa_fwd_bounds``) holds the first live K tile of the q block's row of
    the table (``_segment_tiles``) and one past the last, in place of the
    causal bound and the window's range; a sample's bounds serve its
    ``heads`` heads.  A tile left out held no visible pair, and such a tile
    adds exact zeros (walked before the row's first visible key, the
    rescaling wipes it; after it, ``exp`` gives 0): the results are those of
    walking every tile the mask alone shows, bit for bit.

    Grid: (batch*heads, num_q_blocks).  Block shapes:
      q_ref (block_q, d) VMEM; k_ref (seq_k, d) and v_ref (seq_k, dv) VMEM
      (whole K/V row for this head; ``v`` and the output may be narrower
      or wider than ``q`` and ``k`` — fine at the seq lengths VMEM allows; longer sequences
      ring through context parallelism instead).

    The matmuls take their operands in the input's dtype and accumulate in
    float32 (bf16 products are exact in float32; float32 inputs keep
    float32 operands), and ``p`` goes to ``v``'s dtype for ``p @ v`` as in
    ``_mha_with_lse``.  Scores, max, exp, sum, accumulator and lse are
    float32, the statistics ``(block_q, 1)`` so that they broadcast over
    the score tile's lanes without a relayout.  A K row that is one block
    takes the plain softmax (no rescale); several blocks take the online
    update, unrolled when nothing is masked, and skipping the K blocks no
    row of the q block can see when causal or under ``mask`` (``_visible``
    is the predicate, evaluated here from a column of row positions and a
    row of column positions): under a window the one range of
    ``_window_tile_range``, under block diffusion the two of
    ``_bd_tile_ranges``.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    block_q = q_ref.shape[0]
    qi = pl.program_id(1)
    num_kb = seq_k // block_k
    segmented = qseg_ref is not None

    q = q_ref[:]
    precision = _operand_precision(q.dtype)
    # a power of two scales q exactly in any float dtype (1/8 at head size
    # 64): one multiply a q element in place of one a score
    scale_q = _np.frexp(sm_scale)[0] == 0.5
    if scale_q:
        q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)

    def scores(kb):
        k_blk = k_ref[pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, _NT_DIMS, precision=precision,
                                preferred_element_type=jnp.float32)
        if not scale_q:
            s = s * sm_scale
        if causal and not segmented:
            q_pos = diag_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        elif causal or mask is not None:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            ids = (qseg_ref[...], kseg_ref[:1, pl.ds(kb * block_k, block_k)]) \
                if segmented else None
            s = jnp.where(_visible(jnp, q_pos, k_pos, causal, mask,
                                   seq_k - diag_offset, seq_k, ids), s,
                          NEG_INF)
        return s

    def weighted_v(p, kb):
        v_blk = v_ref[pl.ds(kb * block_k, block_k), :]
        return jax.lax.dot(p.astype(v_blk.dtype), v_blk, precision=precision,
                           preferred_element_type=jnp.float32)

    if num_kb == 1:   # a mask included: its tiles are all live at this size
        s = scores(0)
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = p.sum(axis=-1, keepdims=True)
        acc = weighted_v(p, 0)
    else:
        def body(kb, carry):
            m, l, acc = carry
            s = scores(kb)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            return (m_new, l * alpha + p.sum(axis=-1, keepdims=True),
                    acc * alpha + weighted_v(p, kb))

        carry = (jnp.full((block_q, 1), NEG_INF, dtype=jnp.float32),
                 jnp.zeros((block_q, 1), dtype=jnp.float32),
                 jnp.zeros((block_q, v_ref.shape[-1]), dtype=jnp.float32))
        if segmented:
            # the live K tiles of this sample's ids (a superset: their hull)
            at = _sample_of(pl.program_id(0), heads) * pl.num_programs(1) + qi
            carry = jax.lax.fori_loop(bounds_ref[0, at], bounds_ref[1, at],
                                      body, carry)
        elif causal:
            # skip fully-masked K blocks beyond this q block (offset-aware)
            max_kb = jnp.minimum(
                ((qi + 1) * block_q + diag_offset + block_k - 1) // block_k,
                num_kb)
            carry = jax.lax.fori_loop(0, max_kb, body, carry)
        elif mask is not None and mask[0] == WINDOW:
            carry = jax.lax.fori_loop(
                *_window_tile_range(qi * block_q, (qi + 1) * block_q,
                                    diag_offset, mask[1], block_k, num_kb),
                body, carry)
        elif mask is not None:
            a_lo, a_hi, c_lo, c_hi = _bd_tile_ranges(
                qi * block_q, (qi + 1) * block_q, seq_k // 2, mask[1],
                block_k)
            carry = jax.lax.fori_loop(a_lo, a_hi, body, carry)
            carry = jax.lax.fori_loop(c_lo, c_hi, body, carry)
        else:
            for kb in range(num_kb):
                carry = body(kb, carry)
        m, l, acc = carry

    l = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    # lse tile is (8, block_q) to satisfy TPU (sublane, lane) tiling; the
    # column goes to a row once a q block, is broadcast across the 8
    # sublanes and row 0 is read back
    lse = (m + jnp.log(l)).astype(lse_ref.dtype)
    lse_ref[:] = jnp.broadcast_to(lse.reshape(1, block_q), lse_ref.shape)


def _fa_fwd_kernel_segments(bounds_ref, q_ref, k_ref, v_ref, qseg_ref,
                            kseg_ref, o_ref, lse_ref, **static):
    """``_fa_fwd_kernel`` of a call under segment ids: the bounds prefetched
    to SMEM come first and its two refs of ids after the operands, as
    ``_fa_forward_pallas`` lists them."""
    _fa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, qseg_ref=qseg_ref,
                   kseg_ref=kseg_ref, bounds_ref=bounds_ref, **static)


def _largest_tile(length):
    """The largest of 512 / 256 / 128 that divides ``length``, or None."""
    return next((b for b in (512, 256, 128) if length % b == 0), None)


def _fa_block_sizes(lq, lk, d, itemsize):
    """Forward kernel tile sizes, from what the call shows.  Measured on a
    v5e (PERF.md section 6, PR 25): the larger q tile wins up to 512, and
    one pass over the whole K row beats the online update while the score
    tile stays small.  So ``block_q`` is the largest of 512 / 256 / 128
    that divides ``lq`` (else ``lq``), and ``block_k`` the whole K row
    where one grid step's float32 score tile and operand tiles fit
    ``_TILE_VMEM_BUDGET``, else the largest of 512 / 256 / 128 that
    divides ``lk`` and fits."""
    block_q = _largest_tile(lq) or lq

    def fits(bk):
        return (block_q * bk * 4 + (block_q + 2 * bk) * d * itemsize
                <= _TILE_VMEM_BUDGET)

    k_tiles = [lk] + [b for b in (512, 256, 128) if b < lk and lk % b == 0]
    block_k = next((b for b in k_tiles if fits(b)), k_tiles[-1])
    return block_q, block_k


# Mosaic's scoped VMEM limit where a call states none, and the most a kernel
# may state for itself (a v5e core has 128 MiB)
_VMEM_DEFAULT_LIMIT = 16 << 20
_VMEM_MOST = 96 << 20


def _fa_fwd_vmem_limit(lk, d, itemsize, block_q, segmented, dv=None):
    """The scoped VMEM limit a forward call states, or None.  One grid step
    may hold two buffers of the head's whole K and V rows, twice
    ``_TILE_VMEM_BUDGET`` (what the tile choice may spend on a step's score
    and operand tiles, and as much again for their copies and second
    buffers), and under segment ids two buffers of the keys' ids (8
    sublanes) and of the q tile's column (a lane tile wide in VMEM).  A K
    row too long for Mosaic's default limit states its own, as the backward
    does (16,384 keys of 128 in bf16 are 16 MiB in their two buffers);
    every call under the default states none and is compiled as it always
    was (8,192 keys of 128 in bf16 stand exactly at it: tested)."""
    ids = 2 * 4 * (8 * lk + 128 * block_q) if segmented else 0
    rows = d + (d if dv is None else dv)    # a K row and a V row
    need = 2 * lk * rows * itemsize + 2 * _TILE_VMEM_BUDGET + ids
    return None if need <= _VMEM_DEFAULT_LIMIT else min(need, _VMEM_MOST)


def _fa_forward_pallas(q, k, v, causal, sm_scale, block_q=None, block_k=None,
                       mask=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    mask = _Mask.of(mask)
    seg = mask.seg
    # q and k at a width the tiles take (zeros after a head's own), v and
    # the output at v's own
    q, k = (_pad_width(x, _padded_width(x.shape[-1])) for x in (q, k))
    b, h, lq, d = q.shape
    lk, dv = k.shape[2], v.shape[-1]
    if block_q is None or block_k is None:
        bq, bk = _fa_block_sizes(lq, lk, d, q.dtype.itemsize)
        block_q = bq if block_q is None else block_q
        block_k = bk if block_k is None else block_k
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    assert lq % block_q == 0 and lk % block_k == 0, (
        "sequence must be padded to the attention block size")

    # K and V keep their own heads: a group of rep query heads reads one row
    hkv = k.shape[1]
    rep = h // hkv
    grid = (b * h, lq // block_q)
    qf = q.reshape(b * h, lq, d)
    kf = k.reshape(b * hkv, lk, d)
    vf = v.reshape(b * hkv, lk, dv)

    static = dict(block_k=block_k, causal=causal, sm_scale=sm_scale, seq_k=lk,
                  diag_offset=lk - lq, mask=mask.key)
    # the index maps take the grid's indices and, under ids, the prefetched
    # bounds after them
    in_specs = [
        pl.BlockSpec((None, block_q, d), lambda bh, qi, *_: (bh, qi, 0)),
        pl.BlockSpec((None, lk, d),
                     lambda bh, qi, *_: (_kv_row(bh, rep), 0, 0)),
        pl.BlockSpec((None, lk, dv),
                     lambda bh, qi, *_: (_kv_row(bh, rep), 0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((None, block_q, dv), lambda bh, qi, *_: (bh, qi, 0)),
        pl.BlockSpec((None, 8, block_q), lambda bh, qi, *_: (bh, 0, qi)),
    ]
    operands = [qf, kf, vf]
    limit = _fa_fwd_vmem_limit(lk, d, q.dtype.itemsize, block_q,
                               seg is not None, dv)
    params = {} if limit is None else {
        "compiler_params": pltpu.CompilerParams(vmem_limit_bytes=limit)}
    if seg is None:
        kernel = functools.partial(_fa_fwd_kernel, **static)
        params.update(grid=grid, in_specs=in_specs, out_specs=out_specs)
    else:
        # a sample's ids serve its h heads: the queries' as a column a q
        # block, the keys' whole along the lanes, and the K tiles its q
        # blocks walk, from the table of its live tiles
        kernel = functools.partial(_fa_fwd_kernel_segments, heads=h, **static)
        in_specs += [
            pl.BlockSpec((None, block_q, 1),
                         lambda bh, qi, *_: (_sample_of(bh, h), qi, 0)),
            pl.BlockSpec((None, 8, lk),
                         lambda bh, qi, *_: (_sample_of(bh, h), 0, 0)),
        ]
        live = _segment_tiles(seg, causal, mask.key, lq, lk, block_q, block_k)
        operands = [_fa_fwd_bounds(live)] + operands + [
            seg[:, lk - lq:, None],
            jnp.broadcast_to(seg[:, None, :], (b, 8, lk))]
        params.update(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs))
    o, lse = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((b * h, lq, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, 8, lq), jnp.float32),
        ],
        name=_kernel_name(KERNEL_ATTENTION_FWD, mask),
        **params,
    )(*operands)
    return o.reshape(b, h, lq, dv), lse[:, 0, :].reshape(b, h, lq)


def _per_batch_shard(fn, sharded):
    """``fn`` per batch shard of ``sharded``, the ``(mesh, batch_axes)`` of
    the ``batch_sharded`` step being traced, or ``fn`` itself under none."""
    if sharded is None:
        return fn
    from ..parallel.collectives import shard_map_over_batch

    return shard_map_over_batch(fn, *sharded)


def _fa_forward(q, k, v, causal, sm_scale, mask=None, sharded=None):
    """The Pallas forward, per batch shard under ``sharded`` (the mask's
    operands, where it has any, sharded with the batch)."""
    mask = _Mask.of(mask)

    def fwd(q, k, v, *operands):
        return _fa_forward_pallas(q, k, v, causal, sm_scale,
                                  mask=mask.over(*operands))

    return _per_batch_shard(fwd, sharded)(q, k, v, *mask.operands)


# --------------------------------------------------------------------------
# Pallas backward kernel
# --------------------------------------------------------------------------
# a.T @ b as one dot_general contracting both first dims
_TN_DIMS = (((0,), (0,)), ((), ()))

# flags of a row of the backward's table of tile pairs; under segment ids a
# dead row (past a sample's live pairs) carries that flag alone.  No flag
# says whether the mask hides some pair of a tile: every pair walked is
# compared, with ids and without (``_fa_bwd_kernel``)
_FIRST_OF_K, _LAST_OF_K, _DEAD = 1, 2, 4


def _fa_bwd_block_sizes(lq, lk):
    """Backward kernel tile sizes, from the call's shape alone: the largest
    of 512 / 256 / 128 that divides each length, or None where none does
    (the scores are held transposed, so the q tile is their lane dim and
    has to be whole lane tiles)."""
    return _largest_tile(lq), _largest_tile(lk)


def _fa_bwd_vmem_bytes(lq, d, itemsize, block_q, block_k, dv=None):
    """What one grid step of the backward kernel holds in VMEM: the row of
    ``dq`` (float32 accumulator, and the output's two buffers), two buffers
    of each operand and result tile, the ``dk`` / ``dv`` accumulators, and
    the float32 score-shaped tiles (``s``, ``p``, ``dp``, ``ds``, and the
    narrow copies of ``p`` and ``ds``, with room for what Mosaic keeps
    beside them)."""
    both = d + (d if dv is None else dv)    # q and g, k and v, dk and dv
    return (lq * d * (4 + 2 * itemsize)
            + 2 * (block_q + 2 * block_k) * both * itemsize
            + block_k * both * 4 + 8 * block_q * block_k * 4)


@functools.lru_cache(maxsize=64)
def _fa_bwd_pairs(causal, mask, lq, lk, block_q, block_k):
    """The table the backward kernel walks, int32 ``(3, pairs)``: q tile, k
    tile and flags of every live tile pair (``_live_tiles``), K tile by K
    tile so that a K tile's ``dk`` and ``dv`` are finished before the next
    one's begin.  Flags: first and last pair of their K tile.  Every K tile
    is in it, so every tile of ``dk`` and ``dv`` is written: one that no
    query sees (a window over ``lq < lk`` leaves the first keys to none) is
    walked once, with the first q tile and wholly hidden, and so written as
    zeros."""
    some = _live_tiles(causal, mask, lq, lk, block_q, block_k).copy()
    some[0, ~some.any(axis=0)] = True
    pairs = _np.argwhere(some.T)[:, ::-1]
    turn = pairs[1:, 1] != pairs[:-1, 1]
    flags = _FIRST_OF_K * _np.r_[True, turn] + _LAST_OF_K * _np.r_[turn, True]
    return _np.stack([pairs[:, 0], pairs[:, 1], flags]).astype(_np.int32)


def _fa_bwd_pairs_under_ids(pairs, live):
    """The table of a call under segment ids and the grid steps it takes:
    int32 ``(3, batch x rows)``, a sample's ``rows = pairs.shape[1]`` after
    the other, and the longest sample's count of live pairs.  Of the static
    table's ``pairs`` (``_fa_bwd_pairs``) those whose tile is ``live`` for
    the sample (``_segment_tiles`` at the backward's tiles), packed to the
    front in the static order, K tile by K tile, with the first and last
    flags of what is left.  A K tile none of whose pairs is live keeps its
    first, which the ids then hide wholly, so that every tile of ``dk`` and
    ``dv`` is written (as zeros).  The rows past a sample's live pairs are
    ``_DEAD`` and repeat the last live pair's tiles: those the grid still
    reaches (a batch's shorter samples) copy nothing and compute nothing.
    A few XLA ops on arrays of ``rows`` (one compare of rows x rows a sample
    packs them: no sort, no scatter)."""
    import jax.numpy as jnp

    qi, ki, flags = pairs
    rows = pairs.shape[1]
    keep = live[:, qi, ki] | (((flags & _FIRST_OF_K) != 0)
                              & ~live.any(axis=1)[:, ki])
    count = keep.sum(axis=1, keepdims=True)
    t = jnp.arange(rows)[None, :]
    # row t takes the static row of the (t + 1)-th pair kept: as many rows
    # lie before it as have kept fewer than that
    nth = jnp.minimum(t, count - 1) + 1
    src = (jnp.cumsum(keep, axis=1)[:, None, :] < nth[:, :, None]).sum(axis=-1)
    q_t, k_t = jnp.asarray(qi)[src], jnp.asarray(ki)[src]
    turn = k_t[:, 1:] != k_t[:, :-1]
    edge = jnp.ones((keep.shape[0], 1), bool)
    flags = jnp.where(
        t < count,
        _FIRST_OF_K * jnp.concatenate([edge, turn], axis=1)
        + _LAST_OF_K * (jnp.concatenate([turn, edge], axis=1)
                        | (t == count - 1)), _DEAD)
    table = jnp.stack([q_t, k_t, flags]).astype(jnp.int32).reshape(3, -1)
    return table, jnp.max(count).astype(jnp.int32)


def _fa_bwd_kernel(pairs_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                   dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, causal,
                   sm_scale, seq_q, seq_k, mask, table_row, qseg_ref=None,
                   kseg_ref=None):
    """One live tile pair: its five products, nothing recomputed, and from
    the first product to the add into ``dq`` no branch.  A call with
    ``causal`` or a mask compares ``_visible`` in every pair it walks, one
    the mask shows whole too: a ``lax.cond`` around the compare carries the
    score tile (1 MiB at tiles of 512) between the first product and
    ``exp`` and costs more than the compare (PERF.md section 6, PRs 33 and
    46); a call with neither has no compare at all.  Under segment ids
    (``_fa_bwd_kernel_segments``) ``qseg_ref`` (1, block_q) holds the q
    tile's ids as a row and ``kseg_ref`` (block_k, 1) the K tile's as a
    column, ``_visible`` takes them beside the mask (a tile the mask shows
    whole may still hold two documents), and the table is the sample's own,
    built on the device from its ids (``_fa_bwd_pairs_under_ids``;
    ``table_row`` gives the table's row of a grid step): the pairs whose
    tiles hold a pair of one document, then ``_DEAD`` rows, whose steps do
    nothing.  A pair left out would have added exact zeros to ``dq``,
    ``dk`` and ``dv``: the gradients are those of walking every pair the
    mask alone shows, bit for bit.

    Grid: (batch*heads, live tile pairs), the pairs K tile by K tile
    (``_fa_bwd_pairs``, prefetched to SMEM; the block index maps read it, so
    a dead tile costs no step and no copy); under ids the second length is
    the batch's longest table, a number the device computes (a dynamic grid
    bound).  Blocks: q_ref / g_ref
    (block_q, d) and lse_ref / delta_ref (1, block_q) of the pair's q tile;
    k_ref / v_ref and dk_ref / dv_ref (block_k, d) of its K tile; dq_ref the
    head's whole (seq_q, d) row.  Scratch, float32: dq_acc (q tiles, d,
    block_q), the head's ``dq`` transposed, added to across the K tiles;
    dk_acc / dv_acc (block_k, d), added to across a K tile's q tiles.

    Scores are held transposed, (block_k, block_q): the log-sum-exp and
    ``delta`` are then rows that broadcast down the sublanes, ``p^T g`` and
    ``ds^T q`` are plain products, and the one transposed operand is the
    narrow K tile of ``k^T ds^T = dq^T``.  Operands in the input's dtype,
    float32 accumulation (``_fa_fwd_kernel``); ``p`` and ``ds`` go to the
    input's dtype for their products; ``sm_scale`` is applied to ``dq`` and
    ``dk`` as they are written, not to every ``ds``.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    block_q, block_k = q_ref.shape[0], k_ref.shape[0]
    t = pl.program_id(1)
    ids = None if qseg_ref is None else (qseg_ref[...], kseg_ref[...])
    row = t if ids is None else table_row(pl.program_id(0), t)
    qi, ki, flags = pairs_ref[0, row], pairs_ref[1, row], pairs_ref[2, row]

    @pl.when(t == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def pair():
        @pl.when((flags & _FIRST_OF_K) != 0)
        def _():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        q, k, v, g = q_ref[...], k_ref[...], v_ref[...], g_ref[...]
        dot = functools.partial(jax.lax.dot_general,
                                precision=_operand_precision(q.dtype),
                                preferred_element_type=jnp.float32)
        # a power of two scales q exactly, as in the forward
        scale_q = _np.frexp(sm_scale)[0] == 0.5
        if scale_q:
            s = dot(k, (q.astype(jnp.float32) * sm_scale).astype(q.dtype),
                    _NT_DIMS)
        else:
            s = dot(k, q, _NT_DIMS) * sm_scale
        if causal or mask is not None:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_q), 1)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0)
            s = jnp.where(_visible(jnp, q_pos, k_pos, causal, mask, seq_q,
                                   seq_k, ids), s, NEG_INF)
        p = jnp.exp(s - lse_ref[...])
        dv_acc[...] += dot(p.astype(g.dtype), g, _NN_DIMS)
        dp = dot(v, g, _NT_DIMS)
        ds = (p * (dp - delta_ref[...])).astype(q.dtype)
        dk_acc[...] += dot(ds, q, _NN_DIMS)
        dq_acc[qi] += dot(k, ds, _TN_DIMS)

        @pl.when((flags & _LAST_OF_K) != 0)
        def _():
            dk_ref[...] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    if ids is None:
        pair()
    else:
        pl.when((flags & _DEAD) == 0)(pair)

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        def put(i, carry):
            dq_ref[pl.ds(i * block_q, block_q), :] = (
                dq_acc[i].T * sm_scale).astype(dq_ref.dtype)
            return carry

        jax.lax.fori_loop(0, seq_q // block_q, put, None)


def _fa_bwd_kernel_segments(pairs_ref, q_ref, k_ref, v_ref, g_ref, lse_ref,
                            delta_ref, qseg_ref, kseg_ref, *outputs_and_scratch,
                            **static):
    """``_fa_bwd_kernel`` of a call under segment ids: its two refs of ids
    come after the operands, as ``_fa_backward_pallas`` lists them."""
    _fa_bwd_kernel(pairs_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                   *outputs_and_scratch, qseg_ref=qseg_ref,
                   kseg_ref=kseg_ref, **static)


def _fa_backward_pallas(q, k, v, o, lse, g, causal, sm_scale, mask=None):
    """Gradients of q, k and v from one Pallas call over the live tile
    pairs (``_fa_bwd_kernel``): those of the static table, or under segment
    ids those of the table built here from the ids; ``delta = rowsum(o *
    g)`` and, where a group of query heads shares a key-value head, the sum
    of ``dk`` and ``dv`` over the group (``_fold_group``) are the
    reductions left to XLA.  No score-shaped array reaches HBM, and K and V
    come on their own heads (``_kv_row``)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    mask = _Mask.of(mask)
    seg = mask.seg
    with jax.named_scope(SCOPE_ATTENTION_BWD):
        # q and k padded as the forward has them; dq and dk lose the pad
        widths = q.shape[-1], k.shape[-1]
        q, k = (_pad_width(x, _padded_width(x.shape[-1])) for x in (q, k))
        b, h, lq, d = q.shape
        hkv, lk, dv = k.shape[1], k.shape[2], v.shape[-1]
        rep = h // hkv
        block_q, block_k = _fa_bwd_block_sizes(lq, lk)
        pairs = _fa_bwd_pairs(causal, mask.key, lq, lk, block_q, block_k)
        nq = lq // block_q

        delta = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)
        rows = lambda x: x.astype(jnp.float32).reshape(b * h, nq, 1, block_q)
        flat = lambda x: x.reshape(-1, *x.shape[2:])   # heads: h, or hkv

        # the table's row of grid step (bh, t): row t, or under ids row t
        # of the sample's own table
        n_rows = pairs.shape[1]
        at = (lambda bh, t: t) if seg is None else (
            lambda bh, t: _sample_of(bh, h) * n_rows + t)
        q_tile = pl.BlockSpec((None, block_q, d), lambda bh, t, pairs:
                              (bh, pairs[0, at(bh, t)], 0))
        g_tile = pl.BlockSpec((None, block_q, dv), lambda bh, t, pairs:
                              (bh, pairs[0, at(bh, t)], 0))

        def k_rows(width, row):
            return pl.BlockSpec((None, block_k, width), lambda bh, t, pairs:
                                (row(bh), pairs[1, at(bh, t)], 0))

        # K and V are read a key-value head, a group of rep query heads
        # from one row; dk and dv are written a query head
        shared, own = (lambda bh: _kv_row(bh, rep)), (lambda bh: bh)
        k_tile, v_tile = k_rows(d, shared), k_rows(dv, shared)
        dk_tile, dv_tile = k_rows(d, own), k_rows(dv, own)
        q_row = pl.BlockSpec((None, None, 1, block_q), lambda bh, t, pairs:
                             (bh, pairs[0, at(bh, t)], 0, 0))
        need = _fa_bwd_vmem_bytes(lq, d, q.dtype.itemsize, block_q, block_k,
                                  dv)
        static = dict(causal=causal, sm_scale=sm_scale, seq_q=lq, seq_k=lk,
                      mask=mask.key, table_row=at)
        in_specs = [q_tile, k_tile, v_tile, g_tile, q_row, q_row]
        operands = [flat(q), flat(k), flat(v), flat(g), rows(lse),
                    rows(delta)]
        if seg is None:
            kernel = functools.partial(_fa_bwd_kernel, **static)
            table, steps = jnp.asarray(pairs), n_rows
        else:
            # a sample's ids serve its h heads: the q tile's as a row (the
            # scores are held transposed), the K tile's as a column, and
            # the table of the pairs its documents show
            kernel = functools.partial(_fa_bwd_kernel_segments, **static)
            in_specs += [
                pl.BlockSpec((None, None, 1, block_q), lambda bh, t, pairs:
                             (_sample_of(bh, h), pairs[0, at(bh, t)], 0, 0)),
                pl.BlockSpec((None, block_k, 1), lambda bh, t, pairs:
                             (_sample_of(bh, h), pairs[1, at(bh, t)], 0)),
            ]
            operands += [seg[:, lk - lq:].reshape(b, nq, 1, block_q),
                         seg[:, :, None]]
            table, steps = _fa_bwd_pairs_under_ids(pairs, _segment_tiles(
                seg, causal, mask.key, lq, lk, block_q, block_k))
        dq, dk, dv = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b * h, steps),
                in_specs=in_specs,
                out_specs=[pl.BlockSpec((None, lq, d),
                                        lambda bh, t, pairs: (bh, 0, 0)),
                           dk_tile, dv_tile],
                scratch_shapes=[pltpu.VMEM((nq, d, block_q), jnp.float32),
                                pltpu.VMEM((block_k, d), jnp.float32),
                                pltpu.VMEM((block_k, dv), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct((b * h, lq, d), q.dtype),
                       jax.ShapeDtypeStruct((b * h, lk, d), k.dtype),
                       jax.ShapeDtypeStruct((b * h, lk, dv), v.dtype)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=max(need, _VMEM_DEFAULT_LIMIT)),
            name=_kernel_name(SCOPE_ATTENTION_BWD, mask),
        )(table, *operands)
    dq, dk, dv = (dq.reshape(q.shape)[..., :widths[0]],
                  dk.reshape(b, h, *k.shape[2:])[..., :widths[1]],
                  dv.reshape(b, h, *v.shape[2:]))
    # the group's sum is left to XLA, in the gradients' dtype (what the
    # transpose of a repeat before the call would sum in), as the
    # backward's own work
    with jax.named_scope(SCOPE_ATTENTION_BWD):
        return dq, _fold_group(dk, hkv), _fold_group(dv, hkv)


def _use_pallas_bwd(q, k, v=None):
    """Static gate for the Pallas backward: where ``_use_pallas`` takes the
    forward kernel, the inputs are bf16, both lengths divide into its tiles
    and a head's ``dq`` row fits VMEM beside the tiles.  float32 inputs take
    the scan: their products are several MXU passes either way, and at that
    the kernel is the slower of the two on a v5e (2.18 against 0.86 ms a
    call at (16, 12, 512, 512, 64); PERF.md section 6, PR 27)."""
    import jax.numpy as jnp

    lq, lk = q.shape[2], k.shape[2]
    block_q, block_k = _fa_bwd_block_sizes(lq, lk)
    if not _use_pallas(q, v) or q.dtype != jnp.bfloat16:
        return False
    if block_q is None or block_k is None:
        return False
    return _fa_bwd_vmem_bytes(
        lq, _padded_width(q.shape[3]), q.dtype.itemsize, block_q, block_k,
        None if v is None else v.shape[3]) <= _VMEM_MOST


def _fa_backward(q, k, v, o, lse, g, causal, sm_scale, mask=None,
                 sharded=None):
    """The backward of ``flash_attention``: the Pallas kernel where
    ``_use_pallas_bwd`` takes it (per batch shard under ``sharded``, as
    ``_fa_forward``), the blockwise scan for everything else.  Which one a
    call took is counted once a trace in
    ``mxnet_flash_attention_bwd_calls_total{path}``."""
    from .. import telemetry

    mask = _Mask.of(mask)
    pallas = _use_pallas_bwd(q, k, v)
    telemetry.counter(
        "mxnet_flash_attention_bwd_calls_total",
        "flash_attention backward calls traced, by the path they took",
        ("path",)).labels(path=("pallas" if pallas else "blockwise")
                          + (KERNEL_SUFFIX_SEGMENTS if mask.operands else "")
                          ).inc()
    if not pallas:
        return _fa_backward_blockwise(q, k, v, o, lse, g, causal, sm_scale,
                                      mask=mask)

    def bwd(q, k, v, o, lse, g, *operands):
        return _fa_backward_pallas(q, k, v, o, lse, g, causal, sm_scale,
                                   mask=mask.over(*operands))

    return _per_batch_shard(bwd, sharded)(q, k, v, o, lse, g, *mask.operands)


# --------------------------------------------------------------------------
# blockwise backward (jax, O(L) memory via scan recompute): the fallback
# --------------------------------------------------------------------------
def _fa_backward_blockwise(q, k, v, o, lse, g, causal, sm_scale,
                           block_k=512, mask=None, block_q=512):
    """Gradients of q, k and v, recomputing the probabilities a K block at a
    time from the saved log-sum-exp, in plain jax: the path of every call
    the Pallas backward does not take (``_use_pallas_bwd``).  O(L) memory.
    As in the kernels, the five products take their operands in the input's
    dtype and accumulate in float32 (``p`` and ``ds`` are cast to it for
    theirs); float32 inputs keep float32 operands at the process's
    precision; ``s``, ``p``, ``dp``, ``ds``, ``delta`` and the three sums
    are float32 and the results are cast once, at the end.

    Where no tile of the score matrix is wholly masked (no mask, or a row
    of one q tile), one scan over the K blocks takes every query row at
    once.  Where some are (``causal`` or ``mask`` at lengths of several
    tiles), the scan runs over the live ``(q tile, k tile)`` pairs alone,
    which are static (``_live_tiles``): the dead ones cost nothing, as in
    the forward kernel.  Both evaluate ``_visible`` on positions; neither
    holds an ``(lq, lk)`` array."""
    import jax
    import jax.numpy as jnp

    how = _Mask.of(mask)
    mask, ids = how.key, how.ids(q.shape[2])
    with jax.named_scope(SCOPE_ATTENTION_BWD):
        b, h, lq, d = q.shape
        hkv, lk = k.shape[1], k.shape[2]
        k, v = _per_query_head(k, h), _per_query_head(v, h)
        block_k = min(block_k, lk)
        if lk % block_k != 0:
            block_k = lk
        nkb = lk // block_k
        block_q = min(block_q, lq)
        if lq % block_q != 0:
            block_q = lq
        live = _live_tiles(causal, mask, lq, lk, block_q, block_k)

        acc_t = jnp.result_type(q.dtype, jnp.float32)
        product = functools.partial(jnp.einsum, preferred_element_type=acc_t,
                                    precision=_operand_precision(q.dtype))
        delta = jnp.sum(o.astype(acc_t) * g.astype(acc_t), axis=-1)  # (b,h,lq)

        def ids_of(q0, k0, rows_q):
            """The segment ids of ``rows_q`` query rows from ``q0`` and of
            the K tile from ``k0``, or None."""
            if ids is None:
                return None
            return (jax.lax.dynamic_slice_in_dim(ids[0], q0, rows_q, axis=2),
                    jax.lax.dynamic_slice_in_dim(ids[1], k0, block_k, axis=3))

        def tile_grads(qt, gt, delta_t, lse_t, kt, vt, q_pos, k_pos, ids):
            """One tile's ``(dq part, dk part, dv part)`` in float32;
            ``q_pos`` a column and ``k_pos`` a row of positions, ``ids``
            the tile's segment ids shaped likewise, or None."""
            s = product("bhqd,bhkd->bhqk", qt, kt) * sm_scale
            # same diagonal offset as the forward (q_i attends keys up to
            # i + lk - lq when lengths differ, e.g. decode)
            seen = _visible(jnp, q_pos, k_pos, causal, mask, lq, lk, ids)
            if seen is not None:
                s = jnp.where(seen, s, NEG_INF)
            p = jnp.exp(s - lse_t[..., None])                  # (b,h,q,bk)
            dv = product("bhqk,bhqd->bhkd", p.astype(gt.dtype), gt)
            dp = product("bhqd,bhkd->bhqk", gt, vt)
            ds = (p * (dp - delta_t[..., None]) * sm_scale).astype(qt.dtype)
            dk = product("bhqk,bhqd->bhkd", ds, qt)
            return product("bhqk,bhkd->bhqd", ds, kt), dk, dv

        if live.all():
            kb = k.reshape(b, h, nkb, block_k, d)
            vb = v.reshape(b, h, nkb, block_k, v.shape[3])
            q_pos = jnp.arange(lq)[:, None]

            def step(dq, idx):
                k0 = idx * block_k
                k_pos = k0 + jnp.arange(block_k)[None, :]
                dq_part, dk, dv = tile_grads(
                    q, g, delta, lse, kb[:, :, idx], vb[:, :, idx],
                    q_pos, k_pos, ids_of(0, k0, lq))
                return dq + dq_part, (dk, dv)

            dq0 = jnp.zeros(q.shape, acc_t)
            dq, (dks, dvs) = jax.lax.scan(step, dq0, jnp.arange(nkb))
            dk = jnp.moveaxis(dks, 0, 2).reshape(b, h, lk, d)
            dv = jnp.moveaxis(dvs, 0, 2).reshape(v.shape)
        else:
            # K tile by K tile, so that a K tile's gradient is finished
            # before the next one's begins
            pairs = jnp.asarray(_np.argwhere(live.T)[:, ::-1], jnp.int32)

            def rows(x, start, size):
                return jax.lax.dynamic_slice_in_dim(x, start, size, axis=2)

            def add_rows(acc, part, start):
                size = part.shape[2]
                return jax.lax.dynamic_update_slice_in_dim(
                    acc, rows(acc, start, size) + part, start, axis=2)

            def step(carry, pair):
                dq, dk, dv = carry
                q0, k0 = pair[0] * block_q, pair[1] * block_k
                dq_part, dk_part, dv_part = tile_grads(
                    rows(q, q0, block_q), rows(g, q0, block_q),
                    rows(delta, q0, block_q), rows(lse, q0, block_q),
                    rows(k, k0, block_k), rows(v, k0, block_k),
                    q0 + jnp.arange(block_q)[:, None],
                    k0 + jnp.arange(block_k)[None, :],
                    ids_of(q0, k0, block_q))
                return (add_rows(dq, dq_part, q0), add_rows(dk, dk_part, k0),
                        add_rows(dv, dv_part, k0)), None

            zeros = (jnp.zeros(q.shape, acc_t), jnp.zeros(k.shape, acc_t),
                     jnp.zeros(v.shape, acc_t))
            (dq, dk, dv), _ = jax.lax.scan(step, zeros, pairs)
    return (dq.astype(q.dtype), _fold_group(dk.astype(k.dtype), hkv),
            _fold_group(dv.astype(v.dtype), hkv))


# --------------------------------------------------------------------------
# public op with custom vjp
# --------------------------------------------------------------------------
# The names under which the op's output and its row statistics are kept by
# a checkpoint whose policy asks for them (a decoder layer's,
# ``LlamaDecoderLayer``): with both kept, the checkpoint's backward holds no
# second forward of the op.
KEPT_O = "mxnet_flash_attention_o"
KEPT_LSE = "mxnet_flash_attention_lse"

_KEEPING = threading.local()   # .value: True while such a checkpoint's body
#                                is being traced, see checkpoint_keeps


@contextlib.contextmanager
def checkpoint_keeps():
    """Trace-time scope of a ``jax.checkpoint`` whose policy keeps what the
    ops name (``save_only_these_names`` over ``KEPT_O``, ``KEPT_LSE`` and
    ``kda``'s): an op called inside it names those values in its
    ``custom_vjp`` rule.  Outside it an op names nothing and its program is
    what it was without the names."""
    prev = keeping()
    _KEEPING.value = True
    try:
        yield
    finally:
        _KEEPING.value = prev


def keeping():
    """Whether the call is traced inside ``checkpoint_keeps``.  Read when
    the op is called: its rule is traced after the scope has closed."""
    return getattr(_KEEPING, "value", False)


def kept(name, value):
    """``value`` under ``name`` for ``jax.checkpoint_policies.
    save_only_these_names``, its bytes counted once a trace in
    ``mxnet_layer_checkpoint_kept_bytes_total{name}``."""
    from jax.ad_checkpoint import checkpoint_name

    from .. import telemetry

    telemetry.LAYER_CHECKPOINT_KEPT_BYTES.labels(name=name).inc(
        value.size * value.dtype.itemsize)
    return checkpoint_name(value, name)


@functools.lru_cache(maxsize=None)
def _make_flash(causal, sm_scale_key, mask=None, sharded=None, keeps=False):
    """The op for one static configuration: ``flash(q, k, v, *operands)``,
    the operands those of the call's ``_Mask`` (none, or the segment ids:
    integers, so no gradient goes back to them), of which ``mask`` is the
    static key.  ``sharded`` is the ``batch_sharded`` scope the call was
    traced under: the backward is traced after that scope has closed
    (``value_and_grad`` transposes once the forward has returned), so it is
    kept here and not read again; ``keeps``, whether the call stood inside
    ``checkpoint_keeps``, for the same reason."""
    import jax

    sm_scale = float(sm_scale_key)

    def forward(q, k, v, *operands):
        from .. import telemetry

        how = _Mask(mask, *operands)
        pallas = _use_pallas(q, v)
        telemetry.counter(
            "mxnet_flash_attention_fwd_calls_total",
            "flash_attention forward calls traced, by the path they took "
            "and the mask they ran under (with _segments where segment ids "
            "confine it)",
            ("path", "mask")).labels(
                path="pallas" if pallas else "plain",
                mask=how.label(causal)).inc()
        group = q.shape[1] // k.shape[1]
        if pallas and group > 1:
            telemetry.counter(
                "mxnet_flash_attention_shared_kv_calls_total",
                "flash_attention calls traced whose kernels read a "
                "key-value head shared by a group of query heads through "
                "their block maps, by the group's size",
                ("group",)).labels(group=str(group)).inc()
        if pallas:
            return _fa_forward(q, k, v, causal, sm_scale, how, sharded)
        return _mha_with_lse(q, k, v, causal, sm_scale, how)

    @jax.custom_vjp
    def flash(q, k, v, *operands):
        return forward(q, k, v, *operands)[0]

    def _dispatch_fwd(q, k, v, *operands):
        o, lse = forward(q, k, v, *operands)
        if keeps:
            # named here, inside the rule: the residuals are then the named
            # values themselves (a name put on the op's result names a copy)
            o, lse = kept(KEPT_O, o), kept(KEPT_LSE, lse)
            # What the checkpoint computes again after the op no longer
            # waits for q, k and v, and XLA's scheduler then holds a layer's
            # backward differently: 1.1 to 1.4 GiB more scratch a step in
            # two of three decoder cells on a v5e, the logits among it.  The
            # barrier gives the kept o the place in the order that the
            # recomputed one had.  Only here: in a step without a checkpoint
            # it cost 3.4% (BERT at 512; PERF.md section 6, PR 42).
            q, k, v, o, lse = jax.lax.optimization_barrier((q, k, v, o, lse))
        return o, (q, k, v, o, lse, operands)

    def bwd(res, g):
        q, k, v, o, lse, operands = res
        grads = _fa_backward(q, k, v, o, lse, g, causal, sm_scale,
                             _Mask(mask, *operands), sharded)
        return grads + (None,) * len(operands)

    flash.defvjp(_dispatch_fwd, bwd)
    return flash


def _count_pairs(q, k, causal, mask):
    """Gives the step's two counts of a call under segment ids (``mask``
    its ``_Mask``) to ``telemetry.step_scalar`` (which keeps nothing
    outside a fused step's trace, and the compiler then drops the sum), a
    sample counted once whatever its heads: the pairs the mask and the ids
    show, computed on the device from the ids (a query at position ``p`` of
    its document sees ``p + 1`` keys, or the window's ``W`` if that is
    fewer; a document a run of equal ids), and the pairs of the tiles the
    forward walks: on the kernel's path the K tiles of every q tile's
    bounds (``_fa_fwd_bounds``), summed on the device from the same ids; on
    the plain path every pair of the square."""
    import jax.numpy as jnp

    from .. import telemetry
    from .attention_ops import segment_positions

    b, lq, lk = q.shape[0], q.shape[2], k.shape[2]
    seen = segment_positions(mask.seg)[:, lk - lq:] + 1
    if mask.key is not None:
        seen = jnp.minimum(seen, mask.key[1])
    walked = jnp.float32(b * lq * lk)
    if _use_pallas(q):   # under ids q, k and v are of one width
        block_q, block_k = _fa_block_sizes(lq, lk, q.shape[3],
                                           q.dtype.itemsize)
        lo, hi = _fa_fwd_bounds(_segment_tiles(
            mask.seg, causal, mask.key, lq, lk, block_q, block_k))
        walked = jnp.sum(hi - lo).astype(jnp.float32) * (block_q * block_k)
    telemetry.step_scalar(telemetry.ATTENTION_VISIBLE_PAIRS.name,
                          jnp.sum(seen.astype(jnp.float32)))
    telemetry.step_scalar(telemetry.ATTENTION_WALKED_PAIRS.name, walked)


def flash_attention(q, k, v, causal=False, sm_scale=None, mask=None,
                    mask_block=0, window=0, segment_ids=None):
    """q (B,Hq,Lq,D); k (B,Hkv,Lk,D), v (B,Hkv,Lk,Dv) with Hq % Hkv == 0
    (GQA): K and V keep their heads through the op and its backward, and
    the kernels read a shared head through their block maps (``_kv_row``;
    the paths off the kernels repeat inside, ``_per_query_head``).  ``Dv``
    may differ from ``D`` (latent attention's 192 and 128):
    the output is ``(B,Hq,Lq,Dv)``, and the kernels pad q and k to a width
    they tile (``_padded_width``), never v.

    ``mask="block_diffusion"`` with ``mask_block`` the block length: the
    training mask of block diffusion over rows of a noised copy followed by
    the clean copy.  ``mask="window"`` with ``window`` = W: causal attention
    in which a query sees the last W keys up to its own position.  A mask
    takes the place of ``causal`` (``_visible`` has the predicates).

    ``segment_ids`` (B, Lk) integers, with ``causal=True`` or
    ``mask="window"``: documents packed into one row, a document a run of
    equal ids; a query sees the keys of its own document alone (the queries
    are the last Lq keys).  The ids are an operand: rows whose boundaries
    move from batch to batch run one program.  Of the tiles the mask alone
    shows, the kernels walk those whose queries' and keys' id ranges meet,
    by a table built on the device from each batch's ids
    (``_segment_tiles``), and hide the pairs of two documents in them.
    Where the ids are runs in rising order (what a packer writes) those are
    exactly the tiles that hold a pair of one document; any other ids (runs
    out of order, a document in two places) are computed rightly and skip
    less."""
    import jax.numpy as jnp

    if (q.shape[-1] != k.shape[-1] or k.shape[:3] != v.shape[:3]
            or q.shape[1] % k.shape[1]):
        from ..base import MXNetError

        raise MXNetError(
            "flash_attention: q and k share a head size, k and v their "
            "heads and rows (v's head size is its own), and a whole number "
            f"of query heads a key-value head; got q {q.shape}, k {k.shape}, "
            f"v {v.shape}")
    mask = _mask_key(mask, mask_block, causal, window)
    _check_mask_shape(mask, q.shape[2], k.shape[2])
    d = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / _np.sqrt(d)
    how = _Mask(mask, None if segment_ids is None
                else jnp.asarray(segment_ids).astype(jnp.int32))
    how.check(causal, q.shape[0], k.shape[2])
    if how.seg is not None:
        _count_pairs(q, k, bool(causal), how)
    return _make_flash(bool(causal), float(sm_scale), mask,
                       getattr(_SCOPE, "value", None),
                       keeping())(q, k, v, *how.operands)


# registry entry --------------------------------------------------------------
from .registry import register


@register("_contrib_flash_attention", aliases=("flash_attention",))
def flash_attention_op(q, k, v, segment_ids=None, causal=False, sm_scale=None,
                       mask=None, mask_block=0, window=0):
    """Fused scaled-dot-product attention (net-new vs reference; the TPU
    answer to contrib/transformer.cc's unfused attention path).
    ``segment_ids``, a fourth array, confines causal and window attention
    to the documents of a packed row (``flash_attention``)."""
    return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                           mask=mask, mask_block=mask_block, window=window,
                           segment_ids=segment_ids)
