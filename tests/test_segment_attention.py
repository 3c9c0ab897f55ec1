"""Segment ids in the attention op (ISSUE 32) and the table of live tiles
that both kernels walk under them (ISSUE 33): the predicate, the plain path,
the blockwise scan and both Pallas kernels under the TPU interpreter against
a dense boolean mask, causal and under the window; the table against the
mask, and the kernels walking it against the kernels walking every tile; what
a call without ids keeps; the counters of pairs.  The decoder trained on
packed documents is ``test_packed_yarn_decoder.py``'s (one file until PR 41,
which split it by subject for tier-1's ``--dist loadfile``).  All on the CPU,
seeded random inputs."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu.ops import flash_attention as fa

from test_block_diffusion_moe import dense_attention


# --------------------------------------------------------------------------
# segment ids in the attention op
# --------------------------------------------------------------------------
def segments_of(lengths, ids=None):
    """(batch, lk) int32 from each row's document lengths; ids that are
    neither sorted nor small, a document a run of one id.  ``ids``:
    "rising" for each document's place in its row (what a packer writes),
    or a row's ids document by document (a document in two places)."""
    def of(n, row):
        if ids is None:
            return (np.arange(len(row)) * 7 + 3) % 11 + 40
        return np.arange(len(row)) if ids == "rising" else np.asarray(ids[n])

    return jnp.asarray(np.stack([
        np.repeat(of(n, row), row)
        for n, row in enumerate(lengths)]).astype("int32"))


def dense_seen(lq, lk, window, seg):
    """The mask as the issue words it, entry by entry (no shared code with
    ``_visible``): query i, at position i + lk - lq of the keys, may see key
    j iff j is not after it, is of its document and, under a window, lies
    among the last ``window`` up to it.  (batch, 1, lq, lk)."""
    seg = np.asarray(seg)
    out = np.zeros((seg.shape[0], 1, lq, lk), bool)
    for b in range(seg.shape[0]):
        for i in range(lq):
            at = i + lk - lq
            for j in range(at + 1):
                out[b, 0, i, j] = seg[b, j] == seg[b, at] and (
                    not window or j > at - window)
    return out


# (lq, lk, window, block, lengths a row): boundaries off the tiles; a tile
# wholly of another document than its queries' (the 300 after the 130: K tile
# 0 against q tiles 2 and 3); lq < lk; a document of one token; one document
# a row; a window wider than most documents and narrower than a tile
SEGMENT_CASES = [
    (512, 512, 0, 128, [[130, 300, 82], [1, 510, 1]]),
    (256, 512, 0, 128, [[200, 57, 255], [512]]),
    (512, 512, 200, 128, [[130, 300, 82], [37, 37, 438]]),
    (256, 768, 96, 128, [[600, 168], [300, 301, 167]]),
    (384, 384, 1000, 128, [[129, 255], [383, 1]]),
]


# for the table of live tiles: those, and a row of one document, boundaries
# on the tiles' edges, lq < lk with the queries' first tile inside a document
TABLE_CASES = SEGMENT_CASES + [
    (512, 512, 0, 128, [[512], [256, 256]]),
    (512, 512, 96, 128, [[128, 256, 128], [384, 128]]),
    (256, 768, 0, 128, [[100, 540, 128], [512, 256]]),
    (1024, 1024, 0, 256, [[300, 724], [700, 40, 284]]),
]
# ids by document for those rows: runs out of order, a document in two places
TWICE = {3: [[7, 7], [1, 0, 1]], 5: [[4], [9, 9]], 6: [[2, 1, 2], [0, 0]],
         7: [[5, 3, 5], [3, 3]], 8: [[1, 0], [6, 2, 6]]}
# (case, ids): every row under rising ids and under ``segments_of``'s own
TABLE_IDS = [(case, ids) for case in range(len(TABLE_CASES))
             for ids in ("rising", None)] + [(case, "twice") for case in TWICE]


def _table_case(case, ids):
    """``(lq, lk, window, block, seg)`` of a case of ``TABLE_IDS``."""
    lq, lk, window, block, lengths = TABLE_CASES[case]
    seg = segments_of(lengths, TWICE[case] if ids == "twice" else ids)
    return lq, lk, window, block, seg


def _call(window, seg=None):
    """``(causal, mask)`` of a call: the static key, or under ``seg`` the
    description the paths take."""
    key = (fa.WINDOW, window) if window else None
    return (window == 0, key if seg is None else fa._Mask(key, seg))


@pytest.mark.parametrize("lq,lk,window,block,lengths", SEGMENT_CASES)
def test_segment_predicate_is_the_dense_mask(lq, lk, window, block, lengths):
    seg = np.asarray(segments_of(lengths))
    causal, mask = _call(window)
    for b in range(seg.shape[0]):
        seen = fa._visible(np, np.arange(lq)[:, None], np.arange(lk)[None, :],
                           causal, mask, lq, lk,
                           (seg[b, lk - lq:, None], seg[b, None, :]))
        assert (seen == dense_seen(lq, lk, window, seg[b:b + 1])[0, 0]).all()
        assert seen.any(axis=1).all()       # every query sees itself


@pytest.mark.parametrize("path", ["plain", "pallas", "pallas_tiles"])
@pytest.mark.parametrize("lq,lk,window,block,lengths", SEGMENT_CASES)
def test_segment_attention_forward_matches_dense_mask(lq, lk, window, block,
                                                      lengths, path):
    """The plain path and the interpreted kernel (at the tiles the call's
    shape gives and at tiles of ``block``) against softmax under the dense
    mask, float32: 2e-6, a few units in the last place of outputs of size
    1; the log-sum-exp alike."""
    from jax.experimental.pallas import tpu as pltpu

    rs = np.random.RandomState(lq + window)
    q, k, v = (jnp.asarray(rs.randn(2, 2, n, 64).astype("f"))
               for n in (lq, lk, lk))
    seg = segments_of(lengths)
    causal, mask = _call(window, seg)
    want = dense_attention(q, k, v, dense_seen(lq, lk, window, seg), 0.125)
    if path == "plain":
        o, _ = fa._mha_with_lse(q, k, v, causal, 0.125, mask)
    else:
        tiles = (block, block) if path == "pallas_tiles" else (None, None)
        with pltpu.force_tpu_interpret_mode():
            o, lse = fa._fa_forward_pallas(
                q, k, v, causal, 0.125, block_q=tiles[0], block_k=tiles[1],
                mask=mask)
        np.testing.assert_allclose(
            lse, fa._mha_with_lse(q, k, v, causal, 0.125, mask)[1],
            atol=2e-6)
    np.testing.assert_allclose(o, want, atol=2e-6)


@pytest.mark.parametrize("path", ["scan", "scan_pairs", "pallas"])
@pytest.mark.parametrize("lq,lk,window,block,lengths", SEGMENT_CASES)
def test_segment_attention_backward_matches_dense_mask(lq, lk, window, block,
                                                       lengths, path):
    """The scan (one pass over every query row, and over the live tile
    pairs) and the interpreted backward kernel against autodiff through the
    dense mask: 3e-5, the float32 noise of sums over up to 768 keys in
    another order."""
    from jax.experimental.pallas import tpu as pltpu

    rs = np.random.RandomState(lk + window)
    q, k, v, g = (jnp.asarray(rs.randn(2, 2, n, 64).astype("f"))
                  for n in (lq, lk, lk, lq))
    seg = segments_of(lengths)
    causal, mask = _call(window, seg)
    seen = dense_seen(lq, lk, window, seg)
    want = jax.jit(jax.grad(
        lambda *a: jnp.sum(dense_attention(*a, seen, 0.125) * g),
        (0, 1, 2)))(q, k, v)
    o, lse = fa._mha_with_lse(q, k, v, causal, 0.125, mask)
    if path == "pallas":
        with pltpu.force_tpu_interpret_mode():
            got = fa._fa_backward_pallas(q, k, v, o, lse, g, causal, 0.125,
                                         mask)
    else:
        size = {"scan": (lq, lk), "scan_pairs": (block, block)}[path]
        got = fa._fa_backward_blockwise(q, k, v, o, lse, g, causal, 0.125,
                                        block_k=size[1], mask=mask,
                                        block_q=size[0])
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=3e-5)


@pytest.mark.parametrize("window", [0, 9])
def test_flash_attention_op_takes_segment_ids_with_gqa(window):
    """Through the op table, 4 query heads over 2 key-value heads, ids a
    fourth array: value and all three gradients against the dense mask; no
    gradient goes to the ids; the forward's calls are counted under the
    mask's name with ``_segments``."""
    telemetry._FAMILIES.pop("mxnet_flash_attention_fwd_calls_total", None)
    rs = np.random.RandomState(window)
    q = jnp.asarray(rs.randn(2, 4, 40, 16).astype("f"))
    k, v = (jnp.asarray(rs.randn(2, 2, 40, 16).astype("f")) for _ in "kv")
    g = jnp.asarray(rs.randn(2, 4, 40, 16).astype("f"))
    seg = segments_of([[11, 22, 7], [40]])
    kw = dict(mask="window", window=window) if window else dict(causal=True)
    seen = dense_seen(40, 40, window, seg)
    rep = lambda x: jnp.repeat(x, 2, axis=1)
    want, want_vjp = jax.vjp(lambda q, k, v: dense_attention(
        q, rep(k), rep(v), seen, 0.25), q, k, v)
    got, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
        q, k, v, segment_ids=seg, **kw), q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-6)
    for a, b in zip(vjp(g), want_vjp(g)):
        np.testing.assert_allclose(a, b, atol=3e-5)
    o = nd.flash_attention(nd.array(q), nd.array(k), nd.array(v),
                           nd.array(seg, dtype="int32"), **kw)
    np.testing.assert_allclose(o.asnumpy(), want, atol=2e-6)
    masks = {s["labels"]["mask"] for s in telemetry.snapshot()["metrics"][
        "mxnet_flash_attention_fwd_calls_total"]["samples"]}
    assert masks == {"window_segments" if window else "causal_segments"}


@pytest.mark.parametrize("path", ["plain", "kernels"])
@pytest.mark.parametrize("rep", [1, 2, 8])
@pytest.mark.parametrize("window", [0, 100])
def test_a_group_on_one_kv_head_under_ids_equals_k_and_v_repeated_by_hand(
        monkeypatch, window, rep, path):
    """Causal and under the window, both confined by segment ids (the
    packed cell's two calls): the op on ``8 // rep`` key-value heads against
    K and V repeated by hand, bit for bit, on the CPU's path and through
    the interpreted kernels (``test_flash_attention_bwd.py`` has the masks
    without ids)."""
    from test_flash_attention_bwd import group_equals_repeated

    kw = dict(mask="window", window=window) if window else dict(causal=True)
    group_equals_repeated(monkeypatch, path, rep, **kw,
                          segment_ids=segments_of([[130, 100, 26],
                                                   [1, 254, 1]]))


def test_flash_attention_op_refuses_segment_ids_misused():
    q = nd.array(np.zeros((1, 1, 16, 8), "f"))
    seg = nd.array(np.zeros((1, 16)), dtype="int32")
    with pytest.raises(mx.MXNetError, match="block_diffusion"):
        nd.flash_attention(q, q, q, seg, mask="block_diffusion", mask_block=4)
    with pytest.raises(mx.MXNetError, match="causal=True"):
        nd.flash_attention(q, q, q, seg)              # neither mask
    with pytest.raises(mx.MXNetError, match=r"\(batch, lk\)"):
        nd.flash_attention(q, q, q, nd.array(np.zeros((1, 8)),
                                             dtype="int32"), causal=True)


def test_kernel_names_tell_a_call_under_ids_from_the_others():
    from mxnet_tpu import profiler

    window = (fa.WINDOW, 8)
    seg = segments_of([[3, 5]])
    fwd, bwd = profiler.KERNEL_ATTENTION_FWD, profiler.SCOPE_ATTENTION_BWD
    assert fa._kernel_name(fwd, fa._Mask(None, seg)) \
        == "mxnet_flash_attention_fwd_segments"
    assert fa._kernel_name(fwd, fa._Mask(window, seg)) \
        == "mxnet_flash_attention_fwd_window_segments"
    assert fa._kernel_name(bwd, fa._Mask(None, seg)) \
        == "mxnet_flash_attention_bwd_segments"
    assert fa._kernel_name(bwd, fa._Mask(window, seg)) \
        == "mxnet_flash_attention_bwd_window_segments"
    # calls without ids keep the names they had
    assert fa._kernel_name(fwd, None) == "mxnet_flash_attention_fwd"
    assert fa._kernel_name(fwd, window) == "mxnet_flash_attention_fwd_window"
    assert fa._kernel_name(bwd, (fa.BLOCK_DIFFUSION, 4)) \
        == "mxnet_flash_attention_bwd"


@pytest.mark.parametrize("lk,dim,itemsize", [
    (8192, 128, 2),    # trinity_mini and sdar_30b_a3b: exactly at the default
    (512, 64, 2),      # bert_base
    (2048, 128, 4)])
def test_a_forward_without_ids_that_fitted_states_no_vmem_limit(lk, dim,
                                                                itemsize):
    """The older cells' forward programs are what they were: their calls
    state no ``vmem_limit``, so Mosaic compiles them under its default (a
    row of 8,192 keys of 128 in bf16 stands exactly at it, by ``<=``: a byte
    more in the budget or the formula and this fails before a cell gets
    another program)."""
    assert fa._fa_fwd_vmem_limit(lk, dim, itemsize, 512, False) is None


def test_the_packed_cells_forward_states_its_own_vmem_limit():
    """A row too long for Mosaic's default limit states its own."""
    assert fa._VMEM_DEFAULT_LIMIT < fa._fa_fwd_vmem_limit(
        16384, 128, 2, 512, True) < fa._VMEM_MOST


def _tiles_of(seen, block):
    """Of a dense mask (batch, 1, lq, lk): the tiles of ``block`` x
    ``block`` that hold a visible pair."""
    b, _, lq, lk = seen.shape
    return seen[:, 0].reshape(b, lq // block, block, lk // block,
                              block).any(axis=(2, 4))


@pytest.mark.parametrize("case,ids", TABLE_IDS)
def test_the_table_of_live_tiles_against_the_dense_mask(case, ids):
    """Every tile in which the dense mask shows a pair is live, whatever
    the ids; for runs in rising order no other tile is.  The forward's
    bounds cover the live tiles of a q tile's row (for rising runs: those
    alone); the backward's table holds every live pair K tile by K tile
    with the flags of what it holds, every K tile at least once, dead rows
    after that repeat the last, and the grid is as long as the longest
    sample's live rows."""
    lq, lk, window, block, seg = _table_case(case, ids)
    causal, key = _call(window)
    has = _tiles_of(dense_seen(lq, lk, window, seg), block)
    live = np.asarray(fa._segment_tiles(seg, causal, key, lq, lk, block,
                                        block))
    assert (live | ~has).all()
    if ids == "rising":
        assert (live == has).all()
    else:   # a superset, inside what the mask alone shows
        assert (fa._live_tiles(causal, key, lq, lk, block, block)
                | ~live).all()
    b, nq, nk = live.shape
    lo, hi = np.asarray(fa._fa_fwd_bounds(jnp.asarray(live))).reshape(
        2, b, nq)
    at = np.arange(nk)
    hull = (at >= lo[..., None]) & (at < hi[..., None])
    assert (hull | ~live).all()
    if ids == "rising":
        assert (hull == has).all()
    static = fa._fa_bwd_pairs(causal, key, lq, lk, block, block)
    table, steps = fa._fa_bwd_pairs_under_ids(static, jnp.asarray(live))
    table = np.asarray(table).reshape(3, b, -1)
    assert steps == ((table[2] & fa._DEAD) == 0).sum(axis=1).max()
    for n in range(b):
        qi, ki, flags = table[:, n]
        alive = (flags & fa._DEAD) == 0
        count = alive.sum()
        assert alive[:count].all() and (flags[count:] == fa._DEAD).all()
        assert (qi[count:] == qi[count - 1]).all() \
            and (ki[count:] == ki[count - 1]).all()
        pairs = list(zip(ki[:count], qi[:count]))
        assert pairs == sorted(set(pairs))         # K tile by K tile, once
        assert set(ki[:count]) == set(range(nk))   # dk, dv written whole
        assert {(q, k) for k, q in pairs} >= set(zip(*np.nonzero(live[n])))
        # what is kept beside the live pairs: a K tile's one wholly hidden
        extra = [(k, q) for k, q in pairs if not live[n, q, k]]
        assert all(not live[n, :, k].any() for k, _ in extra)
        turn = np.r_[True, ki[1:count] != ki[:count - 1]]
        assert ((flags[:count] & fa._FIRST_OF_K != 0) == turn).all()
        assert ((flags[:count] & fa._LAST_OF_K != 0)
                == np.r_[turn[1:], True]).all()
    if case == 5 and ids == "rising":
        # one document: the triangle's 10 tiles; [256, 256]: 3 of each half
        assert live.sum(axis=(1, 2)).tolist() == [10, 6] and steps == 10


@functools.lru_cache(maxsize=None)
def _kernels_under_ids(lq, lk, window, block):
    """Both interpreted kernels under ids as one jitted call a shape, with
    the table of live tiles by its operand ``every``: False, the table the
    ids give (``_segment_tiles``); True, every tile the mask alone shows, as
    the kernels walked before PR 33 (``_live_tiles``, the same for every
    sample).  The table is the kernels' operand either way, and so are the
    ids, so the cases of one shape and dtype share one compile (the time of
    a case was four to eight compiles of interpreted kernels)."""
    from jax.experimental.pallas import tpu as pltpu

    of_the_ids = fa._segment_tiles

    @jax.jit
    def run(every, seg, q, k, v, g):
        def tiles(seg, causal, key, lq, lk, block_q, block_k):
            some = fa._live_tiles(causal, key, lq, lk, block_q, block_k)
            return jnp.where(every, jnp.asarray(some), of_the_ids(
                seg, causal, key, lq, lk, block_q, block_k))

        causal, mask = _call(window, seg)
        fa._segment_tiles = tiles
        try:
            with pltpu.force_tpu_interpret_mode():
                o, lse = fa._fa_forward_pallas(q, k, v, causal, 0.125,
                                               block_q=block, block_k=block,
                                               mask=mask)
                grads = fa._fa_backward_pallas(q, k, v, o, lse, g, causal,
                                               0.125, mask)
        finally:
            fa._segment_tiles = of_the_ids
        return (o, lse, *grads)

    return run


@pytest.mark.parametrize("case,ids", TABLE_IDS)
def test_kernels_walking_the_table_equal_walking_every_tile(case, ids):
    """Both interpreted kernels with the table of live tiles against the
    same kernels walking every tile the mask alone shows: outputs,
    log-sum-exp and the three gradients bit for bit, in float32 and, under
    rising ids, in bf16 as the cell runs them; and against the dense mask as
    the older tests do."""
    lq, lk, window, block, seg = _table_case(case, ids)
    kernels = _kernels_under_ids(lq, lk, window, block)
    rs = np.random.RandomState(case)
    q, k, v, g = (jnp.asarray(rs.randn(2, 2, n, 64).astype("f"))
                  for n in (lq, lk, lk, lq))

    def both(dtype, every):
        return [np.asarray(x.astype("float32")) for x in kernels(
            every, seg, *(x.astype(dtype) for x in (q, k, v, g)))]

    got = {dtype: both(dtype, False) for dtype in ("float32", "bfloat16")[
        :2 if ids == "rising" else 1]}
    for dtype, arrays in got.items():
        for a, b in zip(arrays, both(dtype, True)):
            np.testing.assert_array_equal(a.view("uint32"), b.view("uint32"))
    seen = dense_seen(lq, lk, window, seg)
    want = dense_attention(q, k, v, seen, 0.125)
    np.testing.assert_allclose(got["float32"][0], want, atol=2e-6)
    want = jax.jit(jax.grad(
        lambda *a: jnp.sum(dense_attention(*a, seen, 0.125) * g),
        (0, 1, 2)))(q, k, v)
    for a, b in zip(got["float32"][2:], want):
        np.testing.assert_allclose(a, b, atol=3e-5)


def test_two_samples_with_other_boundaries_run_one_compiled_call():
    """The ids are an operand: batches whose documents end elsewhere (and a
    row of one document beside a row of many) go through one program, each
    equal to the dense mask's result."""
    from jax.experimental.pallas import tpu as pltpu

    rs = np.random.RandomState(7)
    q, k, v, g = (jnp.asarray(rs.randn(2, 2, 512, 64).astype("f"))
                  for _ in range(4))

    @jax.jit
    def run(q, k, v, g, seg):
        mask = fa._Mask(None, seg)
        o, lse = fa._fa_forward_pallas(q, k, v, True, 0.125, block_q=128,
                                       block_k=128, mask=mask)
        return o, fa._fa_backward_pallas(q, k, v, o, lse, g, True, 0.125,
                                         mask)

    for lengths in ([[130, 300, 82], [512]], [[256, 256], [1, 510, 1]],
                    [[512], [64, 64, 384]]):
        seg = segments_of(lengths, "rising")
        with pltpu.force_tpu_interpret_mode():
            o, grads = run(q, k, v, g, seg)
        seen = dense_seen(512, 512, 0, seg)
        np.testing.assert_allclose(
            o, dense_attention(q, k, v, seen, 0.125), atol=2e-6)
        want = jax.grad(lambda *a: jnp.sum(
            dense_attention(*a, seen, 0.125) * g), (0, 1, 2))(q, k, v)
        for a, b in zip(grads, want):
            np.testing.assert_allclose(a, b, atol=3e-5)
    assert run._cache_size() == 1


def test_over_a_mesh_each_batch_shard_builds_its_own_table(monkeypatch):
    """Four samples with other boundaries over a dp mesh of four: both
    kernels run inside the shard_maps with the ids sharded as the batch is,
    each shard's table from its own sample; value and gradients against the
    dense mask (bf16 against the float32 answer on the same inputs)."""
    from jax.experimental.pallas import tpu as pltpu

    from mxnet_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(fa, "_use_pallas", lambda q, v=None: q.shape[-2] >= 256)
    mesh = make_mesh(devices=jax.devices()[:4])
    rs = np.random.RandomState(5)
    q, k, v, g = (jnp.asarray(rs.randn(4, 2, 512, 64).astype("f")).astype(
        "bfloat16") for _ in range(4))
    seg = segments_of([[130, 300, 82], [512], [256, 256], [1, 510, 1]],
                      "rising")

    def sharded(q, k, v):
        with fa.batch_sharded(mesh, ("dp",)):
            return fa.flash_attention(q, k, v, causal=True, segment_ids=seg)

    run = jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum(sharded(q, k, v).astype("float32") * g),
        (0, 1, 2)))
    text = run.trace(q, k, v).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("sdy.manual_computation") == 2
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 2
    with pltpu.force_tpu_interpret_mode():
        value, grads = run(q, k, v)
    seen = dense_seen(512, 512, 0, seg)
    f32 = [x.astype("float32") for x in (q, k, v)]
    want, want_grads = jax.value_and_grad(lambda *a: jnp.sum(
        dense_attention(*a, seen, 0.125) * g), (0, 1, 2))(*f32)
    np.testing.assert_allclose(value, want, rtol=2e-3)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(a.astype("float32"), b, rtol=2 ** -6,
                                   atol=2 ** -5)


@pytest.mark.parametrize("causal,key", [(True, None), (False, (fa.WINDOW, 200)),
                                        (False, None)])
def test_a_call_without_ids_builds_no_table_and_prefetches_none(causal, key):
    """The five cells without ids keep their programs: outside the kernel
    a forward without ids is reshapes alone (no table, nothing prefetched to
    SMEM), a backward ``delta`` and reshapes, its table the constant of
    ``_fa_bwd_pairs``; under ids the forward prefetches its bounds."""
    q = jax.ShapeDtypeStruct((2, 4, 512, 64), "bfloat16")
    lse = jax.ShapeDtypeStruct((2, 4, 512), "float32")
    seg = jax.ShapeDtypeStruct((2, 512), "int32")

    def outer(fn, *args):
        jaxpr = jax.make_jaxpr(fn)(*args)
        (call,) = [e for e in jaxpr.jaxpr.eqns
                   if e.primitive.name == "pallas_call"]
        return ([e.primitive.name for e in jaxpr.jaxpr.eqns],
                call.params["grid_mapping"].num_index_operands, jaxpr.consts)

    names, prefetched, _ = outer(lambda q, k, v: fa._fa_forward_pallas(
        q, k, v, causal, 0.125, mask=key), q, q, q)
    assert names == ["reshape"] * 3 + ["pallas_call", "reshape", "slice",
                                      "squeeze", "reshape"]
    assert prefetched == 0
    names, prefetched, consts = outer(
        lambda q, k, v, o, lse, g: fa._fa_backward_pallas(
            q, k, v, o, lse, g, causal, 0.125, mask=key), q, q, q, q, lse, q)
    assert names == ["convert_element_type"] * 2 + ["mul", "reduce_sum"] \
        + ["reshape"] * 6 + ["pallas_call"] + ["reshape"] * 3
    assert prefetched == 1 and len(consts) == 1
    np.testing.assert_array_equal(consts[0], fa._fa_bwd_pairs(
        causal, key, 512, 512, 512, 512))
    if causal or key:
        names, prefetched, _ = outer(lambda q, k, v, seg: fa._fa_forward_pallas(
            q, k, v, causal, 0.125, mask=fa._Mask(key, seg)), q, q, q, seg)
        assert prefetched == 1 and "reduce_min" in names


@pytest.mark.parametrize("lq,lk,window,block,lengths", SEGMENT_CASES[:4])
def test_the_two_pair_counts_of_a_call_under_ids(lq, lk, window, block,
                                                 lengths):
    """What a call under ids gives to ``telemetry.step_scalar``: the pairs
    the dense mask shows (a sample once, whatever its heads), from the ids
    on the device, and every pair of the plain path's square."""
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(2, 2, lq, 16).astype("f"))
    k = jnp.asarray(rs.randn(2, 2, lk, 16).astype("f"))
    seg = segments_of(lengths)
    kw = dict(mask="window", window=window) if window else dict(causal=True)

    def counted(q, k, seg):
        with telemetry.collect_step_scalars() as scalars:
            fa.flash_attention(q, k, k, segment_ids=seg, **kw)
        return scalars.stacked()

    got = {name: float(v.sum())
           for name, v in jax.jit(counted)(q, k, seg).items()}
    assert got == {
        "mxnet_attention_visible_pairs_total":
            dense_seen(lq, lk, window, seg).sum(),
        "mxnet_attention_walked_pairs_total": 2 * lq * lk}
    # outside a fused step's trace nothing is recorded, and nothing fails
    fa.flash_attention(q, k, k, segment_ids=seg, **kw)


@pytest.mark.parametrize("documents,full,band", [
    # one document: the causal triangle's 528 tiles of 512 x 512, the band's 93
    ([16384], 528, 93),
    # the packed cell's documents in their listed order, by hand: a q tile
    # walks back to the tile in which its first row's document begins (at
    # 0, 5083, 8204, 10243, 11792, 13069, 14090, 14863, 15372, 15761, ...:
    # tiles 0, 9, 16, 20, 23, 25, 27, 29, 30, 30), so q tiles 0-9 walk 1..10
    # tiles, 10-16 walk 2..8, 17-20 2..5, 21-23 2..4, 24-29 2 and 3 three
    # times over, 30 and 31 two each: 55 + 35 + 14 + 9 + 15 + 4; under the
    # window of 1,024 no q tile walks more than three: 85 of the band's 93
    ([5083, 3121, 2039, 1549, 1277, 1021, 773, 509, 389, 251, 191, 127, 54],
     132, 85)])
def test_walked_pairs_of_the_kernels_tiles(monkeypatch, documents, full,
                                           band):
    """Where the kernel runs, the walked pairs are those of the tiles its
    table of live tiles walks, summed on the device from the batch's ids."""
    monkeypatch.setattr(fa, "_use_pallas", lambda q, v=None: True)
    q = jax.ShapeDtypeStruct((1, 32, 16384, 128), jnp.bfloat16)
    seg = segments_of([documents], "rising")
    for (causal, mask), tiles in ((_call(0, seg), full),
                                  (_call(1024, seg), band)):
        with telemetry.collect_step_scalars() as scalars:
            fa._count_pairs(q, q, causal, mask)
        walked = scalars.values["mxnet_attention_walked_pairs_total"]
        assert float(walked[0]) == tiles * 512 * 512
