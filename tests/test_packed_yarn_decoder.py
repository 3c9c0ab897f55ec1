"""What ISSUE 32 added for a decoder trained on packed documents with RoPE by
layer kind: YaRN's inverse frequencies and magnitude against values computed
by hand, and ``rope``'s default against the formula it always had; segment
ids in the attention op (plain path, blockwise scan, both Pallas kernels
under the TPU interpreter) against a dense boolean mask, causal and under
the window; a packed row against its documents run alone; the shares of an
expert-parallel deployment against the uncut layer; ``TrainStep`` with a
tuple as ``x``; and a small net of the same shape of layer through
``TrainStep`` against the configuration's plain reference.  All on the CPU,
seeded random weights."""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu.gluon.model_zoo.language import llama
from mxnet_tpu.ops import attention_ops
from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.parallel.data_parallel import TrainStep

from test_block_diffusion_moe import dense_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the published rope_parameters of the full layers
YARN = dict(head_dim=128, base=500000.0, factor=16,
            original_max_position=8192, beta_fast=32, beta_slow=1,
            attention_factor=1.2772588722239782)


# --------------------------------------------------------------------------
# RoPE by kind
# --------------------------------------------------------------------------
def test_yarn_against_values_computed_by_hand():
    """``dim_of(r) = 128 ln(8192 / (2 pi r)) / (2 ln 500000)``: 18.08 for 32
    rotations and 34.98 for one, so the ramp runs over dimensions 18..35.
    Below it the frequencies are the base's own, above it a sixteenth of
    them, inside it the blend: dimension 20 has ramp 2 / 17."""
    inv_freq, magnitude = attention_ops.yarn_rope_parameters(**YARN)
    assert len(inv_freq) == 64 and magnitude == 1.2772588722239782
    assert magnitude == pytest.approx(0.1 * math.log(16) + 1)
    # the same from the factor alone, where the config gives none
    assert attention_ops.yarn_rope_parameters(
        **dict(YARN, attention_factor=None))[1] == pytest.approx(magnitude)
    assert 128 * math.log(8192 / (64 * math.pi)) / (2 * math.log(5e5)) \
        == pytest.approx(18.0811, abs=1e-3)
    assert 128 * math.log(8192 / (2 * math.pi)) / (2 * math.log(5e5)) \
        == pytest.approx(34.9841, abs=1e-3)
    own = [500000.0 ** (-2 * n / 128) for n in range(64)]
    assert inv_freq[0] == 1.0
    assert inv_freq[18] == pytest.approx(own[18], rel=1e-12)        # low
    assert inv_freq[18] == pytest.approx(0.0249554087, rel=1e-8)
    assert inv_freq[20] == pytest.approx(
        own[20] * (15 / 17 + 2 / 17 / 16), rel=1e-12)
    assert inv_freq[20] == pytest.approx(0.0147339210, rel=1e-8)
    assert inv_freq[35] == pytest.approx(own[35] / 16, rel=1e-12)   # high
    assert inv_freq[63] == pytest.approx(1.5344629945e-07, rel=1e-8)
    # not truncated, the ramp's ends are the fractions themselves
    loose, _ = attention_ops.yarn_rope_parameters(**YARN, truncate=False)
    assert loose[18] == own[18] and loose[35] == inv_freq[35]
    assert loose[19] == pytest.approx(own[19] * (1 - 15 / 16 * (
        19 - 18.081135) / (34.984119 - 18.081135)), rel=1e-7)
    assert loose[19] > inv_freq[19]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("positions", [None, "row", "batch"])
def test_rope_default_is_the_formula_it_always_had(positions, dtype):
    """Bit for bit: the inverse frequencies ``base ** (-n / (d / 2))`` in
    float32, cos and sin cast to the input's dtype, no magnitude."""
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(2, 3, 10, 16).astype("f")).astype(dtype)
    pos = {None: None, "row": jnp.arange(10) * 3,
           "batch": jnp.asarray(rs.randint(0, 999, (2, 10)))}[positions]

    def parent(x, positions, base, scale=1.0):
        d = x.shape[-1]
        positions = jnp.arange(x.shape[2]) if positions is None \
            else positions
        freqs = base ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
        angles = (jnp.asarray(positions) * scale)[..., None] * freqs
        angles = angles[None, None] if angles.ndim == 2 else angles[:, None]
        cos = jnp.cos(angles).astype(x.dtype)
        sin = jnp.sin(angles).astype(x.dtype)
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    for base, scale in ((10000.0, 1.0), (500000.0, 0.25)):
        got = attention_ops.rope(x, pos, base=base, scale=scale)
        want = parent(x, pos, base, scale)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got.astype("float32")),
                                      np.asarray(want.astype("float32")))


def test_rope_takes_inverse_frequencies_and_a_magnitude():
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(1, 2, 6, 8).astype("f"))
    pos = jnp.asarray([[0, 1, 2, 0, 1, 0]])
    freqs = (1.0, 0.3, 0.05, 0.001)
    got = np.asarray(attention_ops.rope(x, pos, inv_freq=freqs,
                                        magnitude=1.25))
    angles = np.asarray(pos)[0][:, None] * np.asarray(freqs)
    cos, sin = 1.25 * np.cos(angles), 1.25 * np.sin(angles)
    x1, x2 = np.asarray(x)[..., :4], np.asarray(x)[..., 4:]
    np.testing.assert_allclose(got, np.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1), atol=1e-6)
    # through the op table too, the frequencies a static attribute
    same = nd.rope(nd.array(x), nd.array(pos, dtype="int32"), inv_freq=freqs,
                   magnitude=1.25).asnumpy()
    np.testing.assert_allclose(same, got, atol=1e-6)
    with pytest.raises(mx.MXNetError, match="inv_freq"):
        attention_ops.rope(x, pos, inv_freq=freqs[:3])


def test_config_gives_each_kind_its_rope():
    given = {"full": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                      "original_max_position_embeddings": 8192,
                      "beta_fast": 32, "beta_slow": 1,
                      "attention_factor": 1.2772588722239782},
             "window": {"rope_type": "default", "rope_theta": 250000}}
    cfg = llama.LlamaConfig(hidden_size=512, num_heads=4, num_kv_heads=2,
                            head_dim=128, rope_base=10000.0,
                            rope_parameters=given)
    assert cfg.rope_kwargs("window") == {"base": 250000.0}
    full = cfg.rope_kwargs("full")
    want = attention_ops.yarn_rope_parameters(**YARN)
    assert (full["inv_freq"], full["magnitude"]) == want
    # a kind the mapping leaves out, and every kind without a mapping
    assert llama.LlamaConfig(rope_base=123.0, rope_parameters={
        "full": given["full"]}).rope_kwargs("window") == {"base": 123.0}
    assert llama.LlamaConfig(rope_base=123.0).rope_kwargs("full") \
        == {"base": 123.0}
    for bad in ({"sliding": {}}, {"full": {"rope_type": "llama3"}}):
        with pytest.raises(mx.MXNetError, match="rope_parameters"):
            llama.LlamaConfig(rope_parameters=bad)


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
    want = jax.grad(lambda *a: jnp.sum(dense_attention(*a, seen, 0.125) * g),
                    (0, 1, 2))(q, k, v)
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


def _walking_every_tile(monkeypatch):
    """The kernels under ids as PR 32 had them: every tile the mask alone
    shows is walked."""
    def every_static_tile(seg, causal, mask, lq, lk, block_q, block_k):
        some = fa._live_tiles(causal, mask, lq, lk, block_q, block_k)
        return jnp.broadcast_to(jnp.asarray(some), seg.shape[:1] + some.shape)

    monkeypatch.setattr(fa, "_segment_tiles", every_static_tile)


@pytest.mark.parametrize("case,ids", TABLE_IDS)
def test_kernels_walking_the_table_equal_walking_every_tile(case, ids,
                                                            monkeypatch):
    """Both interpreted kernels with the table of live tiles against the
    same kernels walking every tile the mask alone shows: outputs,
    log-sum-exp and the three gradients bit for bit, in float32 and, under
    rising ids, in bf16 as the cell runs them; and against the dense mask as
    the older tests do."""
    from jax.experimental.pallas import tpu as pltpu

    lq, lk, window, block, seg = _table_case(case, ids)
    causal, mask = _call(window, seg)
    rs = np.random.RandomState(case)
    q, k, v, g = (jnp.asarray(rs.randn(2, 2, n, 64).astype("f"))
                  for n in (lq, lk, lk, lq))

    def both(dtype):
        a = [x.astype(dtype) for x in (q, k, v, g)]
        with pltpu.force_tpu_interpret_mode():
            o, lse = fa._fa_forward_pallas(*a[:3], causal, 0.125,
                                           block_q=block, block_k=block,
                                           mask=mask)
            grads = fa._fa_backward_pallas(*a[:3], o, lse, a[3], causal,
                                           0.125, mask)
        return [np.asarray(x.astype("float32")) for x in (o, lse, *grads)]

    got = {dtype: both(dtype) for dtype in ("float32", "bfloat16")[
        :2 if ids == "rising" else 1]}
    _walking_every_tile(monkeypatch)
    for dtype, arrays in got.items():
        for a, b in zip(arrays, both(dtype)):
            np.testing.assert_array_equal(a.view("uint32"), b.view("uint32"))
    seen = dense_seen(lq, lk, window, seg)
    want = dense_attention(q, k, v, seen, 0.125)
    np.testing.assert_allclose(got["float32"][0], want, atol=2e-6)
    want = jax.grad(lambda *a: jnp.sum(dense_attention(*a, seen, 0.125) * g),
                    (0, 1, 2))(q, k, v)
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


# --------------------------------------------------------------------------
# the decoder by configuration
# --------------------------------------------------------------------------
def _small_mellum(**changes):
    """The benchmark's configuration at a small size of the same shape of
    layer: four layers (window, window, window, full), a window of 8 over
    L = 32, GQA 4 over 2, top-4 of 8 softmax-routed experts with 2 held (the
    first of 4 shares), YaRN at base 100 over an original context of 64 so
    that its ramp (dimensions 2..6) lies inside the head's 8 rotated
    pairs, the window layers at another base."""
    from chipbench.harness.cell import ROOT as BENCH_ROOT, _module

    with open(os.path.join(ROOT, "chipbench", "configs", "mellum2_12b_a2p5b",
                           "config.json")) as f:
        cfg = json.load(f)
    cfg.update(vocab_size=96, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, intermediate_size=96,
               moe_intermediate_size=32, num_experts=2, router_width=8,
               num_experts_per_tok=4, experts_first=0, sliding_window=8)
    cfg["rope_parameters"] = {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 100, "factor": 4,
            "original_max_position_embeddings": 64, "beta_fast": 2,
            "beta_slow": 0.5, "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}}
    cfg.update(changes)
    mods = [_module(BENCH_ROOT, "configs", "mellum2_12b_a2p5b", name)
            for name in ("build", "reference")]
    return (cfg, *mods, _module(BENCH_ROOT, "drivers", "fused_step"))


SPEC = {"batch": 2, "seq": 32, "documents": [13, 9, 5, 3, 2],
        "optimizer": "adam", "amp_dtype": None,
        "optimizer_params": {"learning_rate": 1e-6}}


def _net_with(cfg, build, weights):
    net = build.build_net(cfg, mx.current_context())
    params = net.collect_params()
    for leaf, name in build.leaf_names(cfg, net).items():
        params[name].set_data(nd.array(np.asarray(weights[leaf])))
    return net


def test_the_references_yarn_is_the_programs():
    """Two hands, one function: the reference's float32 blend against the
    program's float64 one, to float32's last places; the ramp's ends."""
    cfg, build, reference, _ = _small_mellum()
    with open(os.path.join(ROOT, "chipbench", "configs", "mellum2_12b_a2p5b",
                           "config.json")) as f:
        real = json.load(f)
    assert reference.yarn_range(
        real["rope_parameters"]["full_attention"], 128) == (18, 35)
    inv_freq, magnitude = reference.rope_of(real, "full_attention")
    want = attention_ops.yarn_rope_parameters(**YARN)
    np.testing.assert_allclose(inv_freq, want[0], rtol=3e-6)
    assert magnitude == want[1]
    plain, one = reference.rope_of(real, "sliding_attention")
    np.testing.assert_allclose(
        plain, [500000.0 ** (-2 * n / 128) for n in range(64)], rtol=3e-6)
    assert one == 1.0
    # the toy's ramp lies inside its 8 pairs, so every part of it is run
    low, high = reference.yarn_range(
        cfg["rope_parameters"]["full_attention"], 16)
    assert (low, high) == (2, 6)


@pytest.mark.parametrize("kinds", [["sliding_attention"] * 3
                                   + ["full_attention"],
                                   ["full_attention"] * 4,
                                   ["sliding_attention"] * 4])
def test_a_packed_row_equals_its_documents_run_alone(kinds):
    """Logits of each document of a packed row against the same document by
    itself, through the net as the benchmark builds it: mask, positions and
    RoPE by kind tied together (a document that saw its neighbour, or kept
    the row's positions, would differ by the size of a logit).  2e-5:
    float32 sums in another order through four layers."""
    cfg, build, reference, _ = _small_mellum(layer_types=kinds)
    net = _net_with(cfg, build, reference.init_params(cfg, 11))
    rng = np.random.default_rng(11)
    (ids, seg), _ = build.make_batch(cfg, SPEC, rng)
    packed = net(nd.array(ids, dtype="int32"),
                 nd.array(seg, dtype="int32")).asnumpy()
    assert np.abs(packed).max() > 0.01
    for row in range(ids.shape[0]):
        starts = np.r_[0, np.flatnonzero(np.diff(seg[row])) + 1, 32]
        assert len(starts) == 6
        for a, b in zip(starts[:-1], starts[1:]):
            alone = net(nd.array(ids[row:row + 1, a:b], dtype="int32"))
            np.testing.assert_allclose(packed[row, a:b], alone.asnumpy()[0],
                                       atol=2e-5)
    # one document a row is the net without ids
    whole = net(nd.array(ids, dtype="int32"),
                nd.array(np.zeros_like(seg), dtype="int32")).asnumpy()
    np.testing.assert_allclose(whole, net(nd.array(ids, dtype="int32"))
                               .asnumpy(), atol=2e-5)
    assert np.abs(whole - packed).max() > 1e-3


def test_the_shares_parts_add_up_to_the_uncut_layer():
    """The model-configs guide's test of the cut: 32 softmax-routed experts
    in 8 shares of 4, 8 a token renormalised, random routers.  Each share's
    ``LlamaMoEMLP`` output summed over the shares is the uncut reference's
    expert layer.  2e-5: float32 sums of 8 terms of size 0.1 in another
    order."""
    cfg, _, reference, _ = _small_mellum(
        num_experts=32, router_width=32, num_experts_per_tok=8)
    rs = np.random.RandomState(2)
    shapes = {"moe.router": (64, 32), "moe.gate": (32, 64, 32),
              "moe.up": (32, 64, 32), "moe.down": (32, 32, 64)}
    p = {k: jnp.asarray(0.3 * rs.randn(*s).astype("f"))
         for k, s in shapes.items()}
    h = jnp.asarray(rs.randn(2, 24, 64).astype("f"))
    with jax.default_matmul_precision("highest"):
        whole = reference.routed_experts(cfg, lambda x: x, h.reshape(-1, 64),
                                         p, 0, 32)
    names = {"router_weight": "moe.router", "gate_proj_weight": "moe.gate",
             "up_proj_weight": "moe.up", "down_proj_weight": "moe.down"}
    total = 0.0
    for share in range(8):
        layer = llama.LlamaMoEMLP(llama.LlamaConfig(
            hidden_size=64, num_heads=4, num_kv_heads=2, num_experts=32,
            moe_capacity_factor=None, moe_top_k=8, moe_renormalize=True,
            moe_experts_held=(4 * share, 4), moe_intermediate_size=32))
        layer.initialize()
        for name, param in layer.collect_params().items():
            value = p[names[name.split("llamamoemlp")[1].split("_", 1)[1]]]
            if value.ndim == 3:
                value = value[4 * share:4 * share + 4]
            param.set_data(nd.array(value))
        part = layer(nd.array(h))._get()
        assert float(jnp.abs(part).max()) > 0
        total = total + part
    np.testing.assert_allclose(total.reshape(-1, 64), whole, atol=2e-5)


@pytest.mark.parametrize("amp,tolerance", [
    # float32 against float32: the gap is the order of the sums: 1e-7 /
    # 1.3e-7 / 5.3e-7 / 2e-5 measured at seed 5, 1e-5 allowed (the change's
    # gap is of differences of float32 weights a step of 1e-6 apart: 1e-3)
    (None, {"loss_gap": 1e-5, "first_gradient_gap": 1e-5,
            "first_gradient_error": 1e-5, "change_gap": 1e-3}),
    # bf16 operands: three decimal digits a product, and a router near-tie
    # may pick another expert of the share for a token; a float32 result
    # reads a thousand times less
    ("bfloat16", {"loss_gap": 2e-3, "first_gradient_gap": 0.1,
                  "first_gradient_error": 0.4, "change_gap": 0.05}),
])
def test_program_matches_the_reference_loss_and_every_gradient(amp,
                                                               tolerance):
    """The toy net through ``TrainStep`` (a tuple as ``x``, the prefetcher's
    staging, the step's first gradient and one Adam update) against the
    configuration's plain reference and the harness's plain Adam."""
    from chipbench.harness import check, loop

    cfg, build, reference, driver = _small_mellum()
    spec = dict(SPEC, amp_dtype=amp)
    telemetry._FAMILIES.pop("mxnet_flash_attention_fwd_calls_total", None)
    telemetry.reset()
    runner = driver.Runner(spec, cfg, build, reference.init_params(cfg, 5))
    pool = loop.make_pool(build, cfg, spec, 5)
    feed = loop.open_feed(pool)
    try:
        got = loop.first_steps(runner, feed, 2)
    finally:
        feed.close()
    assert runner.compiles() == 1       # two orders of documents, one program
    ref = check.follow(reference, cfg, "float32",
                       reference.init_params(cfg, 5), pool[:2], spec)
    assert set(got["first_gradient"]) == set(reference.param_shapes(cfg))
    stats = check.compare(got, ref)
    for name, (value, where) in stats.items():
        assert value <= tolerance[name], (name, value, where)
    for leaf, g in got["first_gradient"].items():
        assert np.abs(g).max() > 0, leaf
    # the assumed routers send this share exactly one pair a token a layer
    metrics = telemetry.snapshot()["metrics"]
    pairs = metrics["mxnet_moe_routed_pairs_total"]["samples"][0]["value"]
    assert pairs == 2 * 4 * 64
    # the pairs the masks show inside the documents, two samples, two steps
    shown = 3 * build.counts.visible_pairs(
        cfg, spec["documents"], "sliding_attention") \
        + build.counts.visible_pairs(cfg, spec["documents"], "full_attention")
    visible, walked = (metrics[name]["samples"][0]["value"] for name in (
        "mxnet_attention_visible_pairs_total",
        "mxnet_attention_walked_pairs_total"))
    assert (visible, walked) == (2 * 2 * shown, 2 * 2 * 4 * 32 * 32)
    calls = {s["labels"]["mask"]: s["value"] for s in metrics[
        "mxnet_flash_attention_fwd_calls_total"]["samples"]}
    assert set(calls) == {"window_segments", "causal_segments"}
    assert calls["window_segments"] == 3 * calls["causal_segments"]


@pytest.mark.parametrize("left_out", ["segments", "yarn", "magnitude",
                                      "window"])
def test_the_parity_test_sees_each_part_left_out(left_out):
    """The reference against a program with one part of the issue left out
    no longer agrees: the float32 comparison above would fail by
    ``first_gradient_error`` or ``loss_gap``, a hundred times over its
    tolerance.  (Positions that run on through the row are not such a part:
    RoPE's products depend on the difference of two positions of one
    document alone, so restarting them moves the rounding and nothing
    else.)"""
    from chipbench.harness import check, loop

    cfg, build, reference, driver = _small_mellum()
    broken = json.loads(json.dumps(cfg))
    full = broken["rope_parameters"]["full_attention"]
    if left_out == "yarn":
        broken["rope_parameters"]["full_attention"] = {
            "rope_type": "default", "rope_theta": full["rope_theta"]}
    elif left_out == "magnitude":
        full["attention_factor"] = 1.0
    elif left_out == "window":
        broken["sliding_window"] = 32

    class Without(driver.Runner):
        """A step that drops the ids."""

        def step(self, batch, span):
            (ids, seg), labels = batch
            if left_out == "segments":
                return self._step(ids, labels)
            return super().step(batch, span)

    runner = Without(SPEC, broken, build, reference.init_params(cfg, 5))
    pool = loop.make_pool(build, cfg, SPEC, 5)
    feed = loop.open_feed(pool)
    try:
        got = loop.first_steps(runner, feed, 1)
    finally:
        feed.close()
    ref = check.follow(reference, cfg, "float32",
                       reference.init_params(cfg, 5), pool[:1], SPEC)
    stats = check.compare(got, ref)
    assert max(stats["first_gradient_error"][0], stats["loss_gap"][0]) > 1e-3


# --------------------------------------------------------------------------
# TrainStep with several arrays as x
# --------------------------------------------------------------------------
def _next_token(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0].mean(-1)


def test_train_step_takes_a_tuple_as_x_and_compiles_once_a_signature():
    from mxnet_tpu.gluon.data.prefetcher import PrefetchIterator

    cfg, build, reference, _ = _small_mellum()
    net = _net_with(cfg, build, reference.init_params(cfg, 3))
    step = TrainStep(net, _next_token, optimizer="adam",
                     optimizer_params={"learning_rate": 1e-3})
    rng = np.random.default_rng(3)
    batches = [build.make_batch(cfg, SPEC, rng) for _ in range(4)]
    losses = [float(step(x, y)) for x, y in batches[:2]]
    assert len(step._seen_sigs) == 1 and len(step._compiled) == 1
    # a list is a tuple; NDArrays are arrays; the prefetcher's staging (it
    # keeps the nesting and wraps the leaves) passes through
    ids, seg = batches[2][0]
    losses.append(float(step([nd.array(ids, dtype="int32"), seg],
                             batches[2][1])))
    feed = PrefetchIterator(iter(batches[3:]))
    try:
        x, y = next(feed)
        assert isinstance(x, tuple) and len(x) == 2
        losses.append(float(step(x, y)))
    finally:
        feed.close()
    assert len(step._seen_sigs) == 1 and len(step._compiled) == 1
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    # another length is another signature
    short = dict(SPEC, seq=16, documents=[9, 4, 3])
    x, y = build.make_batch(cfg, short, rng)
    step(x, y)
    assert len(step._seen_sigs) == 2
    (sig,) = [s for s in step._seen_sigs if s[0] == (2, 16)]
    assert sig == ((2, 16), "int32", (2, 16), "int32", (2, 16), "int32")


def test_train_step_with_one_array_is_as_before():
    """One array as ``x``: the signature it always had, the same loss as the
    same ids in a tuple of one document a row."""
    cfg, build, reference, _ = _small_mellum()
    rng = np.random.default_rng(4)
    (ids, seg), labels = build.make_batch(cfg, SPEC, rng)
    got = []
    for x in (ids, (ids, np.zeros_like(seg))):
        net = _net_with(cfg, build, reference.init_params(cfg, 4))
        step = TrainStep(net, _next_token, optimizer="adam",
                         optimizer_params={"learning_rate": 1e-3})
        got.append([float(step(x, labels)) for _ in range(2)])
        if x is ids:
            assert step._seen_sigs == {((2, 32), "int32", (2, 32), "int32")}
    np.testing.assert_allclose(got[0], got[1], rtol=1e-5)


# --------------------------------------------------------------------------
# what stays as it was, and what refuses
# --------------------------------------------------------------------------
def test_rope_by_kind_adds_no_parameter_and_changes_no_name():
    plain = llama.llama_tiny(qk_norm=True)
    by_kind = llama.llama_tiny(qk_norm=True, rope_parameters={
        "full": {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                 "original_max_position_embeddings": 64}})
    strip = lambda net: [(n[len(net.prefix):], p.shape)
                         for n, p in net.collect_params().items()]
    assert strip(plain) == strip(by_kind)
    assert plain.config.rope_parameters == {}
    # and a default net's forward is the one it was: no ids, base alone
    ids = nd.array(np.arange(12).reshape(1, 12) % 7, dtype="int32")
    plain.initialize()
    jaxpr = str(jax.make_jaxpr(lambda v: plain(
        nd.NDArray._from_jax(v, None))._get())(ids._get()))
    assert "cummax" not in jaxpr and "pow" in jaxpr


def test_serving_and_the_pipeline_refuse_the_new_kinds_by_name():
    cfg = llama.LlamaConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        num_kv_heads=1, rope_parameters={"full": {
            "rope_type": "yarn", "rope_theta": 10000, "factor": 4,
            "original_max_position_embeddings": 64}})
    for apply in (lambda: llama.prefill_apply({}, cfg, None),
                  lambda: llama.decode_apply({}, cfg, None, None, None)):
        with pytest.raises(mx.MXNetError, match="rope_parameters"):
            apply()
    with pytest.raises(mx.MXNetError, match="rope_parameters"):
        llama._refuse_unserved(llama.LlamaConfig(rope_parameters={
            "window": {"rope_theta": 5.0}}))
    # block diffusion takes neither ids (the op refuses them by name) nor
    # RoPE by kind (the configuration does)
    with pytest.raises(mx.MXNetError, match="block-diffusion layout"):
        llama.LlamaConfig(block_diffusion=4, rope_parameters={
            "full": {"rope_theta": 5.0}})
    net = llama.llama_tiny(block_diffusion=4)
    net.initialize()
    ids = nd.array(np.zeros((1, 16)), dtype="int32")
    with pytest.raises(mx.MXNetError, match="segment_ids"):
        net(ids, ids)
    # a pipelined step streams one array
    step = TrainStep.__new__(TrainStep)
    step._pipeline = {"axis": "pp"}
    with pytest.raises(mx.MXNetError, match="one array"):
        step((ids, ids), ids)
