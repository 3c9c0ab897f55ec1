"""What ISSUE 26 added for a decoder trained by block diffusion over a share
of its experts: the block-diffusion mask in the attention op (kernel under
the TPU interpreter, plain path, blockwise backward) against a dense boolean
mask; dropless top-k routing over the experts held (the shares add up, the
worst imbalance loses nothing); and the program's loss and gradients against
the configuration's plain reference at a small size of the same shape of
layer.  All on the CPU, seeded random weights."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.parallel import expert_parallel
from mxnet_tpu.parallel.expert_parallel import (_PART_ROWS, combine, dispatch,
                                                moe_apply)

import decoder_parity as parity


# --------------------------------------------------------------------------
# the mask
# --------------------------------------------------------------------------
def dense_mask(length, block):
    """The mask as the paper words it, entry by entry (no shared code with
    ``_visible``): row i of ``[noised ; clean]`` may see column j."""
    n = 2 * length
    out = np.zeros((n, n), bool)
    for i in range(n):
        for j in range(n):
            bi, bj = (i % length) // block, (j % length) // block
            if i < length:
                out[i, j] = (bi == bj) if j < length else (bj < bi)
            else:
                out[i, j] = j >= length and bj <= bi
    return out


def dense_attention(q, k, v, seen, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# lengths whose diffusion blocks do and do not end on a tile's edge, and one
# whose halves do not (length 192 under tiles of 128: a tile holds rows of
# both halves)
MASK_CASES = [(128, 4, 128, 128), (192, 6, 128, 128), (384, 6, 256, 128),
              (256, 32, 128, 256), (40, 5, None, None)]


@pytest.mark.parametrize("length,block,block_q,block_k", MASK_CASES)
def test_block_diffusion_predicate_and_tile_ranges(length, block, block_q,
                                                   block_k):
    n = 2 * length
    mask = (fa.BLOCK_DIFFUSION, block)
    want = dense_mask(length, block)
    got = fa._visible(np, np.arange(n)[:, None], np.arange(n)[None, :],
                      False, mask, n, n)
    assert (got == want).all()
    assert want.sum() == length * block + block * block * (
        length // block) ** 2      # own blocks, then the two triangles
    if block_q is None:
        return
    # the K tiles the kernel visits for a q tile are exactly the live ones
    live = fa._live_tiles(False, mask, n, n, block_q, block_k)
    by_hand = want.reshape(n // block_q, block_q, n // block_k,
                           block_k).any(axis=(1, 3))
    assert (live == by_hand).all()
    for qi in range(n // block_q):
        a_lo, a_hi, c_lo, c_hi = (int(x) for x in fa._bd_tile_ranges(
            qi * block_q, (qi + 1) * block_q, length, block, block_k))
        visited = np.zeros(n // block_k, bool)
        visited[a_lo:a_hi] = True
        visited[c_lo:c_hi] = True
        assert (visited == live[qi]).all(), (qi, visited, live[qi])
        assert a_hi <= c_lo or a_lo == a_hi     # no tile twice


@pytest.mark.parametrize("length,block,block_q,block_k", MASK_CASES)
def test_masked_attention_forward_matches_dense_mask(length, block, block_q,
                                                     block_k):
    """The interpreted kernel and the plain path against softmax under the
    dense mask, float32: 2e-6, a few units in the last place of outputs of
    size 1 (both accumulate in float32, in another order)."""
    from jax.experimental.pallas import tpu as pltpu

    n = 2 * length
    rs = np.random.RandomState(length)
    q, k, v = (jnp.asarray(rs.randn(1, 2, n, 64).astype("f"))
               for _ in range(3))
    mask = (fa.BLOCK_DIFFUSION, block)
    want = dense_attention(q, k, v, dense_mask(length, block), 0.125)
    plain, plain_lse = fa._mha_with_lse(q, k, v, False, 0.125, mask)
    np.testing.assert_allclose(plain, want, atol=2e-6)
    if block_q is None:
        return
    with pltpu.force_tpu_interpret_mode():
        o, lse = fa._fa_forward_pallas(q, k, v, False, 0.125,
                                       block_q=block_q, block_k=block_k,
                                       mask=mask)
    np.testing.assert_allclose(o, want, atol=2e-6)
    np.testing.assert_allclose(lse, plain_lse, atol=2e-6)


@pytest.mark.parametrize("length,block,block_q,block_k", MASK_CASES)
def test_masked_attention_backward_matches_dense_mask(length, block, block_q,
                                                      block_k):
    """``_fa_backward_blockwise`` over the live tile pairs, and as one scan
    where a q tile is the whole row, against autodiff through the dense
    mask: 2e-5, the float32 noise of sums over up to 768 keys."""
    n = 2 * length
    rs = np.random.RandomState(length + 1)
    q, k, v, g = (jnp.asarray(rs.randn(1, 2, n, 32).astype("f"))
                  for _ in range(4))
    mask = (fa.BLOCK_DIFFUSION, block)
    seen = dense_mask(length, block)
    want = jax.jit(jax.grad(
        lambda *a: jnp.sum(dense_attention(*a, seen, 0.2) * g),
        (0, 1, 2)))(q, k, v)
    o, lse = fa._mha_with_lse(q, k, v, False, 0.2, mask)
    for bq, bk in ((block_q or n, block_k or n), (n, block_k or n)):
        if n // bq > 1:
            assert not fa._live_tiles(False, mask, n, n, bq, bk).all()
        got = fa._fa_backward_blockwise(q, k, v, o, lse, g, False, 0.2,
                                        block_k=bk, mask=mask, block_q=bq)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, atol=2e-5)


def test_causal_backward_skips_dead_tiles_and_matches():
    """Causal attention at several tiles takes the live-pairs scan too."""
    rs = np.random.RandomState(3)
    q, k, v, g = (jnp.asarray(rs.randn(1, 2, 256, 32).astype("f"))
                  for _ in range(4))
    seen = np.tril(np.ones((256, 256), bool))
    want = jax.jit(jax.grad(
        lambda *a: jnp.sum(dense_attention(*a, seen, 0.2) * g),
        (0, 1, 2)))(q, k, v)
    o, lse = fa._mha_with_lse(q, k, v, True, 0.2)
    assert not fa._live_tiles(True, None, 256, 256, 64, 64).all()
    got = fa._fa_backward_blockwise(q, k, v, o, lse, g, True, 0.2,
                                    block_k=64, block_q=64)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_flash_attention_op_takes_the_mask_with_gqa_and_refuses_misuse():
    rs = np.random.RandomState(0)
    q = nd.array(rs.randn(2, 4, 64, 16).astype("f"))
    kv = nd.array(rs.randn(2, 2, 64, 16).astype("f"))
    o = nd.flash_attention(q, kv, kv, mask="block_diffusion", mask_block=4)
    k32 = jnp.repeat(kv._get(), 2, axis=1)
    want = dense_attention(q._get(), k32, k32, dense_mask(32, 4), 0.25)
    np.testing.assert_allclose(o.asnumpy(), want, atol=2e-6)
    with pytest.raises(mx.MXNetError):
        nd.flash_attention(q, kv, kv, mask="block_diffusion", mask_block=4,
                           causal=True)
    with pytest.raises(mx.MXNetError):
        nd.flash_attention(q, kv, kv, mask="block_diffusion")   # no length
    with pytest.raises(mx.MXNetError):
        nd.flash_attention(q, kv, kv, mask="block_diffusion", mask_block=5)
    with pytest.raises(mx.MXNetError):
        nd.flash_attention(q, kv, kv, mask="sliding")


# --------------------------------------------------------------------------
# the expert layer
# --------------------------------------------------------------------------
def _expert_weights(rs, experts, hidden, width):
    return {name: jnp.asarray(0.3 * rs.randn(*shape).astype("f"))
            for name, shape in (("g", (experts, hidden, width)),
                                ("u", (experts, hidden, width)),
                                ("d", (experts, width, hidden)))}


def _grouped(p, rows, sizes):
    hidden = jax.nn.silu(jax.lax.ragged_dot(rows, p["g"], sizes)) \
        * jax.lax.ragged_dot(rows, p["u"], sizes)
    return jax.lax.ragged_dot(hidden, p["d"], sizes)


def _past(rows):
    return jnp.all(rows == 0, axis=1, keepdims=True)


@jax.custom_vjp
def _dirty_grouped(p, rows, sizes):
    """``_grouped`` that answers NaN in the rows past the last pair (they
    enter as zeros), in its output and in its rows' cotangent, as the TPU's
    grouped product may leave them."""
    return _dirty_fwd(p, rows, sizes)[0]


def _dirty_fwd(p, rows, sizes):
    y, vjp = jax.vjp(lambda p, rows: _grouped(p, rows, sizes), p, rows)
    return jnp.where(_past(rows), jnp.nan, y), (vjp, _past(rows))


def _dirty_bwd(res, g):
    vjp, was_past = res
    dp, drows = vjp(jnp.where(was_past, 0, g))
    return dp, jnp.where(was_past, jnp.nan, drows), None


_dirty_grouped.defvjp(_dirty_fwd, _dirty_bwd)


def _dense_moe(x, router, p, top_k, renormalize, experts):
    """Every expert of ``experts`` on every token, weighed by its gate."""
    probs = jax.nn.softmax(x @ router, -1)
    gates, chosen = jax.lax.top_k(probs, top_k)
    if renormalize:
        gates = gates / gates.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in experts:
        w = jnp.sum(jnp.where(chosen == e, gates, 0.0), -1)
        out = (jax.nn.silu(x @ p["g"][e]) * (x @ p["u"][e])) @ p["d"][e]
        y = y + w[:, None] * out
    return y


def test_the_eight_shares_add_up_to_the_whole_layer():
    """16 experts in 8 shares of 2, 4 a token: the shares' partial results
    sum to the uncut layer's, and that to every expert applied densely.
    1e-5: float32 sums of 4 terms of size 1 in another order."""
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(96, 24).astype("f"))
    router = jnp.asarray(rs.randn(24, 16).astype("f"))
    p = _expert_weights(rs, 16, 24, 12)
    whole, aux = moe_apply(_grouped, p, router, x, capacity_factor=None,
                           top_k=4, renormalize=True)
    assert int(aux["routed_pairs"]) == 96 * 4
    parts, pairs = 0.0, 0
    for share in range(8):
        mine = {k: v[2 * share:2 * share + 2] for k, v in p.items()}
        out, aux = moe_apply(_grouped, mine, router, x, capacity_factor=None,
                             top_k=4, renormalize=True, held=(2 * share, 2))
        parts, pairs = parts + out, pairs + int(aux["routed_pairs"])
    assert pairs == 96 * 4          # every pair on exactly one share
    np.testing.assert_allclose(parts, whole, atol=1e-5)
    np.testing.assert_allclose(
        whole, _dense_moe(x, router, p, 4, True, range(16)), atol=1e-5)


@pytest.mark.parametrize("tokens", [128, 16384, 24000])
def test_dropless_under_the_worst_imbalance(tokens):
    """Every token to the same two experts: all ``tokens * k`` pairs are
    computed, in one part of the sorted rows, in one that is exactly full,
    and in two of which an expert's pairs span both, and the result and its
    gradients are the dense ones."""
    rs = np.random.RandomState(1)
    # positive tokens and a router whose columns 5 and 2 are positive: every
    # token's two largest logits are those two, by a wide margin
    x = jnp.asarray(np.abs(rs.randn(tokens, 16)).astype("f"))
    router = np.zeros((16, 8), "f")
    router[:, 5], router[:, 2] = 3.0, 1.5
    router = jnp.asarray(router)
    p = _expert_weights(rs, 8, 16, 8)

    def layer(x, p):
        return moe_apply(_grouped, p, router, x, capacity_factor=None,
                         top_k=2, renormalize=True)

    out, aux = layer(x, p)
    assert int(aux["routed_pairs"]) == tokens * 2
    assert int(aux["dropped"]) == 0
    assert np.asarray(aux["expert_load"]).tolist() == [
        0, 0, tokens, 0, 0, tokens, 0, 0]
    assert float(aux["load_max_over_mean"]) == pytest.approx(4.0)
    # positive tokens make large sums: the tolerances are relative here
    np.testing.assert_allclose(
        out, _dense_moe(x, router, p, 2, True, range(8)), rtol=1e-5,
        atol=1e-5)
    got = jax.grad(lambda x, p: jnp.sum(jnp.cos(layer(x, p)[0])),
                   (0, 1))(x, p)
    want = jax.grad(lambda x, p: jnp.sum(jnp.cos(
        _dense_moe(x, router, p, 2, True, range(8)))), (0, 1))(x, p)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


# a granule of 8 rows in a part of 32: the edges of the walk at a size where
# twelve cases cost nothing
WALK_GRANULE, WALK_PART = 8, 32


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_live", [0, 1, WALK_GRANULE - 1, WALK_GRANULE,
                                    WALK_GRANULE + 1, WALK_PART])
def test_dispatch_and_combine_walk_live_rows_like_the_plain_forms(
        monkeypatch, n_live, dtype):
    """``dispatch`` and ``combine`` against a gather, two selects and a
    scatter-add of the whole part: values, and the gradients of the tokens,
    the experts' rows and the gates.  12 tokens choose 4: a token comes back
    in several groups of the part, which is the sorted rows 8 to 40 of 48;
    the rows past ``n_live`` hold NaN, as the TPU's grouped product may
    leave them, and ``n_live`` is a traced number."""
    monkeypatch.setattr(expert_parallel, "_GRANULE", WALK_GRANULE)
    rs = np.random.RandomState(7)
    tokens, top_k, d, lo = 12, 4, 6, 8
    sorted_pairs = rs.permutation(tokens * top_k).astype("i4")
    order = jnp.asarray(sorted_pairs[lo:lo + WALK_PART])
    token_of = order // top_k
    x = jnp.asarray(rs.randn(tokens, d).astype("f")).astype(dtype)
    out = jnp.asarray(rs.randn(tokens, d).astype("f"))
    valid = (jnp.arange(WALK_PART) < n_live)
    y = jnp.asarray(rs.randn(WALK_PART, d).astype("f")).astype(dtype)
    dirty = jnp.where(valid[:, None], y, jnp.nan)
    gates = jnp.asarray(rs.rand(tokens, top_k).astype("f"))
    w = jnp.asarray(rs.randn(WALK_PART, d).astype("f"))

    def plain_dispatch(x):
        return jnp.where(valid[:, None], x[token_of], 0)

    def plain_combine(out, y, gates):
        return out.at[token_of].add(
            jnp.where(valid[:, None], y.astype(jnp.float32), 0.0)
            * gates.reshape(-1)[order][:, None])

    got = jax.jit(lambda x, n: dispatch(x, token_of, n))(x, n_live)
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(got, plain_dispatch(x))
    got = jax.jit(lambda n: combine(out, dirty, gates, order, n))(n_live)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, plain_combine(out, y, gates), atol=1e-6)

    # float32 sums in another order; bf16 rounds every term of one
    tol = 1e-5 if dtype == "float32" else 0.05
    dx = jax.jit(jax.grad(lambda x, n: jnp.sum(
        dispatch(x, token_of, n).astype(jnp.float32) * w)))(
            x, n_live)
    want = jax.grad(lambda x: jnp.sum(
        plain_dispatch(x).astype(jnp.float32) * w))(x)
    assert dx.dtype == x.dtype
    np.testing.assert_allclose(dx.astype(jnp.float32),
                               want.astype(jnp.float32), atol=tol)

    def through(fn):
        return lambda out, y, gates, *n: jnp.sum(
            jnp.sin(fn(out, y, gates, *n)) * w[:tokens])

    got = jax.jit(jax.grad(through(
        lambda out, y, gates, n: combine(out, y, gates, order, n)),
        (0, 1, 2)))(
        out, dirty, gates, n_live)
    want = jax.grad(through(plain_combine), (0, 1, 2))(out, y, gates)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and np.isfinite(
            np.asarray(a, dtype="f")).all()
        np.testing.assert_allclose(a.astype(jnp.float32),
                                   b.astype(jnp.float32), atol=tol)


def _whole_part_dropless(expert_fn, params, router, x, top_k, held, part):
    """The layer as it stood before ISSUE 29, kept here as the plain form:
    the same router, key and sort, then the gates gathered for every pair
    and, a part at a time, a gather, two selects and a scatter-add of the
    whole part, differentiated by JAX."""
    first, count = held
    pairs = x.shape[0] * top_k
    n_parts = -(-pairs // part)
    logits = jnp.dot(x, router, precision=jax.lax.Precision.HIGHEST)
    gates, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    local = chosen.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True)
    load = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                   dtype=jnp.int32)
    ends = jnp.cumsum(load)
    pad = n_parts * part - pairs
    tokens = jnp.pad(order // top_k, (0, pad)).reshape(n_parts, part)
    gate_of = jnp.pad(gates.reshape(-1)[order], (0, pad)) \
        .reshape(n_parts, part)
    out = jnp.zeros(x.shape, jnp.float32)
    for i in range(n_parts):
        lo = i * part
        sizes = (jnp.clip(ends, lo, lo + part)
                 - jnp.clip(ends - load, lo, lo + part))
        valid = (lo + jnp.arange(part) < ends[-1])[:, None]
        y = expert_fn(params, jnp.where(valid, x[tokens[i]], 0), sizes)
        out = out.at[tokens[i]].add(
            jnp.where(valid, y.astype(jnp.float32), 0.0)
            * gate_of[i][:, None])
    return out.astype(x.dtype)


def test_a_held_share_under_checkpoint_and_jit_is_the_whole_part_form(
        monkeypatch):
    """``moe_apply`` with a share held, inside ``jax.checkpoint`` inside
    ``jit`` (custom VJPs with a traced trip count, in the loop over live
    parts, in the layer's checkpoint): the result and the
    gradients of the tokens, the router and the experts are those of the
    form that moves whole parts.  Three parts of 64 rows in granules of 16,
    the pairs held ending inside the second."""
    monkeypatch.setattr(expert_parallel, "_GRANULE", 16)
    monkeypatch.setattr(expert_parallel, "_PART_ROWS", 64)
    rs = np.random.RandomState(11)
    x = jnp.asarray(rs.randn(48, 12).astype("f"))
    router = jnp.asarray(rs.randn(12, 8).astype("f"))
    p = _expert_weights(rs, 4, 12, 6)

    @jax.checkpoint
    def layer(x, router, p):
        return moe_apply(_grouped, p, router, x, capacity_factor=None,
                         top_k=4, renormalize=True, held=(2, 4))

    def loss(fn):
        return lambda x, router, p: jnp.sum(jnp.sin(fn(x, router, p)))

    out, aux = jax.jit(layer)(x, router, p)
    pairs = int(aux["routed_pairs"])
    assert 64 < pairs < 128                   # the second part ends the load
    assert pairs <= int(aux["walked_rows"]) < pairs + 16
    plain = functools.partial(_whole_part_dropless, _grouped, top_k=4,
                              held=(2, 4), part=64)

    def plain_layer(x, router, p):
        return plain(p, router, x)

    np.testing.assert_allclose(out, plain_layer(x, router, p), atol=1e-6)
    got = jax.jit(jax.grad(loss(lambda *a: layer(*a)[0]), (0, 1, 2)))(
        x, router, p)
    want = jax.grad(loss(plain_layer), (0, 1, 2))(x, router, p)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=6e-6)


# the routers of the four decoder cells: how each scores, and what confines
# or moves its choice
ROUTERS = {
    # sigmoid plus a bias, the choice within 2 of 4 groups (the Ling cell's)
    "sigmoid, groups and a bias": dict(
        score="sigmoid", bias=True, groups=(4, 2), scale=2.5,
        renorm_eps=1e-20),
    # sigmoid plus a bias (the window cell's)
    "sigmoid and a bias": dict(score="sigmoid", bias=True, scale=2.826,
                               renorm_eps=1e-20),
    # softmax (the packed and the block-diffusion cells')
    "softmax": dict(score="softmax"),
    "softmax, gates as they are": dict(score="softmax", renormalize=False),
}


def _parents_router(score, top_k, groups, keeps):
    """``expert_parallel._router`` as the layer had it until ISSUE 49: the
    product, the scores and the choice as plain JAX, differentiated by JAX,
    so that a layer's checkpoint computes all of it again."""
    def route(x, weight, select_bias):
        logits = jnp.dot(x.astype(jnp.float32), weight.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.softmax(logits, axis=-1) if score == "softmax" \
            else jax.nn.sigmoid(logits)
        if select_bias is None and groups is None:
            return jax.lax.top_k(scores, top_k)
        choice = scores if select_bias is None \
            else scores + select_bias.astype(jnp.float32)
        if groups is not None:
            choice = expert_parallel.limit_to_groups(choice, *groups)
        _, chosen = jax.lax.top_k(choice, top_k)
        return jnp.take_along_axis(scores, chosen, axis=-1), chosen

    return route


def _the_parents_layer(monkeypatch):
    """The layer with the parent's router, and nothing of it named for the
    checkpoint."""
    monkeypatch.setattr(expert_parallel, "_router", _parents_router)
    monkeypatch.setattr(expert_parallel, "keeping", lambda: False)


def _checkpointed_layer(router, grad):
    """``(jitted function, arguments)``: a share of 8 of 32 experts under a
    decoder layer's checkpoint (its policy and its ``checkpoint_keeps``), a
    norm's stand-in before it; the loss alone or with every gradient."""
    how = dict(ROUTERS[router])
    rs = np.random.RandomState(3)
    args = [jnp.asarray(rs.randn(96, 16).astype("f")),
            jnp.asarray(rs.randn(16, 32).astype("f")),
            _expert_weights(rs, 8, 16, 8),
            jnp.asarray(rs.rand(32).astype("f")) if how.pop("bias", False)
            else None]

    def layer(x, weight, p, bias):
        return moe_apply(_grouped, p, weight, x * 1.5, capacity_factor=None,
                         top_k=3, held=(4, 8), select_bias=bias,
                         **{"renormalize": True, **how})[0]

    def loss(*args):
        with fa.checkpoint_keeps():
            return jnp.sum(jnp.sin(jax.checkpoint(
                layer, policy=jax.checkpoint_policies.save_only_these_names(
                    *expert_parallel.KEPT))(*args)))

    over = (0, 1, 2) + ((3,) if args[3] is not None else ())
    return jax.jit(jax.value_and_grad(loss, over) if grad else loss), args


@pytest.mark.parametrize("router", list(ROUTERS))
def test_the_choice_kept_is_the_parents_router_computed_again(monkeypatch,
                                                              router):
    """The loss and every gradient (the tokens', the router's, the experts'
    and the bias's zeros) of a layer whose checkpoint keeps the router's
    choice equal, bit for bit in float32, those of the parent's router,
    which the checkpoint computes again: the arithmetic has not changed.
    The kept bytes are counted under their names, and the form the router
    took."""
    from mxnet_tpu import telemetry

    telemetry.reset()
    fn, args = _checkpointed_layer(router, grad=True)
    got = fn(*args)
    metrics = telemetry.snapshot()["metrics"]
    kept = {s["labels"]["name"]: s["value"] for s in metrics[
        "mxnet_layer_checkpoint_kept_bytes_total"]["samples"] if s["value"]}
    form = "choice" if "sigmoid" in router else "logits"
    # chosen and their scores (96, 3); a softmax keeps the logits (96, 32)
    # too; the walks' plan: one part of 288 sorted pairs, its 8 groups, its
    # live rows and the count of live parts
    assert kept == {
        expert_parallel.KEPT_CHOSEN: 96 * 3 * 4,
        expert_parallel.KEPT_SCORES: 96 * (3 + 32 * (form == "logits")) * 4,
        expert_parallel.KEPT_WALK: (288 + 8 + 1 + 1) * 4}
    assert {s["labels"]["kept"]: s["value"] for s in metrics[
        "mxnet_moe_router_kept_total"]["samples"] if s["value"]} == {form: 1}

    _the_parents_layer(monkeypatch)
    want = _checkpointed_layer(router, grad=True)[0](*args)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(got[1][1]).max()) > 1e-3      # the router's moved


@pytest.mark.parametrize("router", list(ROUTERS))
def test_a_checkpointed_layers_backward_holds_no_router_forward(monkeypatch,
                                                                router):
    """The lowered module of a checkpointed layer's loss and gradients holds
    the router's product once forward and its two transposes backward, and
    the ``top_k``s and the sort of the forward alone: the backward computes
    no product of the router again, no ``top_k`` and no sort, and scatters
    no gradient of a gate into its column.  The parent's router under the
    same checkpoint holds each twice, and that scatter."""
    import re

    def counted(grad):
        fn, args = _checkpointed_layer(router, grad)
        text = fn.lower(*args).as_text()
        # of the router's shape: (96, 16) x (16, 32), or a transpose's
        products = [line for line in text.splitlines()
                    if "stablehlo.dot_general" in line
                    and re.search(r"-> tensor<(96x32|16x32|32x16|96x16)xf32>",
                                  line)]
        return (len(products), text.count("chlo.top_k"),
                text.count("stablehlo.sort"), text.count("\n"),
                text.count("stablehlo.scatter"))

    top_ks = 2 if "groups" in router else 1
    assert counted(grad=False)[:3] == (1, top_ks, 1)
    kept = counted(grad=True)
    assert kept[:3] == (1 + 2, top_ks, 1)
    _the_parents_layer(monkeypatch)
    parents = counted(grad=True)
    assert parents[:3] == (2 + 2, 2 * top_ks, 2)
    assert kept[3] < parents[3]             # and the module is the smaller
    # the gates' gradient reaches its columns without the scatter-add of
    # ``tokens x top_k`` updates that a TPU sorts
    assert kept[4] < parents[4]


@pytest.mark.parametrize("held,part_rows", [((0, 8), 256), ((2, 3), 32)])
def test_walked_rows_follow_the_routed_pairs(monkeypatch, held, part_rows):
    """``walked_rows`` is what the layer's sorted walks cover: whole
    granules of the parts' live rows.  With every expert held, in one part
    that divides, that is the routed pairs exactly; on a share in parts of
    32, less than a granule more, in the last part that holds a pair."""
    monkeypatch.setattr(expert_parallel, "_GRANULE", 16)
    monkeypatch.setattr(expert_parallel, "_PART_ROWS", part_rows)
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(80, 12).astype("f"))
    router = jnp.asarray(rs.randn(12, 8).astype("f"))
    p = _expert_weights(rs, held[1], 12, 6)
    _, aux = moe_apply(_grouped, p, router, x, capacity_factor=None, top_k=2,
                       renormalize=True, held=held)
    pairs, walked = int(aux["routed_pairs"]), int(aux["walked_rows"])
    if held == (0, 8):
        assert walked == pairs == 160
    else:
        assert 32 < pairs < 160 and pairs % 16
        assert walked == -(-pairs // 16) * 16


# 64 tokens choose 4 of 16 experts: 256 sorted rows, four parts of 64 in
# granules of 16.  The experts held, the part's size and the parts that then
# hold a pair, of the static number
LOOP_CASES = {
    "0 of 4": ((15, 1), 64, 0, 4),       # an expert no token chooses
    "1 of 4": ((5, 2), 64, 1, 4),        # 50 pairs
    "2 of 4": ((5, 4), 64, 2, 4),        # 113: the second part partly full
    "4 of 4": ((0, 16), 64, 4, 4),       # every expert held: every part full
    "1 of 1": ((5, 4), 256, 1, 1),       # one part by the shape: one trip
    "0 of 1": ((15, 1), 256, 0, 1),      # one part, and no pair in it: none
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(LOOP_CASES))
def test_the_loop_over_live_parts_is_the_scan_over_every_part(
        monkeypatch, case, dtype):
    """``moe_apply`` on a share, under ``jax.checkpoint`` and ``jit`` (the
    loop over the parts that hold a pair, its hand-written backward with a
    traced trip count, inside the layer's checkpoint), against the form that
    walks every part and is differentiated by JAX: the result and the
    gradients of the tokens, the experts' weights and the router, which the
    gates' gradient reaches the router through.  The program's expert
    function answers NaN past a part's last pair, the plain form's does not.
    In float32 bit for bit (the sums are the same, in the same order: a
    gradient that was ``0 + g`` is ``g``), but for the tokens' gradient
    where two parts or more add into it, or where the plain form's one part
    lies open to XLA beside the router's share of that gradient: XLA makes
    of a part's scatter-add into zeros and the add that follows one
    scatter-add into the sum so far, in either form as it sees fit (one unit
    in the last place measured); in bf16 to the tolerance of the walks' own
    test."""
    held, part, live, parts = LOOP_CASES[case]
    monkeypatch.setattr(expert_parallel, "_GRANULE", 16)
    monkeypatch.setattr(expert_parallel, "_PART_ROWS", part)
    # the parts as the case states them, whatever the share's even load
    monkeypatch.setattr(expert_parallel, "_PART_EVEN_LOADS", 1 << 20)
    rs = np.random.RandomState(11)
    x = jnp.asarray(np.abs(rs.randn(64, 12)).astype("f")).astype(dtype)
    router = rs.randn(12, 16).astype("f")
    router[:, 15] = -5.0          # positive tokens never choose expert 15
    router = jnp.asarray(router)
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype),
                               _expert_weights(rs, held[1], 12, 6))

    @jax.checkpoint
    def layer(x, router, p):
        return moe_apply(_dirty_grouped, p, router, x, capacity_factor=None,
                         top_k=4, renormalize=True, held=held)

    def plain_layer(x, router, p):
        return _whole_part_dropless(_grouped, p, router, x, top_k=4,
                                    held=held, part=part)

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32)))

    out, aux = jax.jit(layer)(x, router, p)
    assert (int(aux["live_parts"]), int(aux["parts"])) == (live, parts)
    assert int(aux["live_parts"]) == -(-int(aux["routed_pairs"]) // part)
    if case == "2 of 4":
        assert int(aux["routed_pairs"]) % part
    want_out = jax.jit(plain_layer)(x, router, p)
    got = jax.jit(jax.grad(loss(lambda *a: layer(*a)[0]), (0, 1, 2)))(
        x, router, p)
    want = jax.jit(jax.grad(loss(plain_layer), (0, 1, 2)))(x, router, p)
    if live == 0:
        assert not np.asarray(out, "f").any()
        assert not any(np.asarray(leaf, "f").any()
                       for leaf in jax.tree_util.tree_leaves(got))
    for i, (a, b) in enumerate(zip(
            jax.tree_util.tree_leaves((out, got)),
            jax.tree_util.tree_leaves((want_out, want)))):
        assert a.dtype == b.dtype
        a, b = np.asarray(a, "f"), np.asarray(b, "f")
        assert np.isfinite(a).all()
        if dtype == "bfloat16":
            np.testing.assert_allclose(a, b, atol=0.05)
        elif (live > 1 or parts == 1) and i == 1:   # the tokens' gradient
            np.testing.assert_allclose(a, b, atol=1e-6)
        else:
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("cell,shares,part_rows,parts", [
    # 16,384 tokens choose 8: four parts of 32,768; a share of 8 holds one
    # pair a token, half a part
    ("block diffusion", 8, 2 * 64, 4),
    # 8,192 tokens choose 8: two parts; a share of 16 (the bias keeps the
    # choice on the first 8) holds one pair a token, a quarter part
    ("window", 16, 4 * 64, 2),
])
def test_live_parts_are_the_parts_that_hold_a_pair(monkeypatch, cell, shares,
                                                   part_rows, parts):
    """``live_parts`` of ``parts`` at the two decoder cells' ratios, on
    routers made as theirs: the held experts' columns, the same for every
    share, so that a token's 8 largest are the copies of one column and this
    share is routed one pair a token.  One part of four and one of two hold
    a pair."""
    monkeypatch.setattr(expert_parallel, "_GRANULE", 16)
    monkeypatch.setattr(expert_parallel, "_PART_ROWS", part_rows)
    rs = np.random.RandomState(5)
    held = 4
    x = jnp.asarray(rs.randn(64, 12).astype("f"))
    router = jnp.asarray(np.tile(rs.randn(12, held).astype("f"), (1, shares)))
    bias = jnp.asarray(np.repeat([1.0, 0.0], held * shares // 2).astype("f"))
    p = _expert_weights(rs, held, 12, 6)
    _, aux = moe_apply(
        _grouped, p, router, x, capacity_factor=None, top_k=8,
        renormalize=True, held=(held, held),
        **({"score": "sigmoid", "select_bias": bias} if cell == "window"
           else {}))
    assert int(aux["routed_pairs"]) == 64
    assert (int(aux["live_parts"]), int(aux["parts"])) == (1, parts)


def test_switch_routing_is_the_same_function_with_a_capacity():
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(32, 8).astype("f"))
    router = jnp.asarray(rs.randn(8, 4).astype("f"))
    p = _expert_weights(rs, 4, 8, 6)

    def one(pe, toks):
        return (jax.nn.silu(toks @ pe["g"]) * (toks @ pe["u"])) @ pe["d"]

    out, aux = moe_apply(one, p, router, x, capacity_factor=8.0)
    assert int(aux["dropped"]) == 0 and "load_balance_loss" in aux
    # with room for every token, top-1 with its raw gate is the dense sum
    np.testing.assert_allclose(
        out, _dense_moe(x, router, p, 1, False, range(4)), atol=1e-5)
    with pytest.raises(mx.MXNetError):
        moe_apply(one, p, router, x, capacity_factor=1.25, top_k=2)
    with pytest.raises(mx.MXNetError):
        moe_apply(one, p, router, x, capacity_factor=1.25, held=(0, 2))


def test_amp_keeps_the_router_float32_and_feeds_the_experts_bf16():
    """Under the bf16 cast policy the op's tokens and router weight arrive
    as they are and the expert weights in bf16."""
    from mxnet_tpu.contrib.amp import _cast_scope, lists

    assert lists.KEEP_DTYPE_INPUTS["_contrib_moe_swiglu"] == (0, 1, 5)
    seen = {}

    def spy(expert_fn, params, router, x, **kw):
        seen.update(x=x.dtype, router=router.dtype,
                    experts=params["g"].dtype)
        return moe_apply(expert_fn, params, router, x, **kw)

    import mxnet_tpu.parallel.expert_parallel as ep

    rs = np.random.RandomState(0)
    args = [nd.array(rs.randn(*s).astype("f")) for s in
            ((2, 8, 16), (16, 8), (8, 16, 4), (8, 16, 4), (8, 4, 16))]
    orig = ep.moe_apply
    ep.moe_apply = spy
    try:
        with _cast_scope("bfloat16"):
            out = nd.moe_swiglu(*args, capacity_factor=0.0, top_k=2,
                                renormalize=True)
    finally:
        ep.moe_apply = orig
    assert seen == {"x": jnp.float32, "router": jnp.float32,
                    "experts": jnp.bfloat16}
    assert out.dtype == np.float32


# --------------------------------------------------------------------------
# the decoder by configuration, against the configuration's reference
# --------------------------------------------------------------------------
# float32 against float32: the gap is the order of the sums (the program sorts
# pairs by expert, the reference runs every expert on every token): a few
# 1e-7 measured, 1e-5 allowed
FLOAT32_GAPS = {"loss_gap": 1e-5, "first_gradient_gap": 1e-5,
                "first_gradient_error": 1e-5, "change_gap": 1e-3}
# bf16 operands: three decimal digits a product, and a router near-tie may
# pick another expert for a token: 1.2e-4 / 0.003 / 0.011 / 0.004 measured at
# seed 5; a float32 result would read a hundred times less
BF16_GAPS = {"loss_gap": 2e-3, "first_gradient_gap": 0.03,
             "first_gradient_error": 0.06, "change_gap": 0.03}


@pytest.mark.parametrize("amp,part_rows,tolerance", [
    (None, None, FLOAT32_GAPS),
    ("bfloat16", None, BF16_GAPS),
    # a layer's 256 sorted rows in four parts of 64 (granules of 16), of
    # which its 128 pairs fill two: the loop over live parts and its
    # backward through the fused step, AMP and the layers' checkpoints
    (None, 64, FLOAT32_GAPS),
    ("bfloat16", 64, BF16_GAPS),
])
def test_program_matches_the_reference_loss_and_every_gradient(
        monkeypatch, amp, part_rows, tolerance):
    if part_rows:
        monkeypatch.setattr(expert_parallel, "_GRANULE", 16)
        monkeypatch.setattr(expert_parallel, "_PART_ROWS", part_rows)
    # every leaf got a gradient of its own, the router's and the norms' too
    _, metrics = parity.matches("sdar_30b_a3b", amp, tolerance)
    # the layers' device scalars left the steps beside the loss: 2 steps x 2
    # layers, about tokens * k * held / width pairs each
    pairs = metrics["mxnet_moe_routed_pairs_total"]["samples"][0]["value"]
    load = metrics["mxnet_moe_expert_load_max_over_mean"]["samples"][0]
    assert load["count"] == 4 and load["sum"] / 4 >= 1.0
    assert 0.6 < pairs / (4 * 2 * 64 * 2 * 4 / 8) < 1.4
    # 128 rows choose 2: a layer's sorted walk covers its one part of 256
    # rows, which is one granule; in parts of 64, the two that the share's
    # 128 pairs fill (the assumed routers: one pair a token) of four
    def total(name):
        return metrics[name]["samples"][0]["value"]

    if part_rows:
        assert pairs == 4 * 128
        assert total("mxnet_moe_walked_rows_total") == 4 * 128
        assert total("mxnet_moe_live_parts_total") == 4 * 2
        assert total("mxnet_moe_parts_total") == 4 * 4
    else:
        assert total("mxnet_moe_walked_rows_total") == 4 * 256
        assert total("mxnet_moe_live_parts_total") == 4
        assert total("mxnet_moe_parts_total") == 4


def test_remat_is_real_in_the_fused_step_and_scopes_are_in_its_table():
    """``LlamaConfig(remat=True)`` recomputes under ``TrainStep``, and the
    expert layer's scopes reach the op table."""
    from mxnet_tpu import profiler
    from mxnet_tpu.parallel.data_parallel import TrainStep

    cfg, build, _, _ = parity.small("sdar_30b_a3b")
    net = build.build_net(cfg, mx.current_context())
    step = TrainStep(net, build.step_loss, optimizer="adam",
                     optimizer_params={"learning_rate": 1e-6})
    batch = build.make_batch(cfg, {"batch": 2, "seq": 32},
                             np.random.default_rng(0))
    assert np.isfinite(float(step(*batch)))
    table = list(profiler.op_scopes().values())[-1]
    scopes = [row["scope"] for row in table.values()]
    assert any("rematted_computation" in s for s in scopes)
    for name in (profiler.SCOPE_MOE_ROUTE, profiler.SCOPE_MOE_EXPERTS):
        assert any(name in s and "transpose(" not in s for s in scopes), name
        assert any(name in s and "transpose(" in s for s in scopes), name
    assert any(profiler.SCOPE_ATTENTION_BWD in s for s in scopes)


def test_counts_of_the_configuration():
    """``counts.py`` against the mask and a hand value."""
    cfg, counts = parity.published("sdar_30b_a3b")
    assert counts.visible_pairs(32, 4) == dense_mask(32, 4).sum()
    assert counts.visible_pairs(4096, 4) == 16_793_600   # a quarter of 8192^2
    assert counts.expected_pairs_per_token(cfg) == 1.0
    assert counts.routed_pair_fwd_flops(cfg) == 6 * 2048 * 768
    # the issue's hand values for one sample's forward, TFLOP
    assert counts.attention_fwd_flops(cfg, 4096, 4) / 1e12 == \
        pytest.approx(0.275, abs=1e-3)
    assert counts.train_flops_per_sample(cfg, 4096, 4) * 2 / 1e12 == \
        pytest.approx(17.9, abs=0.05)


@pytest.mark.parametrize("tokens", [64, 16400])
def test_rows_past_the_last_pair_may_hold_anything(tokens):
    """The TPU's grouped product leaves rows outside its groups unwritten,
    forward and backward (the CPU's writes zeros, so no other test here sees
    it).  The rows past the last pair enter the experts as zeros; an expert
    function that answers NaN there, in its output and in its rows'
    cotangent, changes nothing: not the result, not the tokens' gradient,
    not the gates'.  With 16,400 tokens the sorted rows are two parts, and
    the second, which holds no pair, is never walked."""
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(tokens, 16).astype("f"))
    router = jnp.asarray(rs.randn(16, 8).astype("f"))
    p = _expert_weights(rs, 2, 16, 8)

    def loss(fn, x, p, router):
        out, aux = moe_apply(fn, p, router, x, capacity_factor=None, top_k=2,
                             renormalize=True, held=(3, 2))
        return jnp.sum(jnp.sin(out)), aux["routed_pairs"]

    (got, pairs), got_g = jax.value_and_grad(loss, (1, 2, 3), has_aux=True)(
        _dirty_grouped, x, p, router)
    (want, _), want_g = jax.value_and_grad(loss, (1, 2, 3), has_aux=True)(
        _grouped, x, p, router)
    # a part with slack rows, and for two parts none in the second
    assert 0 < int(pairs) < min(tokens * 2, _PART_ROWS)
    for a, b in zip(jax.tree_util.tree_leaves((got, got_g)),
                    jax.tree_util.tree_leaves((want, want_g))):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=1e-6)
