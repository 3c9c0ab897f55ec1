"""``ops/moe_add_rows.py`` (ISSUE 43): the expert layer's way back as a
Pallas kernel under the TPU interpreter against ``out.at[tokens].add(...)``,
the layer's gradients through it, the gate between the kernel and XLA's
scatter-add (off the gate the lowered layer is the parent's line for line),
the counter that says which a call site took, the rows the way back walked,
and what keeps the set-up short: a step's module holds one kernel a distinct
shape, whatever the layers.  All on the CPU; ``tests/test_tpu_compile.py``
is where the chip's compiler reads the kernel inside the cells' layers."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import moe_add_rows
from mxnet_tpu.parallel import expert_parallel
from mxnet_tpu.parallel.expert_parallel import combine, dispatch, moe_apply

import test_grouped_matmul
from test_grouped_matmul import (_decoder_step, _kernels_lowered,
                                 as_on_a_tpu)

ROWS, TOKENS, WIDTH = 512, 160, 128      # two row tiles of 256


_calls = functools.partial(test_grouped_matmul._calls,
                           family="mxnet_moe_add_rows_calls_total")


# group sizes of a part of ROWS rows in four groups, and whether every group
# holds token 5 (a token in several groups of one row tile)
LAYOUTS = {
    "nothing": ([0, 0, 0, 0], False),
    "one row": ([0, 1, 0, 0], False),
    "a tile's edge": ([100, 156, 0, 0], False),
    "a group's edge inside a tile": ([100, 56, 0, 150], False),
    "the whole part": ([128, 128, 128, 128], False),
    "a token in several groups of one tile": ([3, 0, 4, 2], True),
    "empty groups first, between and last": ([0, 70, 0, 0], False),
}


def _part(sizes, shared, seed=0):
    """``(tokens, rows, gates, out)`` of a part: each group's tokens distinct
    and rising, the rows past the last group NaN and their tokens anything."""
    rs = np.random.RandomState(seed)
    live = sum(sizes)
    groups = []
    for size in sizes:
        chosen = rs.permutation(TOKENS)[:size]
        if shared and size:
            chosen[0] = 5 if 5 not in chosen[1:] else chosen[0]
        groups.append(np.sort(chosen))
    tokens = np.concatenate(
        groups + [rs.randint(0, TOKENS, ROWS - live)]).astype("i4")
    rows = rs.randn(ROWS, WIDTH).astype("f")
    rows[live:] = np.nan
    return (tokens, jnp.asarray(rows).astype(jnp.bfloat16),
            rs.rand(ROWS).astype("f"), rs.randn(TOKENS, WIDTH).astype("f"))


@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_kernel_interpreted_adds_the_rows_that_hold_a_pair(monkeypatch,
                                                            layout, gated):
    """The kernel against ``np.add.at`` over the live rows: float32 sums of
    bf16 rows, with the rows' gates (``combine``) and without (``dispatch``'s
    backward); ``out`` as ``(T, d)`` and as the kernel holds it."""
    sizes, shared = LAYOUTS[layout]
    tokens, rows, gates, out = _part(sizes, shared)
    live = sum(sizes)
    assert not shared or (tokens[:live] == 5).sum() == 3
    want = out.copy()
    np.add.at(want, tokens[:live],
              np.asarray(rows.astype(jnp.float32))[:live]
              * (gates[:live, None] if gated else 1.0))
    with as_on_a_tpu(monkeypatch):
        plan = moe_add_rows.group_plan(jnp.asarray(sizes), ROWS, WIDTH)
        assert plan is not None
        for held in (out, out.reshape(TOKENS, 1, WIDTH)):
            got = moe_add_rows.add_rows(
                jnp.asarray(held), rows, jnp.asarray(tokens), plan,
                jnp.asarray(gates) if gated else None)
            assert got.shape == held.shape and got.dtype == jnp.float32
            np.testing.assert_allclose(got.reshape(want.shape), want,
                                       atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_layers_gradients_through_the_kernel_are_the_plain_forms(
        monkeypatch, dtype):
    """``jax.grad`` through ``dispatch`` and ``combine`` with the groups
    handed in, the kernel interpreted, against a gather, two selects and a
    scatter-add differentiated by JAX: 64 tokens choose 4 of 4 experts, so
    every token comes back in every group; the part is the first 256 sorted
    rows, of which 200 hold a pair and the others NaN."""
    rs = np.random.RandomState(3)
    tokens, top_k, d, part, n_live = 64, 4, 128, 256, 200
    # sorted by expert: the pairs of choice c are the group c, tokens rising
    order = jnp.asarray((np.arange(tokens * top_k).reshape(tokens, top_k)
                         .T.reshape(-1)).astype("i4")[:part])
    token_of = order // top_k
    sizes = jnp.asarray([64, 64, 64, 8], jnp.int32)
    valid = (jnp.arange(part) < n_live)[:, None]
    x = jnp.asarray(rs.randn(tokens, d).astype("f")).astype(dtype)
    out = jnp.asarray(rs.randn(tokens, d).astype("f"))
    y = jnp.asarray(rs.randn(part, d).astype("f")).astype(dtype)
    dirty = jnp.where(valid, y, jnp.nan)
    gates = jnp.asarray(rs.rand(tokens, top_k).astype("f"))
    w = jnp.asarray(rs.randn(part, d).astype("f"))

    def plain(out, x, y, gates):
        rows = jnp.where(valid, x[token_of], 0).astype(jnp.float32)
        got = out.at[token_of].add(
            jnp.where(valid, y.astype(jnp.float32), 0.0)
            * gates.reshape(-1)[order][:, None])
        return jnp.sum(jnp.sin(got) * w[:tokens]) + jnp.sum(rows * w)

    def mine(out, x, y, gates):
        rows = dispatch(x, token_of, n_live, sizes).astype(jnp.float32)
        got = combine(out, y, gates, order, n_live, sizes)
        return jnp.sum(jnp.sin(got) * w[:tokens]) + jnp.sum(rows * w)

    want = jax.value_and_grad(plain, (0, 1, 2, 3))(out, x, y, gates)
    before = _calls("pallas"), _calls("scatter")
    with as_on_a_tpu(monkeypatch):
        got = jax.jit(jax.value_and_grad(mine, (0, 1, 2, 3)))(
            out, x, dirty, gates)
    assert (_calls("pallas"), _calls("scatter")) == (before[0] + 2, before[1])
    # the kernel's sums are float32 with one rounding where the plain
    # form's bf16 cotangent rounds after every term
    tol = 1e-5 if dtype == "float32" else 0.05
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and np.isfinite(
            np.asarray(a, dtype="f")).all()
        np.testing.assert_allclose(np.asarray(a, dtype="f"),
                                   np.asarray(b, dtype="f"), atol=tol,
                                   rtol=1e-5)


def _parents_walks():
    """``expert_parallel._walks`` as it stood before ISSUE 43 (one
    scatter-add of the whole part each way), taking and ignoring the plan
    that the rules now carry."""
    from mxnet_tpu.parallel.expert_parallel import (SCOPE_MOE_ROUTE, _cut,
                                                    _put, _sorted_walk)

    def add_rows(out, rows, tokens, n_live):
        live = (jnp.arange(tokens.shape[0]) < n_live)[:, None]
        return out.at[tokens].add(jnp.where(live, rows, 0).astype(out.dtype))

    def dispatch_rows(tokens_count, x, tokens, n_live, plan):
        def body(lo, live, rows):
            return _put(rows, lo, live, x[_cut(tokens, lo, live)])

        with jax.named_scope(SCOPE_MOE_ROUTE):
            return _sorted_walk(
                tokens.shape[0], n_live, body,
                jnp.zeros(tokens.shape + x.shape[1:], x.dtype))

    def dispatch_fwd(tokens_count, x, tokens, n_live, plan):
        return dispatch_rows(tokens_count, x, tokens, n_live, plan), (
            tokens, n_live)

    def dispatch_bwd(tokens_count, res, g):
        tokens, n_live = res
        with jax.named_scope(SCOPE_MOE_ROUTE):
            dx = add_rows(jnp.zeros((tokens_count,) + g.shape[1:], g.dtype),
                          g, tokens, n_live)
        return dx, None, None, None

    def combine_rows(out, y, gates, order, n_live, plan):
        with jax.named_scope(SCOPE_MOE_ROUTE):
            return add_rows(
                out, y.astype(out.dtype) * gates.reshape(-1)[order][:, None],
                order // gates.shape[1], n_live)

    def combine_fwd(out, y, gates, order, n_live, plan):
        return combine_rows(out, y, gates, order, n_live, plan), (
            y, gates, order, n_live)

    def combine_bwd(res, g):
        y, gates, order, n_live = res
        top_k = gates.shape[1]

        def body(lo, live, carry):
            dy, dgates = carry
            pair = _cut(order, lo, live)
            got = g[pair // top_k]
            per_row = jnp.sum(_cut(y, lo, live).astype(g.dtype) * got, axis=1)
            return (_put(dy, lo, live, got * gates.reshape(-1)[pair][:, None]),
                    dgates.at[pair].add(jnp.where(live, per_row, 0)))

        with jax.named_scope(SCOPE_MOE_ROUTE):
            dy, dgates = _sorted_walk(
                order.shape[0], n_live, body,
                (jnp.zeros_like(y), jnp.zeros(gates.size, gates.dtype)))
            return g, dy, dgates.reshape(gates.shape), None, None, None

    dispatch = jax.custom_vjp(dispatch_rows, nondiff_argnums=(0,))
    combine = jax.custom_vjp(combine_rows)
    dispatch.defvjp(dispatch_fwd, dispatch_bwd)
    combine.defvjp(combine_fwd, combine_bwd)
    return dispatch, combine


@pytest.mark.parametrize("why,width,rows_count,on_a_tpu,mesh", [
    ("the CPU", 256, 256, False, False),
    ("a width of 192", 192, 256, True, False),
    ("a handful of rows", 256, 8, True, False),
    ("a mesh being traced", 256, 256, True, True),
])
def test_off_the_gate_the_layer_is_the_parents_line_for_line(
        monkeypatch, why, width, rows_count, on_a_tpu, mesh):
    """``moe_swiglu``'s dropless layer, forward and backward, lowered with
    the gate closed: the module's text is what the parent's two scatter-adds
    give in the rules' place, and the results are equal bit for bit."""
    from mxnet_tpu.ops.attention_ops import moe_swiglu
    from mxnet_tpu.ops.flash_attention import batch_sharded

    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(1, rows_count // 2, width).astype("f"))
    router = jnp.asarray(rs.randn(width, 8).astype("f"))
    g, u = (jnp.asarray(rs.randn(4, width, 128).astype("f") / 16)
            for _ in range(2))
    d = jnp.asarray(rs.randn(4, 128, width).astype("f") / 16)

    def loss(x, router, g, u, d):
        return jnp.sum(jnp.sin(moe_swiglu(
            x, router, g, u, d, capacity_factor=0, top_k=2,
            experts_first=2).astype(jnp.float32)))

    def lowered_and_grads():
        fn = jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3, 4)))
        with batch_sharded(None, ("dp",)) if mesh \
                else pytest.MonkeyPatch.context():
            return (fn.lower(x, router, g, u, d).as_text(),
                    fn(x, router, g, u, d))

    if on_a_tpu:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = _calls("pallas"), _calls("scatter")
    text, got = lowered_and_grads()
    # combine forward, combine under the vjp, dispatch's backward
    assert (_calls("pallas"), _calls("scatter")) == (before[0], before[1] + 3)
    assert "tpu_custom_call" not in text
    monkeypatch.setattr(expert_parallel, "_walks", _parents_walks)
    parents_text, want = lowered_and_grads()
    assert text == parents_text
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.array_equal(a, b)


def test_the_public_walks_without_groups_keep_the_scatter_add(monkeypatch):
    """``dispatch`` and ``combine`` promise nothing about their indices (a
    random permutation: a token twice in a run of rows): without ``sizes``
    they are XLA's scatter-add on a TPU too, and the kernel's gate is
    closed for a mesh being traced whatever the caller hands in."""
    from mxnet_tpu.ops.flash_attention import batch_sharded

    rs = np.random.RandomState(5)
    order = jnp.asarray(rs.permutation(256).astype("i4"))
    out = jnp.zeros((64, 128), jnp.float32)
    y = jnp.asarray(rs.randn(256, 128).astype("f"))
    gates = jnp.asarray(rs.rand(64, 4).astype("f"))
    sizes = jnp.asarray([256, 0], jnp.int32)
    want = combine(out, y, gates, order, 256)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = _calls("pallas"), _calls("scatter")
    np.testing.assert_array_equal(combine(out, y, gates, order, 256), want)
    assert moe_add_rows.use_pallas(256, 128)
    assert moe_add_rows.group_plan(None, 256, 128) is None
    assert moe_add_rows.group_plan(sizes, 256, 192) is None
    assert moe_add_rows.group_plan(sizes, 200, 128) is None
    with batch_sharded(None, ("dp",)):
        assert moe_add_rows.group_plan(sizes, 256, 128) is None
        np.testing.assert_array_equal(
            combine(out, y, gates, order, 256, sizes), want)
    assert (_calls("pallas"), _calls("scatter")) == (before[0], before[1] + 2)


@pytest.mark.parametrize("on_a_tpu", [False, True])
def test_the_rows_the_way_back_walked(monkeypatch, on_a_tpu):
    """``aux["added_rows"]``: the pairs themselves under the kernel, whole
    parts under XLA's scatter-add; 256 tokens choose 2 of 8 and the share
    holds 3, in parts of 128 rows."""
    monkeypatch.setattr(expert_parallel, "_PART_ROWS", 128)
    monkeypatch.setattr(expert_parallel, "_GRANULE", 128)
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(256, 128).astype("f"))
    router = jnp.asarray(rs.randn(128, 8).astype("f"))
    w = jnp.asarray(rs.randn(3, 128, 128).astype("f") / 16)

    def experts(p, rows, sizes):
        return jax.lax.ragged_dot(rows, p, group_sizes=sizes)

    def run():
        return jax.jit(lambda x: moe_apply(
            experts, w, router, x, capacity_factor=None, top_k=2,
            held=(2, 3)))(x)

    want, aux = run()
    pairs, parts = int(aux["routed_pairs"]), int(aux["live_parts"])
    assert 128 < pairs < 256 and parts == 2
    assert int(aux["added_rows"]) == parts * 128
    if on_a_tpu:
        with as_on_a_tpu(monkeypatch):
            got, aux = run()
        assert int(aux["added_rows"]) == pairs
        np.testing.assert_allclose(got, want, atol=1e-5)


def test_a_steps_module_holds_one_kernel_a_shape_whatever_the_layers(
        monkeypatch):
    """A decoder's fused step traced with the gate open and lowered for the
    TPU: the way back is two private functions of the module (float32 rows
    times gates, the cotangent's rows without), each with one kernel, for
    two layers and for four; the module's call sites are three a layer
    (``combine`` forward, again under the backward loop's ``jax.vjp`` where
    XLA finds it dead, ``dispatch``'s backward) and
    ``mxnet_moe_add_rows_calls_total`` counts the traces, one more a layer
    for the forward that the layer's checkpoint traces again."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for layers in (2, 4):
        step, args = _decoder_step(layers)
        before = _calls("pallas"), _calls("scatter")
        text = step._step.trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
        assert (_calls("pallas"), _calls("scatter")) == (
            before[0] + 4 * layers, before[1])
        functions = set(re.findall(r"func.func private @(_add_rows_call\w*)",
                                   text))
        assert len(functions) == 2
        assert len(re.findall(r"call @_add_rows_call", text)) == 3 * layers
        # the grouped products' eight kernels and these two
        assert text.count("stablehlo.custom_call @tpu_custom_call") == 10


def test_a_float32_step_on_a_tpu_takes_the_kernel_and_the_cpu_none(
        monkeypatch):
    """The way back's gate asks nothing of the experts' dtype: a float32
    step, whose products are ``ragged_dot``, still adds its rows by the
    kernel; on the CPU the module holds no kernel at all."""
    step, args = _decoder_step(2, "float32")
    assert _kernels_lowered(step, args) == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step, args = _decoder_step(2, "float32")
    # one shape: the step's cotangents are float32 as its rows are, so
    # ``dispatch``'s backward differs from ``combine`` by the gates alone
    assert _kernels_lowered(step, args) == 2
