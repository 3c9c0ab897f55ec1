"""The Pallas backward kernel of ``flash_attention`` (ISSUE 27) under the TPU
interpreter, against autodiff through dense attention; what reaches its
products and the blockwise scan's; the gate between the two and the counter
that says which a call took; a group of query heads on one key-value head
(ISSUE 50) against K and V repeated by hand.  All on the CPU;
``tests/test_tpu_compile.py`` is where the chip's compiler reads the
kernel."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import flash_attention as fa

from test_block_diffusion_moe import MASK_CASES

# (lq, lk, causal, mask): full, causal, causal with lq < lk (the diagonal
# moved by lk - lq), several tiles a side in each; then the block-diffusion
# lengths of MASK_CASES that divide into tiles (the last one does not: the
# gate's test below)
SHAPES = [(256, 256, False, None), (384, 384, False, None),
          (384, 384, True, None), (1024, 1024, True, None),
          (256, 512, True, None), (128, 384, True, None)] + [
    (2 * length, 2 * length, False, (fa.BLOCK_DIFFUSION, block))
    for length, block, block_q, _ in MASK_CASES if block_q is not None]


def dense_grads(q, k, v, g, causal, mask, scale):
    """Autodiff through softmax over a dense boolean mask, float32 at
    ``highest``: shares ``_visible`` with the program and nothing else."""
    lq, lk = q.shape[2], k.shape[2]
    seen = fa._visible(np, np.arange(lq)[:, None], np.arange(lk)[None, :],
                       causal, mask, lq, lk)

    def attend(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        if seen is not None:
            s = jnp.where(seen, s, -jnp.inf)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1),
                                  v) * g)

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.grad(attend, (0, 1, 2)))(q, k, v)


def inputs(lq, lk, dim, dtype, seed=0):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(1, 2, n, dim).astype("f")).astype(dtype)
                 for n in (lq, lk, lk, lq))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("dim", [64, 128])
@pytest.mark.parametrize("lq,lk,causal,mask", SHAPES)
def test_backward_kernel_interpreted_matches_dense_autodiff(lq, lk, causal,
                                                            mask, dim, dtype):
    """float32: 2e-5, the noise of float32 sums over up to 1,024 keys in
    another order.  bf16: against the float32 answer on the same (rounded)
    inputs, ``p`` and ``ds`` go to bf16 before three products and the
    results to bf16 once: 2^-8 of the gradient's size plus 2^-6 absolute,
    gradients of size up to 4."""
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, g = inputs(lq, lk, dim, dtype)
    scale = dim ** -0.5
    want = dense_grads(*(x.astype("float32") for x in (q, k, v, g)), causal,
                       mask, scale)
    o, lse = fa._mha_with_lse(q, k, v, causal, scale, mask)
    with pltpu.force_tpu_interpret_mode():
        got = fa._fa_backward_pallas(q, k, v, o, lse, g, causal, scale, mask)
    for a, b in zip(got, want):
        assert a.dtype == q.dtype and a.shape == b.shape
        if dtype == "float32":
            np.testing.assert_allclose(a, b, atol=2e-5)
        else:
            np.testing.assert_allclose(a.astype("float32"), b, rtol=2 ** -8,
                                       atol=2 ** -6)


def test_backward_pairs_table_walks_the_live_tiles_k_tile_by_k_tile():
    mask = (fa.BLOCK_DIFFUSION, 4)
    table = fa._fa_bwd_pairs(False, mask, 8192, 8192, 512, 512)
    live = fa._live_tiles(False, mask, 8192, 8192, 512, 512)
    assert table.shape == (3, 80) and live.sum() == 80
    qt, kt, flags = table
    assert live[qt, kt].all() and (np.diff(kt) >= 0).all()
    first, last = flags & fa._FIRST_OF_K != 0, flags & fa._LAST_OF_K != 0
    assert first.sum() == last.sum() == 16      # once a K tile, at its ends
    assert first[0] and last[-1] and (first[1:] == last[:-1]).all()
    # the 24 tiles on the three diagonals hold hidden pairs, the rest none
    # (the kernel compares in all 80: no flag tells them apart)
    _, every = fa._tile_visibility(False, mask, 8192, 8192, 512, 512)
    assert (~every[qt, kt]).sum() == 24
    # nothing hidden, nothing to evaluate; one tile a head at BERT's shape
    assert (fa._fa_bwd_pairs(False, None, 512, 512, 512, 512)
            == [[0], [0], [fa._FIRST_OF_K | fa._LAST_OF_K]]).all()
    # causal with lq > lk: the first K tiles' keys are seen, the rows before
    # the diagonal see nothing and the walk leaves their dq at zero
    assert fa._fa_bwd_pairs(True, None, 512, 256, 128, 128).shape == (3, 3)


def _eqns(jaxpr, kernel=False):
    """``(equation, whether it lies inside a pallas_call)`` of a jaxpr,
    kernels' bodies, loops' and branches' included."""
    for eqn in jaxpr.eqns:
        yield eqn, kernel
        inside = kernel or eqn.primitive.name == "pallas_call"
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, inside)


def _dots(jaxpr):
    """Operand dtypes and precision of every dot_general."""
    for eqn, _ in _eqns(jaxpr):
        if eqn.primitive.name == "dot_general":
            yield (tuple(str(x.aval.dtype) for x in eqn.invars),
                   eqn.params["precision"])


@pytest.mark.parametrize("causal,mask,ids", [
    (True, None, False), (False, (fa.WINDOW, 2048), False),
    (False, (fa.BLOCK_DIFFUSION, 4), False), (True, None, True)],
    ids=["causal", "window", "block_diffusion", "segment_ids"])
def test_a_kernel_step_runs_straight_through(causal, mask, ids):
    """From the first product to the add into ``dq`` a grid step is one
    basic block: the mask is compared in every tile pair walked, with and
    without ids, and the branches left (``pl.when``: the accumulators'
    zeroing and writing, a dead row's step) return nothing, so no score
    tile is carried through one."""
    x = jax.ShapeDtypeStruct((1, 2, 4096, 128), "bfloat16")
    lse = jax.ShapeDtypeStruct((1, 2, 4096), "float32")
    seg = jax.ShapeDtypeStruct((1, 4096), "int32")

    def backward(seg, q, k, v, o, lse, g):
        return fa._fa_backward_pallas(
            q, k, v, o, lse, g, causal, 128 ** -0.5,
            fa._Mask(mask, seg if ids else None))

    jaxpr = jax.make_jaxpr(backward)(seg, x, x, x, x, lse, x).jaxpr
    conds = [eqn for eqn, kernel in _eqns(jaxpr)
             if kernel and eqn.primitive.name == "cond"]
    assert conds and all(not eqn.outvars for eqn in conds)
    assert len(list(_dots(jaxpr))) == 5


@pytest.mark.parametrize("path", ["pallas", "blockwise", "blockwise_pairs"])
def test_products_take_their_operands_in_the_inputs_dtype(path):
    """bf16 inputs reach all five products as bf16, at ``DEFAULT`` (Mosaic
    refuses them the float32 contraction of ``highest``), accumulated in
    float32; float32 inputs stay float32 at the process's precision."""
    causal = path == "blockwise_pairs"       # the scan over live pairs
    fn = fa._fa_backward_pallas if path == "pallas" else \
        fa._fa_backward_blockwise
    one = jax.ShapeDtypeStruct((8, 8), "float32")
    (_, of_the_process), = _dots(jax.make_jaxpr(jnp.dot)(one, one).jaxpr)
    for dtype in ("bfloat16", "float32"):
        x = jax.ShapeDtypeStruct((1, 2, 1024, 64), dtype)
        lse = jax.ShapeDtypeStruct((1, 2, 1024), "float32")
        dots = list(_dots(jax.make_jaxpr(
            lambda q, k, v, o, lse, g: fn(q, k, v, o, lse, g, causal, 0.125)
        )(x, x, x, x, lse, x).jaxpr))
        assert len(dots) == 5
        for operands, precision in dots:
            assert operands == (dtype, dtype)
            if dtype == "float32":
                assert precision == of_the_process
            else:
                assert precision == (jax.lax.Precision.DEFAULT,) * 2


def _calls(path):
    s = [s for s in telemetry.snapshot()["metrics"].get(
        "mxnet_flash_attention_bwd_calls_total", {"samples": []})["samples"]
        if s["labels"] == {"path": path}]
    return s[0]["value"] if s else 0


def test_the_gate_and_the_counter_of_the_path_taken(monkeypatch):
    from jax.experimental.pallas import tpu as pltpu

    def grads(lq, lk, causal=False, dtype="float32"):
        q, k, v, g = inputs(lq, lk, 64, dtype, seed=lq)
        got = jax.grad(lambda q, k, v: jnp.sum(
            fa.flash_attention(q, k, v, causal=causal) * g), (0, 1, 2))(
                q, k, v)
        want = dense_grads(*(x.astype("float32") for x in (q, k, v, g)),
                           causal, None, 0.125)
        for a, b in zip(got, want):
            if dtype == "float32":
                np.testing.assert_allclose(a, b, atol=2e-5)
            else:
                np.testing.assert_allclose(a.astype("float32"), b,
                                           rtol=2 ** -6, atol=2 ** -5)

    before = _calls("pallas"), _calls("blockwise")
    grads(256, 256)                 # no TPU to compile for: the scan
    assert (_calls("pallas"), _calls("blockwise")) == \
        (before[0], before[1] + 1)
    # what the forward's gate answers where the default backend is the chip
    monkeypatch.setattr(fa, "_use_pallas", lambda q, v=None: q.shape[-2] >= 256)
    x = lambda lq, dim=64, dtype="bfloat16": jax.ShapeDtypeStruct(
        (1, 2, lq, dim), dtype)
    assert fa._use_pallas_bwd(x(512), x(512))
    assert fa._use_pallas_bwd(x(256), x(512))
    assert fa._use_pallas_bwd(x(8192, 128), x(8192, 128))
    # float32 products are several passes on either path: the scan's
    assert not fa._use_pallas_bwd(x(512, 64, "float32"),
                                  x(512, 64, "float32"))
    assert not fa._use_pallas_bwd(x(128), x(128))       # short
    assert not fa._use_pallas_bwd(x(320), x(320))       # no tiles
    assert not fa._use_pallas_bwd(x(256), x(320))
    # a row of dq that no VMEM holds beside the tiles
    assert not fa._use_pallas_bwd(x(1 << 17, 128), x(512, 128))
    with pltpu.force_tpu_interpret_mode():
        grads(256, 512, causal=True, dtype="bfloat16")
        # kernel forward, scan backward: a length of no tiles, then float32
        grads(320, 320, causal=True, dtype="bfloat16")
        grads(256, 256)
    assert (_calls("pallas"), _calls("blockwise")) == \
        (before[0] + 1, before[1] + 3)


# --------------------------------------------------------------------------
# a group of query heads on one key-value head (ISSUE 50)
# --------------------------------------------------------------------------
def _shared_kv_calls(group):
    """The counter of the calls traced whose kernels read a shared head, at
    ``group`` query heads a key-value head."""
    family = telemetry.snapshot()["metrics"].get(
        "mxnet_flash_attention_shared_kv_calls_total", {"samples": []})
    return sum(s["value"] for s in family["samples"]
               if s["labels"] == {"group": str(group)})


def group_equals_repeated(monkeypatch, path, rep, **call):
    """``o``, ``dq``, ``dk`` and ``dv`` of the op on ``8 // rep`` key-value
    heads against the same call on K and V repeated by hand to the 8 query
    heads, bit for bit: on the CPU's path (plain forward and scan backward,
    float32), and through both kernels under the TPU interpreter with the
    gate opened (bf16, as the cells run them), where the heads of a group
    read one row of K and V through the block maps and ``dk`` / ``dv`` are
    summed over the group after the call."""
    from jax.experimental.pallas import tpu as pltpu

    kernels = path == "kernels"
    if kernels:
        monkeypatch.setattr(fa, "_use_pallas", lambda q, v=None: True)
    rs = np.random.RandomState(rep)
    q, k, v, g = (jnp.asarray(rs.randn(2, heads, 256, 64).astype("f")).astype(
        "bfloat16" if kernels else "float32")
        for heads in (8, 8 // rep, 8 // rep, 8))

    def run(per_query_head):
        def op(q, k, v):
            return fa.flash_attention(q, per_query_head(k), per_query_head(v),
                                      **call)

        @jax.jit
        def both(q, k, v, g):
            o, vjp = jax.vjp(op, q, k, v)
            return (o, *vjp(g))

        return both(q, k, v, g)

    shared = _shared_kv_calls(rep)
    with pltpu.force_tpu_interpret_mode() if kernels \
            else contextlib.nullcontext():
        got = run(lambda x: x)
        assert _shared_kv_calls(rep) == shared + (kernels and rep > 1)
        want = run(lambda x: jnp.repeat(x, rep, axis=1))
    assert [x.shape for x in got] == [q.shape, q.shape, k.shape, v.shape]
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a.astype("float32")),
                                      np.asarray(b.astype("float32")))


@pytest.mark.parametrize("path", ["plain", "kernels"])
@pytest.mark.parametrize("rep", [1, 2, 8])
@pytest.mark.parametrize("call", [
    dict(causal=True), dict(mask="window", window=100),
    dict(mask="block_diffusion", mask_block=4)],
    ids=["causal", "window", "block_diffusion"])
def test_a_group_on_one_kv_head_equals_k_and_v_repeated_by_hand(
        monkeypatch, call, rep, path):
    group_equals_repeated(monkeypatch, path, rep, **call)


def test_the_counter_of_the_calls_that_read_a_shared_head(monkeypatch):
    """A traced call of 32 query heads on 4 key-value heads through the
    kernels adds one at ``group="8"``; a head of its own adds nowhere, and
    neither does a group off the kernels' gate (the plain path repeats)."""
    def trace(heads, kv_heads):
        x = lambda h: jax.ShapeDtypeStruct((1, h, 512, 128), "bfloat16")
        jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True))(x(heads), x(kv_heads), x(kv_heads))

    def counted():
        return {group: _shared_kv_calls(group) for group in (1, 2, 8)}

    before = counted()
    trace(32, 4)                    # no TPU to compile for: the plain path
    assert counted() == before
    monkeypatch.setattr(fa, "_use_pallas", lambda q, v=None: True)
    trace(32, 32)
    assert counted() == before
    trace(32, 4)
    assert counted() == {1: before[1], 2: before[2], 8: before[8] + 1}
    trace(40, 20)                   # Phi's pairs
    assert counted() == {1: before[1], 2: before[2] + 1, 8: before[8] + 1}


@pytest.mark.parametrize("kv_heads", [4, 32])
def test_the_group_is_in_the_block_maps_and_nowhere_else(kv_heads):
    """32 query heads on 4 key-value heads: outside the kernels nothing
    repeats K or V (the forward is reshapes alone, the backward ``delta``,
    reshapes and a ``reduce_sum`` a gradient of the group), K's and V's block
    maps take the grid's row over the group by one truncating ``div`` (no
    floor divide's ``sign`` and ``select``, ROADMAP D15) and ``dk`` / ``dv``
    are written a query head.  A head of its own traces no division."""
    q, kv = (jax.ShapeDtypeStruct((2, heads, 512, 128), "bfloat16")
             for heads in (32, kv_heads))
    lse = jax.ShapeDtypeStruct((2, 32, 512), "float32")

    def outer_and_maps(fn, *args):
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
        (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        return ([e.primitive.name for e in jaxpr.eqns],
                {m.origin: [e.primitive.name
                            for e in m.index_map_jaxpr.jaxpr.eqns]
                 for m in call.params["grid_mapping"].block_mappings})

    row = ["div"] * (kv_heads != 32)
    names, maps = outer_and_maps(lambda q, k, v: fa._fa_forward_pallas(
        q, k, v, True, 0.125), q, kv, kv)
    assert set(names) <= {"reshape", "pallas_call", "slice", "squeeze"}
    assert maps == {"args[0]": [], "args[1]": row, "args[2]": row,
                    "outputs[0]": [], "outputs[1]": []}
    names, maps = outer_and_maps(
        lambda q, k, v, o, lse, g: fa._fa_backward_pallas(
            q, k, v, o, lse, g, True, 0.125), q, kv, kv, q, lse, q)
    assert "broadcast_in_dim" not in names
    assert names.count("reduce_sum") == 1 + 2 * (kv_heads != 32)
    shared = {"args[2]", "args[3]"}             # after the table: q, k, v
    assert {origin for origin, eqns in maps.items() if "div" in eqns} \
        == (shared if kv_heads != 32 else set())
    assert all(set(eqns) <= {"div", "get"} for eqns in maps.values())
