"""``ops/grouped_matmul.py`` (ISSUE 40): the experts' grouped products as
Pallas kernels under the TPU interpreter against ``jax.lax.ragged_dot`` and
its ``jax.vjp``, the gate between the two, the counter that says which a
call took, and what keeps the set-up short: a step's lowered module holds
one kernel a distinct shape, whatever the number of layers.  All on the CPU;
``tests/test_tpu_compile.py`` is where the chip's compiler reads the
kernels."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import grouped_matmul as gm, moe_add_rows

ROWS = 768      # three row tiles of 256

# the four decoder cells' expert layers: hidden, width, experts held; and the
# narrowest the gate lets through.  At every one of them the kernels' blocks
# hold the whole widths, so which rows a visit reads and writes (what the
# layouts are about) is the same walk at a lane tile as at a cell's widths,
# and the interpreter's time goes with rows x widths
CELLS = {"block diffusion": (2048, 768, 16), "packed": (2304, 896, 8),
         "window": (2048, 1024, 8), "ling": (2560, 768, 8),
         "a lane tile": (128, 128, 8)}


def layout(name, held):
    """Group sizes that bite, over ``ROWS`` rows in tiles of 256."""
    sizes = np.zeros(held, np.int32)
    if name == "empty groups":      # first, last and in the middle
        sizes[[1, 2, 4, 6]] = 200, 56, 300, 212
    elif name == "boundaries inside tiles":
        sizes[:] = ROWS // held
        sizes[0] += 37
        sizes[-1] -= 37
    elif name == "one group":
        sizes[3] = ROWS
    elif name == "a fraction of the rows":
        sizes[[0, 5]] = 130, 70
    return sizes


def as_on_a_tpu(monkeypatch):
    """The gate asks JAX's default backend; the kernels then run under
    Pallas' TPU interpreter."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return pltpu.force_tpu_interpret_mode()


def ragged(rows, weights, sizes):
    return jax.lax.ragged_dot(
        rows, weights, group_sizes=sizes,
        precision=None if rows.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT)


def operands(k, n, held, sizes, dtype, seed=0):
    rs = np.random.RandomState(seed)
    rows = jnp.asarray(rs.randn(ROWS, k).astype("f")).astype(dtype)
    weights = jnp.asarray(rs.randn(held, k, n).astype("f") / k ** 0.5) \
        .astype(dtype)
    dy = jnp.asarray(rs.randn(ROWS, n).astype("f")).astype(dtype)
    live = (np.arange(ROWS) < sizes.sum())[:, None]
    return rows, weights, dy, live


@pytest.mark.parametrize("cell,rows_layout", [
    # every layout at a lane tile of width, and each cell's own widths (the
    # packed cell's are no multiple of 256) at the layout with most in it
    ("a lane tile", "empty groups"), ("a lane tile", "boundaries inside tiles"),
    ("a lane tile", "one group"), ("a lane tile", "a fraction of the rows"),
    ("packed", "empty groups"), ("block diffusion", "empty groups"),
    ("window", "empty groups"), ("ling", "empty groups")])
def test_kernels_interpreted_match_ragged_dot_and_its_vjp(monkeypatch, cell,
                                                          rows_layout):
    """Forward, the rows' gradient and the weights', bf16: each within the
    rounding of one accumulation order of ``ragged_dot`` in float32 on the
    same operands.  The rows past the last group are compared nowhere; a
    NaN planted there, in the rows and in the cotangent, reaches no compared
    row and no gradient."""
    k, n, held = CELLS[cell]
    sizes = layout(rows_layout, held)
    rows, weights, dy, live = operands(k, n, held, sizes, jnp.bfloat16)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    want, pull = jax.vjp(functools.partial(ragged, sizes=jnp.asarray(sizes)),
                         f32(jnp.where(live, rows, 0)), f32(weights))
    want_drows, want_dweights = pull(f32(jnp.where(live, dy, 0)))
    with as_on_a_tpu(monkeypatch):
        assert gm._use_pallas(rows, weights)
        got, pull = jax.vjp(
            lambda r, w: gm.grouped_dot(r, w, jnp.asarray(sizes)),
            jnp.where(live, rows, jnp.nan), weights)
        drows, dweights = pull(jnp.where(live, dy, jnp.nan))
    assert got.dtype == drows.dtype == dweights.dtype == jnp.bfloat16
    assert dweights.shape == weights.shape and drows.shape == rows.shape
    for a, b in ((got, want), (drows, want_drows)):
        a, b = (np.asarray(x, np.float32)[live[:, 0]] for x in (a, b))
        np.testing.assert_allclose(a, b, rtol=2 ** -7, atol=2 ** -7)
    # a group's sum over up to 768 rows, rounded once
    np.testing.assert_allclose(
        np.asarray(dweights, np.float32), want_dweights, rtol=2 ** -7,
        atol=2 ** -7 * float(jnp.max(jnp.abs(want_dweights))))
    assert not np.asarray(dweights[sizes == 0], np.float32).any()


@pytest.mark.parametrize("live", [0, 1, 256, 300, ROWS])
def test_swiglu_interpreted_over_the_live_row_tiles(monkeypatch, live):
    """``silu(gate) * up`` and its gradients against the registered ops in
    float32, on the rows before the last pair; NaN past them, in the
    operands and in the cotangent, reaches none of those; without a plan the
    registered ops themselves, bit for bit."""
    rs = np.random.RandomState(live)
    gate, up, dy = (jnp.asarray(rs.randn(ROWS, 256).astype("f") * 3)
                    .astype(jnp.bfloat16) for _ in range(3))
    sizes = jnp.asarray([0, live, 0, 0], jnp.int32)
    held = (np.arange(ROWS) < live)[:, None]

    def plain(gate, up):
        return jax.nn.silu(gate) * up

    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    want, pull = jax.vjp(plain, f32(gate), f32(up))
    want_grads = pull(f32(dy))
    with as_on_a_tpu(monkeypatch):
        plan = gm.group_plan(sizes, gate, jnp.zeros((4, 256, 256),
                                                    jnp.bfloat16))
        assert int(plan.tiles[0]) == -(-live // gm._row_tile(ROWS))
        got, pull = jax.vjp(lambda g, u: gm.swiglu(g, u, plan),
                            jnp.where(held, gate, jnp.nan),
                            jnp.where(held, up, jnp.nan))
        grads = pull(jnp.where(held, dy, jnp.nan))
    for a, b in zip((got, *grads), (want, *want_grads)):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32)[held[:, 0]],
                                   np.asarray(b)[held[:, 0]], rtol=2 ** -7,
                                   atol=2 ** -7)
    assert np.array_equal(gm.swiglu(gate, up), plain(gate, up))


def test_the_plan_visits_every_tile_of_every_group_in_order():
    """``_visits`` against a walk in Python: group by group, the row tiles
    that hold one of its rows; an empty group once; past the count the last
    visit again."""
    sizes = np.array([0, 300, 0, 212, 1, 255, 0], np.int32)
    ends, group, tile, visits, tiles = (np.asarray(a) for a in gm._visits(
        jnp.asarray(sizes), 1024))
    tm = gm._row_tile(1024)
    assert tiles[0] == -(-sizes.sum() // tm)
    stops = np.cumsum(sizes)
    want = []
    for g, (lo, hi) in enumerate(zip(stops - sizes, stops)):
        tiles = range(lo // tm, (hi - 1) // tm + 1) if hi > lo \
            else [min(lo, 1023) // tm]
        want += [(g, t) for t in tiles]
    assert list(ends) == [0, *stops] and visits[0] == len(want)
    assert list(zip(group, tile))[:len(want)] == want
    assert len(group) == 1024 // tm + len(sizes)
    assert set(zip(group[len(want):], tile[len(want):])) <= {want[-1]}
    # a row tile's visits are consecutive, and so are a group's
    assert list(tile[:len(want)]) == sorted(tile[:len(want)])


def parent_dot(rows, weights, sizes, plan=None):
    """The grouped product as the expert layer wrote it before the
    kernels."""
    return ragged(rows, weights, sizes)


@pytest.mark.parametrize("why,dtype,k,rows_count,on_a_tpu", [
    ("the CPU", "bfloat16", 256, 256, False),
    ("float32", "float32", 256, 256, True),
    ("a width of 192", "bfloat16", 192, 256, True),
    ("a handful of rows", "bfloat16", 256, 8, True),
])
def test_off_the_gate_the_layer_is_the_parents_line_for_line(
        monkeypatch, why, dtype, k, rows_count, on_a_tpu):
    """``moe_swiglu``'s dropless layer, forward and backward, lowered with
    the gate closed: the module's text is what the layer gives with
    ``lax.ragged_dot`` in ``grouped_dot``'s place, and the results are equal
    bit for bit."""
    from mxnet_tpu.ops.attention_ops import moe_swiglu

    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(1, rows_count // 2, k).astype("f"))
    router = jnp.asarray(rs.randn(k, 8).astype("f"))
    g, u = (jnp.asarray(rs.randn(4, k, 128).astype("f") / 16).astype(dtype)
            for _ in range(2))
    d = jnp.asarray(rs.randn(4, 128, k).astype("f") / 16).astype(dtype)

    def loss(x, router, g, u, d):
        return jnp.sum(jnp.sin(moe_swiglu(
            x, router, g, u, d, capacity_factor=0, top_k=2,
            experts_first=2).astype(jnp.float32)))

    def lowered_and_grads():
        fn = jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3, 4)))
        return fn.lower(x, router, g, u, d).as_text(), fn(x, router, g, u, d)

    if on_a_tpu:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # (the way back has a gate of its own: ``tests/test_moe_add_rows.py``)
    monkeypatch.setattr(moe_add_rows, "use_pallas", lambda *a: False)
    assert not gm._use_pallas(jnp.zeros((rows_count, k), dtype), g)
    before = _calls("pallas"), _calls("ragged_dot")
    text, got = lowered_and_grads()
    # three products a part, traced forward, under the vjp and again there
    assert _calls("pallas") == before[0] and _calls("ragged_dot") > before[1]
    monkeypatch.setattr(gm, "grouped_dot", parent_dot)
    monkeypatch.setattr(gm, "group_plan", lambda *a: None)
    parents_text, want = lowered_and_grads()
    assert text == parents_text
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.array_equal(a, b)


def _calls(path, family="mxnet_moe_grouped_dot_calls_total"):
    s = [s for s in telemetry.snapshot()["metrics"].get(
        family, {"samples": []})["samples"]
        if s["labels"] == {"path": path}]
    return s[0]["value"] if s else 0


def test_float32_through_the_gate_is_ragged_dot_exactly(monkeypatch):
    sizes = layout("empty groups", 8)
    rows, weights, dy, _ = operands(256, 128, 8, sizes, jnp.float32)
    want, pull = jax.vjp(functools.partial(ragged, sizes=jnp.asarray(sizes)),
                         rows, weights)
    with as_on_a_tpu(monkeypatch):
        got, mine = jax.vjp(
            lambda r, w: gm.grouped_dot(r, w, jnp.asarray(sizes)), rows,
            weights)
    for a, b in zip((got, *mine(dy)), (want, *pull(dy))):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_the_counter_of_the_path_taken_and_a_mesh_being_traced(monkeypatch):
    """Once a trace a call site, under the label of the path, counted
    outside the jitted entries: two sites of one shape count two."""
    from mxnet_tpu.ops.flash_attention import batch_sharded

    sizes = jnp.asarray(layout("empty groups", 8))
    rows, weights, _, _ = operands(256, 128, 8, np.asarray(sizes),
                                   jnp.bfloat16)
    twice = jax.jit(lambda r, w: gm.grouped_dot(
        gm.grouped_dot(r, w, sizes)[:, :1] * r, w, sizes))
    before = _calls("pallas"), _calls("ragged_dot")
    twice(rows, weights), twice(rows, weights)  # the second traces nothing
    assert (_calls("pallas"), _calls("ragged_dot")) == (before[0],
                                                        before[1] + 2)
    with as_on_a_tpu(monkeypatch):
        twice(rows, weights)
        assert _calls("pallas") == before[0] + 2
        with batch_sharded(None, ("dp",)):
            assert not gm._use_pallas(rows, weights)
            assert gm.group_plan(sizes, rows, weights) is None
            gm.grouped_dot(rows, weights, sizes)
    assert (_calls("pallas"), _calls("ragged_dot")) == (before[0] + 2,
                                                        before[1] + 3)


def test_the_backward_keeps_the_products_own_inputs(monkeypatch):
    """The residuals of the op's ``custom_vjp`` are the rows, the weights
    and the plan's few integers."""
    sizes = jnp.asarray(layout("empty groups", 8))
    rows, weights, _, _ = operands(256, 128, 8, np.asarray(sizes),
                                   jnp.bfloat16)
    with as_on_a_tpu(monkeypatch):
        _, vjp = jax.vjp(lambda r, w: gm.grouped_dot(r, w, sizes), rows,
                         weights)
    kept = sorted((tuple(leaf.shape), str(leaf.dtype))
                  for leaf in jax.tree_util.tree_leaves(vjp)
                  if hasattr(leaf, "shape") and leaf.dtype != jnp.int32)
    assert kept == [((8, 256, 128), "bfloat16"), ((768, 256), "bfloat16")]


def _decoder_step(layers, dtype="bfloat16", width=256):
    """A decoder of ``layers`` sparse layers under their checkpoints and
    its fused step's operands: 128 tokens choose 2 of 8 experts, 4 held."""
    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu.gluon.model_zoo.language import llama
    from mxnet_tpu.parallel.data_parallel import TrainStep

    net = llama.LlamaForCausalLM(llama.LlamaConfig(
        vocab_size=256, hidden_size=128, num_layers=layers, num_heads=2,
        num_kv_heads=1, head_dim=64, intermediate_size=width, num_experts=8,
        moe_capacity_factor=None, moe_top_k=2, moe_experts_held=(2, 4),
        moe_intermediate_size=width, remat=True))
    net.initialize()

    def loss(logits, labels):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]

    step = TrainStep(net, loss, optimizer="adam",
                     dtype=None if dtype == "float32" else dtype,
                     optimizer_params={"learning_rate": 1e-4})
    ids = np.zeros((1, 128), np.int32)
    return step, (step.train_params, step.rest_params, step.opt_state,
                  jax.random.PRNGKey(0), ids, ids)


def _kernels_lowered(step, args):
    text = step._step.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    return text.count("stablehlo.custom_call @tpu_custom_call")


def test_a_steps_module_holds_one_kernel_a_shape_whatever_the_layers(
        monkeypatch):
    """Forward and backward of a decoder's fused step under each layer's
    checkpoint, traced with the gate open and lowered for the TPU (without
    the interpreter, which lowers no custom call): the module holds the
    distinct kernels of one layer, for two layers and for four, since every
    call site reaches the kernels through ``jax.jit`` entries that trace and
    lower once a shape; the sites themselves, 15 a layer, are counted by
    ``mxnet_moe_grouped_dot_calls_total``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    counts, sites = [], []
    for layers in (2, 4):
        step, args = _decoder_step(layers)
        before = _calls("pallas"), _calls("ragged_dot")
        counts.append(_kernels_lowered(step, args))
        assert _calls("ragged_dot") == before[1]
        sites.append(_calls("pallas") - before[0])
    # gate/up and down forward, their two transposes and their two weights'
    # gradients, SwiGLU and its backward; and the way back's two
    # (``tests/test_moe_add_rows.py``)
    assert counts[0] == counts[1] == 8 + 2
    assert sites[1] == 2 * sites[0] and sites[0] >= 2 * 9


@pytest.mark.parametrize("why,dtype,width,on_a_tpu", [
    ("float32", "float32", 256, True),
    ("a width of 192", "bfloat16", 192, True),
    ("the CPU", "bfloat16", 256, False),
])
def test_a_step_off_the_gate_lowers_no_kernel(monkeypatch, why, dtype, width,
                                              on_a_tpu):
    if on_a_tpu:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the way back has a gate of its own, which asks nothing of the experts
    monkeypatch.setattr(moe_add_rows, "use_pallas", lambda *a: False)
    step, args = _decoder_step(2, dtype, width)
    before = _calls("pallas"), _calls("ragged_dot")
    assert _kernels_lowered(step, args) == 0
    assert _calls("pallas") == before[0] and _calls("ragged_dot") > before[1]
