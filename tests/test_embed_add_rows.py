"""``ops/embed_add_rows.py`` (ISSUE 53): the embedding's way back as a Pallas
kernel under the TPU interpreter against ``out.at[ids].add(rows)`` for ids
that repeat, ``F.Embedding``'s gradient through it (alone and with the table
tied to a head product), the gate between the kernel and XLA's scatter-add
(off the gate the lowered step is the parent's line for line), the counter
that says which a call took, and what keeps the set-up short: a step's
module holds one kernel a distinct shape, whatever the call sites.  All on
the CPU; ``tests/test_tpu_compile.py`` is where the chip's compiler reads
the kernel at the cells' shapes."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import embed_add_rows
from mxnet_tpu.ops.registry import OP_TABLE

import test_grouped_matmul
from test_grouped_matmul import as_on_a_tpu

VOCAB = 300

_calls = functools.partial(test_grouped_matmul._calls,
                           family="mxnet_embedding_grad_calls_total")


@pytest.fixture(autouse=True)
def a_lane_tile_is_wide_enough(monkeypatch):
    """The gate's least width is where XLA's scatter-add falls off its cliff
    (2,560); the tests run at a lane tile and at the cells' widths."""
    monkeypatch.setattr(embed_add_rows, "_MIN_WIDTH", 128)


def _distinct(rs, rows):
    return rs.permutation(max(VOCAB, rows))[:rows]


def _zipf(rs, rows):
    """A corpus's draw: the commonest id holds three fifths of the rows, a
    run of some 300 in 512 that crosses the tiles' edges."""
    return np.minimum(rs.zipf(2.0, rows), VOCAB) - 1


# name -> (ids of ``rows`` rows over a table of VOCAB rows, zeros out)
CASES = {
    "every id distinct": (_distinct, True),
    "every id the same": (lambda rs, rows: np.full(rows, 7), True),
    "a Zipf draw": (_zipf, True),
    "ids out of range": (
        lambda rs, rows: rs.randint(-40, VOCAB + 40, rows), True),
    "an out that is not zeros": (
        lambda rs, rows: rs.randint(0, VOCAB // 4, rows), False),
}


@pytest.mark.parametrize("width,rows", [(128, 128), (128, 512), (768, 256),
                                        (2560, 256)])
@pytest.mark.parametrize("case", list(CASES))
def test_kernel_interpreted_adds_rows_whose_ids_repeat(monkeypatch, case,
                                                       width, rows):
    """The kernel against XLA's scatter-add in float32: one chunk of ids
    and several, a table whose last block is partial, a lane tile of width,
    BERT's and the Ling cell's; into zeros (``token_rows_sum``) and into an
    ``out`` handed in (``add_token_rows``: whole blocks here, the
    interpreter slices no partial block of an aliased result)."""
    draw, zeros = CASES[case]
    rs = np.random.RandomState(rows + width)
    ids = draw(rs, rows).astype("i4")
    vocab = max(VOCAB, rows) if draw is _distinct else VOCAB
    if draw is _zipf and rows == 512:
        assert np.bincount(ids).max() > 200
    g = jnp.asarray(rs.randn(rows, width).astype("f"))
    if zeros:
        out = jnp.zeros((vocab, width), jnp.float32)
    else:
        vocab = 3 * embed_add_rows._BLOCK
        out = jnp.asarray(rs.randn(vocab, width).astype("f"))
    want = out.at[np.clip(ids, 0, vocab - 1)].add(g)
    with as_on_a_tpu(monkeypatch):
        got = embed_add_rows.token_rows_sum(vocab, g, jnp.asarray(ids)) \
            if zeros else embed_add_rows.add_token_rows(out, g,
                                                        jnp.asarray(ids))
    assert got.shape == (vocab, width) and got.dtype == jnp.float32
    # a run's sum in another order than XLA's: float32's last bits
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * np.bincount(ids.clip(0)).max())
    untouched = np.setdiff1d(np.arange(vocab), np.clip(ids, 0, vocab - 1))
    assert np.array_equal(np.asarray(got)[untouched],
                          np.asarray(out)[untouched])


def _kernels(text):
    """The lines of a lowered module that call the kernel itself; the copy
    of the cotangent's rows in front of it (``..._apart``) is one more
    custom call a function."""
    calls = [line for line in text.splitlines()
             if "stablehlo.custom_call @tpu_custom_call" in line]
    mine = [line for line in calls
            if re.search(embed_add_rows.KERNEL + r"\b(?!_)", line)]
    assert len(calls) == 2 * len(mine)
    assert sum(embed_add_rows.KERNEL + "_apart" in line
               for line in calls) == len(mine)
    return mine


def _the_parents_embedding(data, weight, input_dim=None, output_dim=None,
                           dtype=None, sparse_grad=False):
    idx = jnp.clip(data.astype(np.int32), 0, weight.shape[0] - 1)
    return jnp.take(weight, idx, axis=0)


def _embedding(ids, table):
    return OP_TABLE["Embedding"].fn(ids, table)


def _shared_embedding(ids, table):
    return OP_TABLE["_contrib_shared_embedding"].fn(ids, table)


@pytest.mark.parametrize("head", ["none", "tied", "handed on"])
def test_the_ops_gradient_is_the_parents_rule(monkeypatch, head):
    """``jax.grad`` of a loss over ``F.Embedding`` through the kernel
    against the gather's own transpose: alone; with the table tied to a head
    product that reads it beside the op (JAX adds the rule's cotangent to
    the head's); and with the head reading the table that
    ``F.shared_embedding`` hands on (the Phi cell's form: the rule adds the
    rows into the head's gradient, one kernel, no sum after it)."""
    rs = np.random.RandomState(3)
    vocab = 3 * embed_add_rows._BLOCK
    table = jnp.asarray(rs.randn(vocab, 128).astype("f"))
    ids = jnp.asarray(_zipf(rs, 2 * 128).reshape(2, 128).astype("i4"))
    mix = jnp.asarray(rs.randn(128, 128).astype("f") / 11)

    def loss(embedding, table):
        rows, read = embedding(ids, table) if head == "handed on" \
            else (embedding(ids, table), table)
        h = jnp.tanh(rows @ mix)
        if head != "none":
            h = jax.nn.log_softmax(h @ read.T, axis=-1)
        return jnp.sum(h * h)

    want = jax.jit(jax.grad(functools.partial(
        loss, lambda ids, table: (_the_parents_embedding(ids, table), table)
        if head == "handed on" else _the_parents_embedding(ids, table))))(
            table)
    mine = jax.jit(jax.grad(functools.partial(
        loss, _shared_embedding if head == "handed on" else _embedding)))
    before = _calls("rows"), _calls("scatter")
    with as_on_a_tpu(monkeypatch):
        got = mine(table)
    assert (_calls("rows"), _calls("scatter")) == (before[0] + 1, before[1])
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * scale)
    if head == "none":
        return
    # the sum after the rule, or none: the kernel reads what it adds to
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = mine.trace(table).lower(lowering_platforms=("tpu",)).as_text()
    kernel, = _kernels(text)
    assert ("output_operand_aliases" in kernel and "operand_index = 4"
            in kernel) == (head == "handed on")


def test_the_forward_and_the_eager_call_are_the_gathers(monkeypatch):
    """On the gate the forward is ``jnp.take`` still (equal bit for bit),
    ids of any shape, and a call that is not traced takes the kernel's
    jitted entry all the same."""
    rs = np.random.RandomState(4)
    table = jnp.asarray(rs.randn(VOCAB, 128).astype("f"))
    ids = jnp.asarray(rs.randint(-3, VOCAB + 3, (2, 2, 64)).astype("i4"))
    want, pull = jax.vjp(lambda t: _the_parents_embedding(ids, t), table)
    with as_on_a_tpu(monkeypatch):
        got, mine = jax.vjp(lambda t: _embedding(ids, t), table)
        g = jnp.asarray(rs.randn(*want.shape).astype("f"))
        np.testing.assert_allclose(mine(g)[0], pull(g)[0], rtol=1e-6,
                                   atol=1e-6)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("why,table,rows,on_a_tpu,mesh", [
    ("the CPU", (VOCAB, 128), 256, False, False),
    ("a mesh being traced", (VOCAB, 128), 256, True, True),
    ("a width off a lane tile", (VOCAB, 192), 256, True, False),
    ("a table narrower than the cliff", (40, 2304), 256, True, False),
    ("rows that are no whole tile", (VOCAB, 128), 96, True, False),
    ("a bfloat16 table", (VOCAB, 128), 256, True, False),
])
def test_the_gate_and_the_counter(monkeypatch, why, table, rows, on_a_tpu,
                                  mesh):
    """Everything but a float32 table of whole lane tiles no narrower than
    the cliff, ids in whole tiles, a TPU and no mesh is XLA's scatter-add,
    counted as such once a trace, and the lowered gradient is the parent's
    line for line."""
    from mxnet_tpu.ops.flash_attention import batch_sharded

    if "cliff" in why:
        monkeypatch.setattr(embed_add_rows, "_MIN_WIDTH", 2560)
    dtype = jnp.bfloat16 if "bfloat16" in why else jnp.float32
    weight = jnp.ones(table, dtype)
    ids = jnp.zeros((rows,), jnp.int32)

    def lowered(embedding):
        fn = jax.jit(jax.grad(lambda t: jnp.sum(
            jnp.sin(embedding(ids, t).astype(jnp.float32)))))
        with batch_sharded(None, ("dp",)) if mesh \
                else pytest.MonkeyPatch.context():
            return fn.lower(weight).as_text()

    if on_a_tpu:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = _calls("rows"), _calls("scatter")
    text = lowered(_embedding)
    assert (_calls("rows"), _calls("scatter")) == (before[0], before[1] + 1)
    assert text == lowered(_the_parents_embedding)
    if not mesh and "CPU" not in why:
        return
    # the same call on the gate
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn = jax.jit(jax.grad(lambda t: jnp.sum(jnp.sin(_embedding(ids, t)))))
    text = fn.trace(weight).lower(lowering_platforms=("tpu",)).as_text()
    assert (_calls("rows"), _calls("scatter")) == (before[0] + 1,
                                                   before[1] + 1)
    assert len(_kernels(text)) == 1


def _decoder_step(hidden=128, mesh=None, tied=False):
    """A small dense decoder's fused step and its operands: a table of 256
    rows of ``hidden`` and 256 ids a batch row."""
    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu.gluon.model_zoo.language import llama
    from mxnet_tpu.parallel.data_parallel import TrainStep

    net = llama.LlamaForCausalLM(llama.LlamaConfig(
        vocab_size=256, hidden_size=hidden, num_layers=1, num_heads=2,
        num_kv_heads=1, head_dim=64, intermediate_size=256,
        tie_embeddings=tied))
    net.initialize()

    def loss(logits, labels):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]

    step = TrainStep(net, loss, optimizer="adam", dtype="bfloat16",
                     optimizer_params={"learning_rate": 1e-4}, mesh=mesh,
                     batch_axes=("dp",) if mesh is not None else None)
    ids = step._stage_batch(np.zeros((4, 256), np.int32)) \
        if mesh is not None else np.zeros((4, 256), np.int32)
    return step, (step.train_params, step.rest_params, step.opt_state,
                  jax.random.PRNGKey(0), ids, ids)


def _lowered(step, args, platform=None, locations=False):
    """The step's module with the nets' running number taken out of its
    parameters' names."""
    traced = step._step.trace(*args)
    lowered = traced.lower(lowering_platforms=(platform,)) if platform \
        else traced.lower()
    return re.sub(r"llamaforcausallm\d+_", "net_",
                  lowered.as_text(debug_info=locations))


@pytest.mark.parametrize("why,hidden,on_a_tpu,mesh,tied", [
    ("the CPU", 128, False, False, False),
    ("the CPU, a tied head", 128, False, False, True),
    ("a mesh", 128, True, True, False),
    ("a width off a lane tile", 192, True, False, False),
    ("a width off a lane tile, a tied head", 192, True, False, True),
])
def test_off_the_gate_a_step_is_the_parents_line_for_line(monkeypatch, why,
                                                          hidden, on_a_tpu,
                                                          mesh, tied):
    """A small decoder's ``TrainStep`` lowered with the gate closed against
    the same step over the parent's ``Embedding`` (a tied head reading the
    very table the embedding was given): one text."""
    from mxnet_tpu.parallel.mesh import make_mesh

    if on_a_tpu:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    platform = "tpu" if on_a_tpu else None
    mesh = make_mesh(devices=jax.devices()[:4]) if mesh else None
    before = _calls("rows"), _calls("scatter")
    text = _lowered(*_decoder_step(hidden, mesh, tied), platform)
    assert (_calls("rows"), _calls("scatter")) == (before[0], before[1] + 1)
    assert embed_add_rows.KERNEL not in text
    monkeypatch.setattr(OP_TABLE["Embedding"], "fn", _the_parents_embedding)
    monkeypatch.setattr(
        OP_TABLE["_contrib_shared_embedding"], "fn",
        lambda ids, table: (_the_parents_embedding(ids, table), table))
    assert text == _lowered(*_decoder_step(hidden, mesh, tied), platform)


@pytest.mark.parametrize("tied", [False, True])
def test_a_steps_module_holds_one_kernel_under_the_embeddings_scope(
        monkeypatch, tied):
    """On the gate the step's module holds the kernel once, behind one
    private function, named under ``mx_embed`` so that the program's
    op-to-scope table gives the kernel, the sort and the relay to the
    embedding; a second site of another shape brings a second function."""
    from mxnet_tpu import profiler

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = _calls("rows"), _calls("scatter")
    text = _lowered(*_decoder_step(tied=tied), "tpu", locations=True)
    assert (_calls("rows"), _calls("scatter")) == (before[0] + 1, before[1])
    assert len(re.findall(r"func.func private @(_call\w*)", text)) == 1
    assert len(re.findall(r"call @_call", text)) == 1
    kernels = [line for line in text.splitlines()
               if "stablehlo.custom_call @tpu_custom_call" in line
               and re.search(embed_add_rows.KERNEL + r"\b(?!_)", line)]
    assert len(kernels) == 1
    # a tied head reads the table the op hands on: the kernel adds into the
    # head's gradient in place
    assert ("operand_index = 4" in kernels[0]) == tied
    # the call site's name stack: the rule's ops are the backward's and the
    # embedding's
    names = dict(re.findall(r'^(#loc\d+) = loc\((.*)\)$', text, re.M))
    site, = re.findall(r"call @_call[^\n]* loc\((#loc\d+)\)", text)
    assert re.match(rf'"jit\(train_step_\w+\)/transpose\(jvp\(mx_forward\)\)/'
                    rf'({profiler.SCOPE_EMBED}/)+jit\(_call\)"', names[site])


def test_two_sites_of_one_shape_share_the_function(monkeypatch):
    """BERT's three tables are three sites: those of one shape are one
    private function of the module with one kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    a, b = (jnp.ones((VOCAB, 128), jnp.float32) for _ in range(2))
    c = jnp.ones((40, 256), jnp.float32)
    ids = jnp.zeros((2, 128), jnp.int32)

    def loss(a, b, c):
        return jnp.sum(_embedding(ids, a) * _embedding(ids + 1, b)) \
            + jnp.sum(_embedding(ids, c))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).trace(a, b, c).lower(
        lowering_platforms=("tpu",)).as_text()
    assert len(re.findall(r"func.func private @(_call\w*)", text)) == 2
    assert len(re.findall(r"call @_call", text)) == 3
    assert len(_kernels(text)) == 2
