"""The Pallas kernels (attention forward and backward, q/k norm and RoPE, the
experts' grouped products, the delta rule, the selective scan) and the expert
layer, compiled at
real widths for a TPU v5e that is described and not attached: what the chip's
compiler refuses (an operand type, a slice off the tiling, too much VMEM) the
TPU interpreter of ``test_chip_smoke.py`` lets through.  Nothing runs, so
nothing here is a time or a result.

All of these stay in this one file: the worker that is given it is the only
one that loads the TPU's library, so the file cannot be spread over workers
and has to stay short as it is.  Its budget is 200 s under tier-1's six
workers (ROADMAP.md, D8) and it stands at it: 186 to 202 s in PR 41's runs, of
which the four cells' expert layers are 105 (293 before, with each layer
compiled twice).  So a new kernel's compile joins its cell's case: one more
assertion on a program that is compiled already (``_expert_layer_for_v5e``
holds a cell's layer, forward and backward in one program; a cell's attention
shapes are rows of one table), at the cell's own shape and no other, and a
case a shape only where the shape changes what the compiler is asked to
accept.  A sweep over layouts belongs under the TPU interpreter at a lane
tile of width (``test_grouped_matmul.py``)."""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache and cannot be read back without one: the next run would warn
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _attention_for_v5e(one_chip, backward, shape, dim, dtype, ids=False,
                       **call):
    """An attention kernel at ``shape`` (batch, heads, lq, lk) with heads of
    ``dim``, compiled for the chip: the forward, or with ``backward`` the
    backward kernel.  ``heads`` is a number, or ``(query heads, key-value
    heads)`` as the decoder cells call the op: K and V then come with their
    own heads, and what was compiled is held to reading them as they are
    (``_reads_shared_heads``).  ``call`` goes to the path (``causal``, the
    static ``mask``, the forward's blocks); ``ids`` hands it segment ids
    (batch, lk) beside that mask."""
    from mxnet_tpu.ops.flash_attention import (_Mask, _fa_backward_pallas,
                                               _fa_forward_pallas)

    def spec(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    b, heads, lq, lk = shape
    h, hkv = heads if isinstance(heads, tuple) else (heads, heads)
    q, kv, lse = spec((b, h, lq, dim)), spec((b, hkv, lk, dim)), \
        spec((b, h, lq), "float32")
    operands = (q, kv, kv, q, lse, q) if backward else (q, kv, kv)
    path = _fa_backward_pallas if backward else _fa_forward_pallas
    call = dict({"causal": False, "sm_scale": dim ** -0.5}, **call)
    if ids:
        key = call.pop("mask", None)
        compiled = jax.jit(
            lambda seg, *a: path(*a, mask=_Mask(key, seg), **call)).lower(
                spec((b, lk), "int32"), *operands).compile()
    else:
        compiled = jax.jit(functools.partial(path, **call)).lower(
            *operands).compile()
    if h != hkv:
        _reads_shared_heads(compiled, (kv,), h)
        # the forward then needs next to nothing beside its operands (the
        # parent planned 256 MiB at the block-diffusion cell's shape); the
        # backward writes dk and dv a query head and sums the groups after
        if not backward:
            assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
    return compiled


def _reads_shared_heads(compiled, kv, heads):
    """A call of ``heads`` query heads on the fewer key-value heads of
    ``kv`` (K's and V's shapes; ISSUE 50): the kernels' block maps send a
    group to its one row of K and V, so the module broadcasts neither to the
    query heads (the parent's ``bf16[2,4,8,8192,128] broadcast`` before
    every call: 128 MiB each at the block-diffusion cell's shape)."""
    broadcasts = "".join(line for line in compiled.as_text().splitlines()
                         if " broadcast(" in line)
    for b, hkv, lk, dim in (x.shape for x in kv):
        assert f"[{b},{hkv},{heads // hkv},{lk},{dim}]" not in broadcasts


@pytest.mark.parametrize("shape,dim,dtype,causal,blocks", [
    # BERT-base as the benchmark's cell runs it: one pass over the K row
    ((16, 12, 512, 512), 64, "bfloat16", False, (None, None)),
    ((16, 12, 512, 512), 64, "float32", False, (None, None)),
    # the widest score tile the default rule gives (the budget's edge)
    ((2, 4, 512, 1792), 64, "bfloat16", True, (None, None)),
    ((2, 4, 1024, 1024), 128, "float32", False, (None, None)),
    # several K blocks: unrolled, and causal with its skip and lq < lk
    ((2, 12, 2048, 2048), 64, "bfloat16", False, (None, None)),
    ((2, 16, 2048, 2048), 128, "bfloat16", True, (None, None)),
    ((2, 16, 256, 512), 128, "bfloat16", True, (128, 128)),
    ((2, 4, 320, 320), 64, "bfloat16", True, (None, None)),
])
def test_flash_forward_compiles_for_v5e(one_chip, shape, dim, dtype, causal,
                                        blocks):
    text = _attention_for_v5e(one_chip, False, shape, dim, dtype,
                              causal=causal, block_q=blocks[0],
                              block_k=blocks[1]).as_text()
    assert "tpu_custom_call" in text and "mxnet_flash_attention_fwd" in text


@pytest.mark.parametrize("shape,dim,dtype,block,blocks", [
    # the decoder cell's own call: 2 samples of [xt ; x0], L = 4096, the 32
    # query heads on their 4 key-value heads, head 128, block 4; the whole
    # K row of 8,192 resident and tiles of 512 from the shape
    ((2, (32, 4), 8192, 8192), 128, "bfloat16", 4, (None, None)),
    # a block length that is no power of two (vector integer division), a
    # K tile that holds keys of both halves, float32 operands
    ((1, 4, 768, 768), 128, "float32", 6, (256, 256)),
    ((2, 4, 512, 512), 64, "bfloat16", 32, (128, 128)),
])
def test_masked_flash_forward_compiles_for_v5e(one_chip, shape, dim, dtype,
                                               block, blocks):
    from mxnet_tpu.ops.flash_attention import BLOCK_DIFFUSION

    text = _attention_for_v5e(one_chip, False, shape, dim, dtype,
                              mask=(BLOCK_DIFFUSION, block),
                              block_q=blocks[0], block_k=blocks[1]).as_text()
    assert "tpu_custom_call" in text and "mxnet_flash_attention_fwd" in text


def test_masked_flash_backward_compiles_for_v5e(one_chip):
    """The blockwise backward over the live tile pairs at the cell's shape:
    plain XLA, and under a gigabyte of scratch (the dead three quarters of
    the tiles are neither computed nor held)."""
    from mxnet_tpu.ops.flash_attention import (BLOCK_DIFFUSION,
                                               _fa_backward_blockwise)

    bwd = functools.partial(_fa_backward_blockwise, causal=False,
                            sm_scale=128 ** -0.5, mask=(BLOCK_DIFFUSION, 4))
    x = jax.ShapeDtypeStruct((2, 32, 8192, 128), "bfloat16",
                             sharding=one_chip)
    lse = jax.ShapeDtypeStruct((2, 32, 8192), "float32", sharding=one_chip)
    compiled = jax.jit(bwd).lower(x, x, x, x, lse, x).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("shape,dim,dtype,causal,block", [
    # BERT-base as the benchmark's cell runs it: a head is one tile pair
    ((16, 12, 512, 512), 64, "bfloat16", False, None),
    ((16, 12, 512, 512), 64, "float32", False, None),
    # the decoder cell's own call, 32 query heads on 4 key-value heads: 80
    # live pairs of 256 a head, the row of dq (4 MiB in float32) resident,
    # the VMEM limit stated by the call
    ((2, (32, 4), 8192, 8192), 128, "bfloat16", False, 4),
    # causal: the lower triangle's pairs, and lq < lk with its offset
    ((2, 16, 2048, 2048), 128, "bfloat16", True, None),
    ((2, 16, 256, 512), 128, "bfloat16", True, None),
])
def test_flash_backward_kernel_compiles_for_v5e(one_chip, shape, dim, dtype,
                                                causal, block):
    """The Pallas backward: a transposed narrow operand, a transposed
    float32 accumulator, a branch on a prefetched flag and bf16 operands at
    ``DEFAULT`` are all the chip's compiler's to accept or refuse.  Under
    the mask it needs no more scratch in HBM than the scan's gigabyte."""
    from mxnet_tpu.ops.flash_attention import BLOCK_DIFFUSION

    compiled = _attention_for_v5e(
        one_chip, True, shape, dim, dtype, causal=causal,
        mask=(BLOCK_DIFFUSION, block) if block else None)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mxnet_flash_attention_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# the window cell's own call (one sample of 8,192 rows, the 32 query heads
# on their 4 key-value heads, head 128, a window of 2,048: four K tiles of
# 512 wide), a window narrower than a tile, and ``lq < lk`` with K tiles
# that no query sees
WINDOW_SHAPES = [((1, (32, 4), 8192, 8192), 128, "bfloat16", 2048),
                 ((2, 4, 1024, 1024), 128, "bfloat16", 96),
                 ((2, 4, 256, 1024), 64, "bfloat16", 300)]


@pytest.mark.parametrize("shape,dim,dtype,window", WINDOW_SHAPES)
def test_window_flash_forward_compiles_for_v5e(one_chip, shape, dim, dtype,
                                               window):
    """The forward under ``mask="window"``: the band's one range of K tiles
    as a loop with traced bounds, the predicate's two comparisons joined,
    under a name that tells the call from a full one."""
    from mxnet_tpu.ops.flash_attention import WINDOW

    text = _attention_for_v5e(one_chip, False, shape, dim, dtype,
                              mask=(WINDOW, window)).as_text()
    assert "tpu_custom_call" in text
    assert "mxnet_flash_attention_fwd_window" in text


@pytest.mark.parametrize("shape,dim,dtype,window", WINDOW_SHAPES)
def test_window_flash_backward_kernel_compiles_for_v5e(one_chip, shape, dim,
                                                       dtype, window):
    """The backward kernel over the band's tile pairs alone (at the cell's
    shape 70 of the causal 136), a K tile that no query sees walked once."""
    from mxnet_tpu.ops.flash_attention import WINDOW

    compiled = _attention_for_v5e(one_chip, True, shape, dim, dtype,
                                  mask=(WINDOW, window))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "mxnet_flash_attention_bwd_window" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# under segment ids: the packed cell's own calls (1 sample of 16,384 rows of
# 13 documents, 32 heads of 128 on 4 key-value heads; causal on the full
# layers, a window of 1,024 on the others), then lq < lk, two samples to a
# grid and float32
SEGMENT_SHAPES = [((1, (32, 4), 16384, 16384), 128, "bfloat16", 0),
                  ((1, (32, 4), 16384, 16384), 128, "bfloat16", 1024),
                  ((2, 4, 256, 1024), 128, "bfloat16", 300),
                  ((2, 4, 512, 512), 64, "float32", 0)]


def _under_ids_for_v5e(one_chip, backward, shape, dim, dtype, window):
    from mxnet_tpu.ops.flash_attention import WINDOW

    return _attention_for_v5e(
        one_chip, backward, shape, dim, dtype, ids=True, causal=not window,
        mask=(WINDOW, window) if window else None)


@pytest.mark.parametrize("shape,dim,dtype,window", SEGMENT_SHAPES)
def test_segment_flash_forward_compiles_for_v5e(one_chip, shape, dim, dtype,
                                                window):
    """The forward with segment ids as blocked operands beside q and k (a
    column of the q block's, the row's whole along the lanes), causal and
    under the window, each under its own name."""
    text = _under_ids_for_v5e(one_chip, False, shape, dim, dtype,
                              window).as_text()
    assert "tpu_custom_call" in text
    assert ("mxnet_flash_attention_fwd_window_segments" if window
            else "mxnet_flash_attention_fwd_segments") in text


@pytest.mark.parametrize("shape,dim,dtype,window", SEGMENT_SHAPES[:3])
def test_segment_flash_backward_kernel_compiles_for_v5e(one_chip, shape, dim,
                                                        dtype, window):
    """The backward kernel with the ids of the pair's tiles (the q tile's a
    row, the K tile's a column), every pair walked masked."""
    compiled = _under_ids_for_v5e(one_chip, True, shape, dim, dtype, window)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("mxnet_flash_attention_bwd_window_segments" if window
            else "mxnet_flash_attention_bwd_segments") in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# q/k norm and RoPE in one pass (ISSUE 36), at the three decoder cells'
# shapes: rows a sample, samples, and how the positions come (one row of the
# block-diffusion halves' for every sample, none, a row a sample)
QK_SHAPES = [(2, 8192, "row"), (1, 8192, None), (1, 16384, "sample")]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("heads", [32, 4])
@pytest.mark.parametrize("b,l,positions", QK_SHAPES)
def test_qk_norm_rope_kernels_compile_for_v5e(one_chip, b, l, positions,
                                              heads, dtype):
    """Forward and backward kernels through the op's ``custom_vjp``: block
    maps that transpose, a lane roll, a column block of the projection, a
    block of one row of ``dgamma``'s partial sums; and nothing of the
    output's size in HBM beside the operands."""
    from mxnet_tpu.ops import qk_norm_rope as qnr

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    hd = 128
    op = qnr._make_kernel_op(heads, 1e-6)
    x, gamma = spec((b, l, heads * hd), dtype), spec((hd,), "float32")
    table = spec((b, l, 2 * hd) if positions == "sample" else (l, 2 * hd),
                 "float32")
    g = spec((b, heads, l, hd), dtype)
    text = jax.jit(op).lower(x, gamma, table).compile().as_text()
    assert "tpu_custom_call" in text and qnr.KERNEL_FWD in text

    def backward(x, gamma, table, g):
        return jax.vjp(op, x, gamma, table)[1](g)[:2]

    compiled = jax.jit(backward).lower(x, gamma, table, g).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and qnr.KERNEL_BWD in text
    assert qnr.KERNEL_FWD not in text       # nothing of the forward is kept
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("norm,turn", [(True, False), (False, True)])
def test_qk_norm_rope_alone_norm_or_turn_compiles_for_v5e(one_chip, norm,
                                                          turn):
    """The window cell's full layers (the norm alone) and a configuration
    without q/k norm (the turn alone, whose backward reads no projection)."""
    from mxnet_tpu.ops import qk_norm_rope as qnr

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    op = qnr._make_kernel_op(32, 1e-6)
    x = spec((1, 8192, 4096), "bfloat16")
    gamma = spec((128,), "float32") if norm else None
    table = spec((8192, 256), "float32") if turn else None
    g = spec((1, 32, 8192, 128), "bfloat16")
    compiled = jax.jit(lambda g, *given: jax.vjp(op, *given)[1](g)).lower(
        g, x, gamma, table).compile()
    assert qnr.KERNEL_BWD in compiled.as_text()
    assert qnr.KERNEL_FWD in jax.jit(op).lower(
        x, gamma, table).compile().as_text()


def test_the_steps_table_resolves_the_qk_kernels_to_their_part(one_chip,
                                                              monkeypatch):
    """A small decoder's fused step with both gates open, compiled for the
    chip: the op's kernels (forward, recomputed forward and backward) are
    rows of ``mx_rope`` where the layer turns and of ``mx_norm`` where it
    only normalises, by the scope they were called under: a custom call is
    fused with nothing, so none of them reads ``mixed``."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from mxnet_tpu.gluon.model_zoo.language import llama
    from mxnet_tpu.ops import flash_attention as fa
    from mxnet_tpu.ops import qk_norm_rope as qnr
    from mxnet_tpu.parallel.data_parallel import TrainStep

    monkeypatch.setattr(fa, "_use_pallas", lambda q, v=None: True)
    monkeypatch.setattr(qnr, "_use_pallas", lambda x, hd: True)
    net = llama.LlamaForCausalLM(llama.LlamaConfig(
        vocab_size=512, hidden_size=256, num_layers=2, num_heads=2,
        num_kv_heads=1, head_dim=128, intermediate_size=256, qk_norm=True,
        attention_types=("window", "full"), attention_window=128,
        rope_attention_types=("window",), remat=True))
    net.initialize()

    def loss(logits, labels):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]

    step = TrainStep(net, loss, optimizer="adam", dtype="bfloat16",
                     optimizer_params={"learning_rate": 1e-4})

    def spec(a):
        return jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one_chip)

    ids = np.zeros((1, 256), np.int32)
    args = jax.tree_util.tree_map(spec, (
        step.train_params, step.rest_params, step.opt_state,
        jax.random.PRNGKey(0), ids, ids))
    table = profiler.scopes_of(step._step.lower(*args).compile())
    # the layers' checkpoints keep the attention op's output and statistics:
    # one forward kernel a layer (the plain checkpoint's step held two), and
    # the q/k kernel before it still runs again
    assert len([name for name in table
                if name.startswith(profiler.KERNEL_ATTENTION_FWD)]) == 2
    for kernel, calls in ((qnr.KERNEL_FWD, 4), (qnr.KERNEL_BWD, 2)):
        parts = [row["part"] for name, row in table.items()
                 if name.startswith(kernel)]
        assert sorted(parts) == sorted(
            [profiler.SCOPE_ROPE, profiler.SCOPE_NORM] * calls), kernel


def _gathered_and_scattered(text, backward):
    """From a compiled program's text: the shapes of what its gathers
    produce and of what its scatters are given to put; with ``backward``
    those of the whole program, without it those of its forward pass alone
    (the instructions that no transpose named)."""
    import re

    dims = {name: tuple(int(n) for n in shape.split(",") if n)
            for name, shape in re.findall(
                r"%?([\w.-]+) = \w+\[([\d,]*)\]", text)}
    lines = [line for line in text.splitlines()
             if backward or "transpose(" not in line]
    gathered = [dims[name] for line in lines for name in re.findall(
        r"%?([\w.-]+) = \S+ gather\(", line)]
    scattered = [dims[updates] for line in lines for updates in re.findall(
        r" scatter\(%?[\w.-]+, %?[\w.-]+, %?([\w.-]+)\)", line)]
    return gathered, scattered


# the four decoder cells' expert layers: rows, hidden, the router's width, the
# experts held and their width, experts a token, and how the router scores
EXPERT_LAYERS = {
    # 16,384 rows choose 8 of 128 by softmax, 16 of width 768 held: four
    # parts of 32,768 sorted rows, of which the share's pairs fill half of one
    "block diffusion": (16384, 2048, 128, 16, 768, 8, {}),
    # 8,192 rows choose 8 of 128 by sigmoid plus a bias, 8 of width 1,024
    # held: two parts, a quarter of one filled
    "window": (8192, 2048, 128, 8, 1024, 8,
               {"score": "sigmoid", "scale": 2.826, "renorm_eps": 1e-20}),
    # 16,384 packed rows choose 8 of 64 by softmax, 8 of width 896 held:
    # widths of 18 and 7 lane tiles, no multiples of 256
    "packed": (16384, 2304, 64, 8, 896, 8, {}),
    # 8,192 rows choose 8 of 512 within 4 of 8 groups, 8 of width 768 held:
    # the thin share's parts of 8,192 rows (``_PART_EVEN_LOADS``)
    "ling": (8192, 2560, 512, 8, 768, 8,
             {"score": "sigmoid", "scale": 2.5, "renorm_eps": 1e-20,
              "groups": (8, 4)}),
}


def _loops_over_parts(text):
    """From a compiled program's text: ``[(own op_name, condition's
    instructions, body's instructions)]`` of the ``while`` loops that are
    under neither of the expert layer's scopes themselves and hold a grouped
    product: the loops over parts (the sorted walks are under the
    routing's).  An instruction is ``(name, own op_name, rest of line)``."""
    import re

    from mxnet_tpu import profiler

    _, computations = profiler._hlo_computations(text)
    found = []
    for instructions in computations.values():
        for _, own, rest in instructions:
            if " while(" not in rest or profiler.SCOPE_MOE_ROUTE in own:
                continue
            cond, body = (computations[re.search(
                rf"\b{key}=%?([\w.\-]+)", rest).group(1)]
                for key in ("condition", "body"))
            if any("ragged-dot" in line or "ragged_dot" in line
                   for _, _, line in body):
                found.append((own, cond, body))
    return found


@functools.lru_cache(maxsize=None)
def _expert_layer_for_v5e(cell, one_chip):
    """``(compiled, part)``: a cell's expert layer as ``moe_swiglu`` runs
    it, the experts held in bf16, under a layer's checkpoint and the step's
    forward scope, its value and gradients compiled for the described chip
    once a cell (20 to 40 s each); and the rows of a part.  The program
    holds the forward pass whole, so the forward case reads that part of it
    and compiles nothing (until PR 41 it compiled the forward again alone:
    113 s of this file's 273)."""
    from mxnet_tpu import profiler
    from mxnet_tpu.ops import flash_attention as fa
    from mxnet_tpu.ops.attention_ops import moe_swiglu
    from mxnet_tpu.parallel import expert_parallel
    from mxnet_tpu.parallel.expert_parallel import (_PART_EVEN_LOADS,
                                                    _PART_ROWS)

    tokens, hidden, experts, held, width, top_k, scoring = EXPERT_LAYERS[cell]
    scoring = dict(scoring)
    n_group, topk_group = scoring.pop("groups", (1, 1))
    part = min(_PART_ROWS, tokens * top_k,
               _PART_EVEN_LOADS * tokens * top_k * held // experts)

    def layer(x, router, p, bias):
        return moe_swiglu(
            x[None], router, p["g"], p["u"], p["d"], bias, capacity_factor=0,
            top_k=top_k, renormalize=True, experts_first=16,
            score=scoring.get("score", "softmax"),
            route_scale=scoring.get("scale", 1.0),
            renorm_eps=scoring.get("renorm_eps", 0.0), n_group=n_group,
            topk_group=topk_group)[0]

    # as a decoder layer's checkpoint calls it: the router's choice is kept
    @jax.named_scope(profiler.SCOPE_FORWARD)
    def loss(x, router, p, bias):
        with fa.checkpoint_keeps():
            return jnp.sum(jnp.sin(jax.checkpoint(
                layer, policy=jax.checkpoint_policies.save_only_these_names(
                    *expert_parallel.KEPT))(x, router, p, bias)))

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (spec((tokens, hidden), "float32"),
            spec((hidden, experts), "float32"),
            {"g": spec((held, hidden, width), "bfloat16"),
             "u": spec((held, hidden, width), "bfloat16"),
             "d": spec((held, width, hidden), "bfloat16")},
            spec((experts,), "float32") if scoring else None)
    # the layer as the decoders call it, its own grouped products: with a
    # TPU as JAX's backend the gate sends them to the kernels
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
            *args).compile(), part


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("cell", list(EXPERT_LAYERS))
def test_dropless_expert_layer_compiles_for_v5e_and_gathers_live_rows(
        one_chip, cell, backward):
    """The four decoder cells' expert layers as ``moe_swiglu`` runs them, the
    experts held in bf16 and their grouped products the Pallas kernels of
    ``ops/grouped_matmul.py`` at whole widths (2304 x 896 too), under a
    layer's checkpoint and the step's forward scope: loops with a traced
    trip count (over the parts that hold a pair, and inside them the walks
    of ``dispatch`` and ``combine``, custom VJPs all) are the chip's
    compiler's to accept.  One program a cell (``_expert_layer_for_v5e``):
    ``backward`` reads the whole of it, the other case its forward pass.

    What it compiled gathers rows a granule of the sorted rows at a time
    and no gate for each of the ``rows x 8`` pairs; nothing of a part's
    32,768 rows moves at once any more: the way back (``combine`` forward,
    ``dispatch`` backward) is the kernel of ``ops/moe_add_rows.py`` over the
    rows that hold a pair, once forward and once more backward (the
    ``combine`` that the backward loop's ``jax.vjp`` computes again is dead:
    the kernel has no side effect), inside the loops, a row of the routing's
    part, its target ``(T, 1, d)`` float32 updated in place: no copy of the
    target stands before it.

    The loops over parts: one forward and, backward, one more (what the
    layer's checkpoint computes again is the loop's inputs, so its second
    forward loop is dead).  Their trip count is a number they carry, not
    the static number of parts.  Every instruction of theirs that the
    program named (the counter and the bound's compare, which part, the
    accumulators' adds, the walks, the products' element-wise ops) is under
    exactly one of ``mx_moe_route`` and ``mx_moe_experts``, the grouped
    products' kernels too, and the backward loop's are of the class
    ``backward`` by their own names; the ``while`` instruction itself carries its caller's
    scope (one around it would name its body's products too), and what the
    compiler adds without a name (copies, a buffer's fill sunk into the
    body) carries none.

    The router chooses once (ISSUE 49): the layer's checkpoint keeps the
    choice, so the backward holds the router's two transposed products and
    no forward one, and no sort (on a TPU a ``top_k`` is one)."""
    import re

    from mxnet_tpu import profiler
    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.parallel.expert_parallel import _GRANULE

    tokens, hidden, _, _, _, top_k, _ = EXPERT_LAYERS[cell]
    compiled, part = _expert_layer_for_v5e(cell, one_chip)
    text = compiled.as_text()
    gathered, scattered = _gathered_and_scattered(text, backward)
    # the router's top-k scatters a token's 8 gates back and a granule's
    # gates' gradients go to their pairs: scalars both
    assert all(hidden not in shape[1:] for shape in scattered)
    # forward: the tokens' rows; backward: those again and the cotangent's
    # rows (the rows' gates are gathered a part at a time: scalars)
    rows = [shape[0] for shape in gathered if shape[-1] == hidden]
    assert rows.count(_GRANULE) >= (3 if backward else 1)
    assert set(rows) == {_GRANULE}
    assert all(tokens * top_k not in shape for shape in gathered)

    loops = [loop for loop in _loops_over_parts(text)
             if backward or "transpose(" not in loop[0]]
    assert len(loops) == (2 if backward else 1)
    table = profiler.scopes_of(compiled)
    for own, cond, body in loops:
        assert profiler.SCOPE_MOE_EXPERTS not in own
        # the bound is carried: no constant to compare the counter with
        assert not any(" constant(" in rest for _, _, rest in cond)
        # (a constant, or an element of the loop's carry, keeps the name of
        # whoever made it first, and is no work)
        named = [(name, scope) for name, scope, rest in cond + body
                 if scope and profiler._HLO_OPCODE.search(rest).group(1)
                 not in ("constant", "get-tuple-element")]
        assert len(named) > 20
        for name, scope in named:
            assert (profiler.SCOPE_MOE_ROUTE in scope) \
                != (profiler.SCOPE_MOE_EXPERTS in scope), (name, scope)
            # (a fusion may take in an op that the forward named as well)
            want = "backward" if "transpose(" in own else "forward"
            assert profiler._scope_classes([scope]) == [want], (name, scope)
            assert want in table[name]["classes"], name
    assert sum("transpose(" in own for own, _, _ in loops) == int(backward)
    # the loops hold the kernels' custom calls, XLA's grouped product
    # nowhere: three products forward; backward those again, their three
    # transposes and the three weights' gradients.  Each is a row of the
    # experts' part whose scope holds ``ragged_dot``, which is how the
    # benchmark's readers find a grouped product
    assert "ragged-dot" not in text
    in_loops = {name for _, _, body in loops for name, _, _ in body}
    kernels = {name: row for name, row in table.items()
               if name.startswith(gm.KERNEL)
               and (backward or name in in_loops)}
    assert len(kernels) == (12 if backward else 3)
    for name, row in kernels.items():
        assert name in in_loops, name
        assert row["part"] == profiler.SCOPE_MOE_EXPERTS, (name, row)
        assert "ragged_dot" in row["scope"], (name, row)
    names = sorted(name.split(".", 1)[0] for name in kernels)
    assert names == sorted(
        [gm.KERNEL] * (6 if backward else 3)
        + [gm.KERNEL_TRANSPOSED, gm.KERNEL_DWEIGHTS] * (3 * backward))
    # SwiGLU between them, over the live row tiles: forward, and backward
    # the forward again and its gradient
    glu = sorted(name.split(".", 1)[0] for name, row in table.items()
                 if name.startswith(gm.KERNEL_SWIGLU)
                 and row["part"] == profiler.SCOPE_MOE_EXPERTS
                 and name in in_loops)
    assert glu == [gm.KERNEL_SWIGLU] * (1 + backward) \
        + [gm.KERNEL_SWIGLU_BWD] * backward
    # the way back: once forward, once more backward, in the loops, the
    # routing's; and no copy of its target before it
    back = {name: row for name, row in table.items()
            if name.startswith(profiler.KERNEL_MOE_ADD_ROWS)
            and (backward or name in in_loops)}
    assert len(back) == 1 + backward
    for name, row in back.items():
        assert name in in_loops, name
        assert row["part"] == profiler.SCOPE_MOE_ROUTE, (name, row)
    target = rf"f32\[{tokens},1,{hidden}\]"
    assert len(re.findall(target + r"\S* custom-call\(", text)) == 2
    # (in the kernel's layout, a token's row contiguous: the pass that
    # brings the result back to rows of tiles is fused into whoever reads it)
    assert not re.search(target + r"\{2,1,0:T\(1,128\)\S* copy\(", text)
    if not backward:
        return
    # the router (ISSUE 49; the layer's checkpoint keeps its choice): the
    # float32 product once forward and backward its two transposes, no
    # third; every sort of the program (on a TPU ``top_k`` is one, and so
    # is a scatter of 65,536 updates with indices of its own) is the
    # forward's: the ``top_k``s and the sort of the pairs
    _, _, experts, _, _, _, scoring = EXPERT_LAYERS[cell]
    lines = [(line, re.search(r'op_name="([^"]*)"', line))
             for line in text.splitlines()]
    products = sorted(
        (tuple(int(n) for n in re.search(
            r"= f32\[(\d+),(\d+)\]", line).groups()),
         "transpose(" in op.group(1))
        for line, op in lines
        if " convolution(" in line and "{highest,highest}" in line)
    assert products == sorted([((tokens, experts), False),
                               ((hidden, experts), True),
                               ((tokens, hidden), True)])
    sorts = [op.group(1) if op else "" for line, op in lines
             if " sort(" in line]
    assert len(sorts) == (3 if "groups" in scoring else 2)
    assert all(profiler.SCOPE_MOE_ROUTE in name and "transpose(" not in name
               for name in sorts)
    # what set-up pays for (PERF.md section 6, PR 47): with the router
    # computed again the Ling cell's layer compiled to 2,728 lines
    if cell == "ling":
        assert text.count("\n") <= 2728


# --------------------------------------------------------------------------
# the Ling cell's mixers (PR 38): the chunked delta rule, forward and
# backward (the chunk solve by doubling, the scans, the decays a sub-block),
# and both attention kernels at q/k 192 beside v 128, each at the cell's shape
# --------------------------------------------------------------------------
@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_kda_compiles_for_v5e(one_chip, dtype, backward):
    from mxnet_tpu.ops import kda

    wide = jax.ShapeDtypeStruct((1, 8, 8192, 128), dtype, sharding=one_chip)
    decay = jax.ShapeDtypeStruct((1, 8, 8192, 128), "float32",
                                 sharding=one_chip)
    beta = jax.ShapeDtypeStruct((1, 8, 8192), dtype, sharding=one_chip)
    fn = kda.kda
    if backward:
        fn = jax.grad(lambda *a: jnp.sum(kda.kda(*a).astype(jnp.float32)),
                      argnums=(0, 1, 2, 3, 4))
    compiled = jax.jit(fn).lower(wide, wide, wide, decay, beta).compile()
    text = compiled.as_text()
    assert "mxnet_kda_fwd" in text and ("mxnet_kda_bwd" in text) == backward
    # XLA's row-at-a-time inversion (0.69 ms a call on a v5e) is nowhere
    assert "InvertDiagBlocksLowerTriangular" not in text
    # what the op needs beside its operands stays a fraction of a GiB
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_latent_attention_kernels_compile_for_v5e(one_chip):
    """q and k of 192 go in padded to 256, v and the output stay 128; the
    backward hands back gradients of 192."""
    from mxnet_tpu.ops.flash_attention import (_fa_backward_pallas,
                                               _fa_forward_pallas)

    scale = 1.0 / 192 ** 0.5
    qk = jax.ShapeDtypeStruct((1, 8, 8192, 192), "bfloat16",
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 8, 8192, 128), "bfloat16",
                             sharding=one_chip)
    lse = jax.ShapeDtypeStruct((1, 8, 8192), "float32", sharding=one_chip)
    fwd = jax.jit(functools.partial(_fa_forward_pallas, causal=True,
                                    sm_scale=scale)).lower(qk, qk, v)
    assert [x.shape for x in fwd.out_info] == [(1, 8, 8192, 128),
                                               (1, 8, 8192)]
    assert "mxnet_flash_attention_fwd" in fwd.compile().as_text()
    bwd = jax.jit(functools.partial(_fa_backward_pallas, causal=True,
                                    sm_scale=scale)).lower(qk, qk, v, v, lse,
                                                           v)
    assert [x.shape for x in bwd.out_info] == [qk.shape, qk.shape, v.shape]
    assert "mxnet_flash_attention_bwd" in bwd.compile().as_text()


# --------------------------------------------------------------------------
# the Phi cell's mixers (PR 45): the selective scan's two kernels at the
# cell's shape, both attention kernels at q/k 64 beside v 128 (a pair's
# values side by side) under the causal mask and the window of 512, and a
# small step of the five kinds of layer with every gate open
# --------------------------------------------------------------------------
@pytest.mark.parametrize("backward", [False, True])
def test_selective_scan_compiles_for_v5e(one_chip, backward, monkeypatch):
    """8,192 rows of 5120 channels x 16 states in chunks of 64: the state
    stays on chip, so nothing the size of ``rows x 5120 x 16`` (2.7 GB) is
    held, and what the op needs beside its operands (the channels laid out
    in blocks for the kernels, float32) stays under a GiB."""
    import re

    from mxnet_tpu.ops import selective_scan as ss

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ss._make_scan.cache_clear()

    def spec(shape, dtype="bfloat16"):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    operands = (spec((1, 8192, 5120)), spec((1, 8192, 5120), "float32"),
                spec((5120, 16), "float32"), spec((1, 8192, 16)),
                spec((1, 8192, 16)), spec((5120,), "float32"))
    fn = ss.selective_scan
    if backward:
        fn = jax.grad(lambda *a: jnp.sum(
            ss.selective_scan(*a).astype(jnp.float32)), argnums=tuple(range(6)))
    compiled = jax.jit(fn).lower(*operands).compile()
    ss._make_scan.cache_clear()
    text = compiled.as_text()
    assert "mxnet_selective_scan_fwd" in text
    assert ("mxnet_selective_scan_bwd" in text) == backward
    assert not re.search(r"f32\[(\d+,)*8192,(\d+,)*(5120,16|16,5120|5,16,8,128)"
                         r"\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


@pytest.mark.parametrize("call", [{"causal": True},
                                  {"mask": ("window", 512)}],
                         ids=["causal", "window"])
def test_differential_attention_kernels_compile_for_v5e(one_chip, call):
    """One call of 40 query heads on 20 key-value heads (a pair of query
    heads to each): q and k of 64, v and the output of 128; the backward
    hands back gradients of 64, 64 and 128, K's and V's on their 20 heads;
    neither is repeated to the 40."""
    from mxnet_tpu.ops.flash_attention import (_fa_backward_pallas,
                                               _fa_forward_pallas)

    def spec(heads, dim, dtype="bfloat16"):
        return jax.ShapeDtypeStruct((1, heads, 8192) + ((dim,) if dim else ()),
                                    dtype, sharding=one_chip)

    q, k, v, o = spec(40, 64), spec(20, 64), spec(20, 128), spec(40, 128)
    lse = spec(40, None, "float32")
    call = dict({"causal": False, "sm_scale": 0.125}, **call)
    fwd = jax.jit(functools.partial(_fa_forward_pallas, **call)).lower(
        q, k, v)
    assert [x.shape for x in fwd.out_info] == [o.shape, lse.shape]
    compiled = fwd.compile()
    assert "mxnet_flash_attention_fwd" in compiled.as_text()
    _reads_shared_heads(compiled, (k, v), 40)
    bwd = jax.jit(functools.partial(_fa_backward_pallas, **call)).lower(
        q, k, v, o, lse, o)
    assert [x.shape for x in bwd.out_info] == [q.shape, k.shape, v.shape]
    compiled = bwd.compile()
    assert "mxnet_flash_attention_bwd" in compiled.as_text()
    _reads_shared_heads(compiled, (k, v), 40)


def test_a_small_phi_step_compiles_for_v5e_with_one_scan_a_layer(
        one_chip, monkeypatch):
    """The five kinds of layer (window, state-space, full, gated memory,
    cross) in one fused bf16 step with both gates open, compiled for the
    chip: the layers' checkpoints keep the scan's output and chunk states and
    the attention's output and statistics, so the step holds one scan
    forward kernel and one backward (rows of ``mx_ssm_scan``) and one
    attention forward kernel a layer that attends."""
    import numpy as np

    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu import profiler
    from mxnet_tpu.gluon.model_zoo.language import llama
    from mxnet_tpu.ops import flash_attention as fa
    from mxnet_tpu.ops import selective_scan as ss
    from mxnet_tpu.parallel.data_parallel import TrainStep

    monkeypatch.setattr(fa, "_use_pallas", lambda q, v=None: True)
    monkeypatch.setattr(ss, "use_pallas",
                        lambda x: x.shape[-1] % ss._BLOCK == 0)
    ss._make_scan.cache_clear()
    net = llama.LlamaForCausalLM(llama.LlamaConfig(
        vocab_size=512, hidden_size=512, num_layers=5, num_heads=8,
        num_kv_heads=4, head_dim=64, intermediate_size=512,
        attention_types=("window", "ssm", "full", "gmu", "cross"),
        attention_window=128, rope_attention_types=(), differential=True,
        norm="layer", attention_bias=True, first_layer_index=15,
        tie_embeddings=True, remat=True))
    net.initialize()

    def loss(logits, labels):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]

    step = TrainStep(net, loss, optimizer="adam", dtype="bfloat16",
                     optimizer_params={"learning_rate": 1e-4})

    def spec(a):
        return jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one_chip)

    ids = np.zeros((1, 256), np.int32)
    args = jax.tree_util.tree_map(spec, (
        step.train_params, step.rest_params, step.opt_state,
        jax.random.PRNGKey(0), ids, ids))
    table = profiler.scopes_of(step._step.lower(*args).compile())
    ss._make_scan.cache_clear()
    for kernel, calls in ((profiler.KERNEL_SSM_SCAN_FWD, 1),
                          (profiler.KERNEL_SSM_SCAN_BWD, 1)):
        parts = [row["part"] for name, row in table.items()
                 if name.startswith(kernel)]
        assert parts == [profiler.SCOPE_SSM_SCAN] * calls, kernel
    assert len([name for name in table
                if name.startswith(profiler.KERNEL_ATTENTION_FWD)]) == 3


# --------------------------------------------------------------------------
# the embedding's way back (PR 53): the Ling cell's table and the Phi
# cell's, 8,192 ids each, in one program
# --------------------------------------------------------------------------
def test_the_embeddings_way_back_compiles_for_v5e(one_chip, monkeypatch):
    """``jax.vjp`` of ``F.Embedding`` at ``(19648, 2560)`` and ``(25008,
    2560)`` with 8,192 ids: the sort beside an ``iota`` and the kernel, a
    DMA a row of the cotangent as ``(R, 1, d)`` (a row of ``(R, d)`` is a
    slice off the tiling; a kernel in front makes that copy), a row of the
    block in VMEM read, added to and
    written at a sublane the ids give, the Phi table's partial last block,
    the buffers inside the default VMEM; no scatter left."""
    from mxnet_tpu import profiler
    from mxnet_tpu.ops.registry import OP_TABLE

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    embedding = OP_TABLE["Embedding"].fn

    def both(ids, ling, phi, g):
        rows, pull = jax.vjp(
            lambda a, b: (embedding(ids, a), embedding(ids, b)), ling, phi)
        return rows, pull((g, g))

    def shaped(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(both).lower(
        shaped(1, 8192, dtype=jnp.int32), shaped(19648, 2560),
        shaped(25008, 2560), shaped(1, 8192, 2560)).compile().as_text()
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line]
    # a table: the copy that sets the cotangent's rows apart, and the kernel
    assert len(kernels) == 4
    assert sum(f"%{profiler.KERNEL_EMBED_ADD_ROWS}_apart" in
               line.split(" = ")[0] for line in kernels) == 2
    assert all(profiler.KERNEL_EMBED_ADD_ROWS in line
               and profiler.SCOPE_EMBED in line for line in kernels)
    assert " scatter(" not in text and text.count(" sort(") == 2
