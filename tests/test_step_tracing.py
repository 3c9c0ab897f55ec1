"""The fused step named from inside: ``jax.named_scope``s in the compiled
step, the op-to-scope table built from the executable's HLO text, and the
host phases of a ``TrainStep`` call on the profiler's clock."""
import gc
import glob
import re
import threading
import time
import weakref

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, telemetry
from mxnet_tpu.gluon.data.prefetcher import PrefetchIterator
from mxnet_tpu.parallel import data_parallel
from mxnet_tpu.parallel.data_parallel import TrainStep

PHASES = (data_parallel.PHASE_PREPARE, data_parallel.PHASE_EXECUTE,
          data_parallel.PHASE_COMPILE)


@pytest.fixture(autouse=True)
def _clean():
    telemetry.reset()
    profiler._OP_SCOPES.clear()
    yield
    telemetry.reset()
    profiler._OP_SCOPES.clear()


@pytest.fixture(scope="module")
def toy_bert():
    from mxnet_tpu.gluon.model_zoo.language import bert

    mx.random.seed(0)
    # one width everywhere: initialisation compiles a program per shape
    net = bert.BertForPretraining(bert.BertConfig(
        vocab_size=64, hidden_size=64, num_layers=1, num_heads=1,
        intermediate_size=64, max_position=64, dropout=0.0))
    net.initialize()
    net(mx.nd.zeros((1, 16), dtype="int32"))
    return net


def _loss(outs, labels):
    import jax.numpy as jnp

    mlm, nsp = outs
    logp = jax.nn.log_softmax(mlm, axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, :-1, None], axis=-1)
    return -jnp.mean(picked) - jnp.mean(jax.nn.log_softmax(nsp)[:, 0])


def _batch(rows=4, seq=16):
    rng = np.random.default_rng(0)
    return (rng.integers(0, 64, (rows, seq), dtype=np.int32),
            rng.integers(0, 2, (rows, seq + 1), dtype=np.int32))


def _step(net, **kw):
    return TrainStep(net, _loss, optimizer="adam",
                     optimizer_params={"learning_rate": 1e-4}, **kw)


def _phase_counts():
    fam = telemetry.snapshot()["metrics"]["mxnet_step_phase_seconds"]
    got = {s["labels"]["phase"]: s["count"] for s in fam["samples"]}
    return tuple(got.get(p, 0) for p in PHASES)


# -- the scopes inside the compiled step -------------------------------------

@pytest.mark.parametrize("options", [{}, {"dtype": "bfloat16"},
                                     {"remat": True}],
                         ids=["plain", "bfloat16", "remat"])
def test_lowered_step_carries_the_scopes(toy_bert, options):
    step = _step(toy_bert, **options)
    x, y = _batch()
    args = (step.train_params, step.rest_params, step.opt_state,
            jax.random.PRNGKey(0), x, y)
    hlo = step._step.lower(*args).as_text(dialect="hlo", debug_info=True)
    forward = f"jvp({profiler.SCOPE_FORWARD})/"
    assert forward in hlo
    assert f"transpose(jvp({profiler.SCOPE_FORWARD}))/" in hlo
    assert f"/{profiler.SCOPE_OPTIMIZER}/" in hlo
    assert f"/{profiler.SCOPE_ATTENTION_BWD}/" in hlo
    # the toy's sequence is below the kernel's gate: the plain forward
    assert f"/{profiler.SCOPE_ATTENTION_PLAIN_FWD}/" in hlo


# -- the op-to-scope table ---------------------------------------------------

@pytest.fixture
def fresh_compiles():
    """JAX's persistent compile cache keys on the program without its
    metadata: an executable cached before a scope was renamed would be
    loaded with the old names."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_scopes_of_finds_each_class_in_the_compiled_step(toy_bert,
                                                         fresh_compiles):
    step = _step(toy_bert)
    step(*_batch())
    (name, table), = profiler.op_scopes().items()
    assert name == "train_step:BertForPretraining"
    single = {row["classes"][0] for row in table.values()
              if len(row["classes"]) == 1}
    assert single == {"forward", "backward", "optimizer"}
    assert any(profiler.SCOPE_ATTENTION_BWD in row["scope"]
               for row in table.values())


class _Text:
    """Stands for a compiled executable: ``as_text()`` and nothing else."""

    def __init__(self, text):
        self.text = text

    def as_text(self):
        if isinstance(self.text, Exception):
            raise self.text
        return self.text


def _meta(name):
    return f'metadata={{op_name="jit(train_step)/{name}" source_line=1}}'


HAND_HLO = f"""HloModule jit_train_step, is_scheduled=true

%fused_inner (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0)
  ROOT %c = f32[8]{{0}} convert(%p), {_meta("jvp(mx_forward)/convert")}
}}

%fused_mixed (a: f32[8], b: f32[8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0)
  %b = f32[8]{{0}} parameter(1)
  %dot.1 = f32[8]{{0:T(8,128)S(1)}} multiply(%a, %b), {_meta("transpose(jvp(mx_forward))/dot_general")}
  ROOT %sub.1 = f32[8]{{0}} subtract(%a, %dot.1), {_meta("mx_optimizer/sub")}
}}

%fused_nested (a: f32[8]) -> f32[8] {{
  %a.1 = f32[8]{{0}} parameter(0)
  %fusion.9 = f32[8]{{0}} fusion(%a.1), kind=kLoop, calls=%fused_inner
  ROOT %neg = f32[8]{{0}} negate(%fusion.9), {_meta("transpose(jvp(mx_forward))/neg")}
}}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {{
  %t = (s32[], f32[8]{{0}}) parameter(0)
  %g = f32[8]{{0}} get-tuple-element(%t), index=1
  %exp.3 = f32[8]{{0}} exponential(%g), {_meta("transpose(jvp(mx_forward))/mxnet_flash_attention_bwd/while/body/exp")}
  ROOT %out = (s32[], f32[8]{{0}}) tuple(%t, %exp.3)
}}

%cond (t.1: (s32[], f32[8])) -> pred[] {{
  %t.1 = (s32[], f32[8]{{0}}) parameter(0)
  ROOT %lt = pred[] constant(false)
}}

%sum (x: f32[], y: f32[]) -> f32[] {{
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %hidden = f32[] add(%x, %y), {_meta("jvp(mx_forward)/reduce_sum")}
}}

ENTRY %main.7 (Arg_0: f32[8], Arg_1: f32[8]) -> f32[8] {{
  %Arg_0 = f32[8]{{0}} parameter(0), metadata={{op_name="train_params"}}
  %Arg_1 = f32[8]{{0}} parameter(1)
  %tanh.2 = f32[8]{{0}} tanh(%Arg_0), {_meta("jvp(mx_forward)/tanh")}
  %copy.4 = f32[8]{{0}} copy(%Arg_1)
  %init = (s32[], f32[8]{{0}}) tuple(%Arg_1, %tanh.2)
  %while.5 = (s32[], f32[8]{{0}}) while(%init), condition=%cond, body=%body, {_meta("transpose(jvp(mx_forward))/mxnet_flash_attention_bwd/while")}
  %fusion.6 = f32[8]{{0}} fusion(%tanh.2, %copy.4), kind=kOutput, calls=%fused_mixed, {_meta("transpose(jvp(mx_forward))/dot_general")}
  %fusion.8 = f32[8]{{0}} fusion(%copy.4), kind=kLoop, calls=%fused_nested, {_meta("transpose(jvp(mx_forward))/neg")}
  ROOT %reduce.9 = f32[] reduce(%fusion.6, %Arg_1), dimensions={{0}}, to_apply=%sum, {_meta("jvp(mx_forward)/reduce_sum")}
}}
"""


def test_scopes_of_marks_a_mixed_fusion_and_follows_loop_bodies():
    table = profiler.scopes_of(_Text(HAND_HLO))
    assert table["tanh.2"] == {"scope": "jit(train_step)/jvp(mx_forward)/tanh",
                               "classes": ["forward"], "part": ""}
    # a weight gradient's product with the optimizer's update as epilogue:
    # its own name says backward, what it fuses says both
    assert table["fusion.6"]["scope"].endswith("/dot_general")
    assert table["fusion.6"]["classes"] == ["backward", "optimizer"]
    # a fusion nested in a fusion counts too
    assert table["fusion.8"]["classes"] == ["backward", "forward"]
    assert table["copy.4"] == {"scope": "", "classes": [], "part": ""}
    # the loop and the ops of its body and condition are all on the line
    assert table["while.5"]["classes"] == ["backward"]
    assert profiler.SCOPE_ATTENTION_BWD in table["exp.3"]["scope"]
    assert "lt" in table
    # what a reducer applies and what a fusion fused never run by name
    assert not {"hidden", "dot.1", "sub.1", "c"} & set(table)


# -- every instruction resolved to one part ----------------------------------

FWD, BWD = "jvp(mx_forward)/", "transpose(jvp(mx_forward))/"
HAND_PARTS_HLO = f"""HloModule jit_train_step, is_scheduled=true

%fused_grad_and_update (a: f32[8], b: f32[8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0)
  %b = f32[8]{{0}} parameter(1)
  %dot.1 = f32[8]{{0}} multiply(%a, %b), {_meta(BWD + "mx_head/dot_general")}
  ROOT %sub.1 = f32[8]{{0}} subtract(%a, %dot.1), {_meta("mx_optimizer/sub")}
}}

%fused_two_parts (a.1: f32[8]) -> f32[8] {{
  %a.1 = f32[8]{{0}} parameter(0)
  %mul.2 = f32[8]{{0}} multiply(%a.1, %a.1), {_meta(FWD + "mx_norm/mul")}
  %inner = f32[8]{{0}} fusion(%mul.2), kind=kLoop, calls=%fused_rope
  ROOT %r = f32[8]{{0}} reshape(%inner), {_meta(FWD + "reshape")}
}}

%fused_rope (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0)
  ROOT %cos = f32[8]{{0}} cosine(%p), {_meta(FWD + "mx_rope/cos")}
}}

%fused_update (a.2: f32[8]) -> f32[8] {{
  %a.2 = f32[8]{{0}} parameter(0)
  ROOT %sq = f32[8]{{0}} sqrt(%a.2), {_meta("mx_optimizer/sqrt")}
}}

%fused_plain (a.3: f32[8]) -> f32[8] {{
  %a.3 = f32[8]{{0}} parameter(0)
  ROOT %n = f32[8]{{0}} negate(%a.3), {_meta(FWD + "neg")}
}}

%fused_own_name_wins (a.4: f32[8]) -> f32[8] {{
  %a.4 = f32[8]{{0}} parameter(0)
  %m = f32[8]{{0}} multiply(%a.4, %a.4), {_meta(FWD + "mx_norm/mul")}
  ROOT %d = f32[8]{{0}} add(%m, %a.4), {_meta(FWD + "mx_attn_proj/dot_general")}
}}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {{
  %t = (s32[], f32[8]{{0}}) parameter(0)
  %g = f32[8]{{0}} get-tuple-element(%t), index=1
  %gather.3 = f32[8]{{0}} negate(%g), {_meta(FWD + "while/body/mx_moe_route/gather")}
  %ragged-dot-none.4 = f32[8]{{0}} custom-call(%gather.3), custom_call_target="x", metadata={{op_name="ragged-dot-none"}}
  %fill.5 = f32[8]{{0}} broadcast(%g), dimensions={{0}}
  ROOT %out = (s32[], f32[8]{{0}}) tuple(%t, %fill.5)
}}

%cond (t.1: (s32[], f32[8])) -> pred[] {{
  %t.1 = (s32[], f32[8]{{0}}) parameter(0)
  ROOT %lt = pred[] constant(false)
}}

ENTRY %main.7 (Arg_0: f32[8]) -> f32[8] {{
  %Arg_0 = f32[8]{{0}} parameter(0)
  %fusion.1 = f32[8]{{0}} fusion(%Arg_0), kind=kLoop, calls=%fused_own_name_wins, {_meta(FWD + "mx_attn_proj/dot_general")}
  %divide_subtract_fusion.2 = f32[8]{{0}} fusion(%fusion.1, %Arg_0), kind=kOutput, calls=%fused_grad_and_update, {_meta("mx_optimizer/sub")}
  %fusion.3 = f32[8]{{0}} fusion(%Arg_0), kind=kLoop, calls=%fused_two_parts, {_meta(FWD + "reshape")}
  %fusion.4 = f32[8]{{0}} fusion(%Arg_0), kind=kLoop, calls=%fused_update, {_meta("mx_optimizer/sqrt")}
  %fusion.5 = f32[8]{{0}} fusion(%Arg_0), kind=kLoop, calls=%fused_plain, {_meta(FWD + "neg")}
  %mxnet_flash_attention_fwd_window.6 = f32[8]{{0}} custom-call(%Arg_0), custom_call_target="tpu_custom_call", {_meta(FWD + "checkpoint/pallas_call")}
  %mxnet_flash_attention_bwd_segments.7 = f32[8]{{0}} custom-call(%Arg_0), custom_call_target="tpu_custom_call", {_meta(BWD + "mxnet_flash_attention_bwd/pallas_call")}
  %all-reduce.8 = f32[8]{{0}} all-reduce(%Arg_0), replica_groups={{}}, to_apply=%cond, {_meta(BWD + "mx_head/dot_general")}
  %init = (s32[], f32[8]{{0}}) tuple(%Arg_0, %fusion.5)
  %while.9 = (s32[], f32[8]{{0}}) while(%init), condition=%cond, body=%body, {_meta(FWD + "while")}
  %remat.10 = f32[8]{{0}} tanh(%Arg_0), {_meta(BWD + FWD + "checkpoint/rematted_computation/mx_moe_route/mx_moe_route/tanh")}
  %copy.12 = f32[8]{{0}} copy(%Arg_0), metadata={{op_name="jit(train_step)/{BWD}mx_attn_proj/reshape;jit(train_step)/{BWD}mx_attn_proj/transpose"}}
  %copy.13 = f32[8]{{0}} copy(%Arg_0), metadata={{op_name="jit(train_step)/{BWD}mx_attn_proj/reshape;jit(train_step)/{BWD}mxnet_flash_attention_bwd/transpose"}}
  ROOT %copy.11 = f32[8]{{0}} copy(%Arg_0)
}}
"""


def test_the_table_resolves_every_instruction_to_one_part():
    table = profiler.scopes_of(_Text(HAND_PARTS_HLO))
    part = {name: row["part"] for name, row in table.items()}
    # (i) its own name wins, whatever it fused
    assert part["fusion.1"] == profiler.SCOPE_ATTENTION_PROJ
    assert part["remat.10"] == profiler.SCOPE_MOE_ROUTE
    # the kernels, the grouped products and the all-reduce by their
    # instruction names, whatever metadata the compiler hands them
    assert part["mxnet_flash_attention_fwd_window.6"] \
        == profiler.KERNEL_ATTENTION_FWD
    assert part["mxnet_flash_attention_bwd_segments.7"] \
        == profiler.SCOPE_ATTENTION_BWD
    assert part["ragged-dot-none.4"] == profiler.SCOPE_MOE_EXPERTS
    assert part["all-reduce.8"] == profiler.PART_COLLECTIVES
    # (ii) a weight gradient's matmul with Adam's update as epilogue, named
    # after the update, is the part of its matmul
    assert table["divide_subtract_fusion.2"]["scope"].endswith(
        "mx_optimizer/sub")
    assert part["divide_subtract_fusion.2"] == profiler.SCOPE_HEAD
    # (iii) two parts (one in a nested fusion), the optimizer alone, nothing
    assert part["fusion.3"] == profiler.PART_MIXED
    assert part["fusion.4"] == profiler.PART_OPTIMIZER
    assert part["fusion.5"] == part["copy.11"] == part["fill.5"] == ""
    # names the compiler joined by ";" when it merged instructions are
    # looked at one by one: one part between them, or two
    assert part["copy.12"] == profiler.SCOPE_ATTENTION_PROJ
    assert part["copy.13"] == profiler.PART_MIXED
    # a loop keeps the part of its own name (none here); its body's ops are
    # rows of their own
    assert part["while.9"] == ""
    assert part["gather.3"] == profiler.SCOPE_MOE_ROUTE
    # the classes and the scopes are what they were
    assert table["divide_subtract_fusion.2"]["classes"] \
        == ["backward", "optimizer"]


PART_SCOPES = tuple(
    value for name, value in vars(profiler).items()
    if name.startswith("SCOPE_") and value not in (
        profiler.SCOPE_FORWARD, profiler.SCOPE_OPTIMIZER))
# the block's own, entered in the model and around the loss
BLOCK_SCOPES = (profiler.SCOPE_ATTENTION_PROJ, profiler.SCOPE_FFN,
                profiler.SCOPE_NORM, profiler.SCOPE_ROPE,
                profiler.SCOPE_EMBED, profiler.SCOPE_HEAD,
                profiler.SCOPE_LOSS)


@pytest.fixture(scope="module")
def toy_decoder():
    """Two layers of every kind the decoder cells have between them: a dense
    and a window layer first, then a full layer of routed experts beside a
    shared one; gate, norms on both sides, q/k norm, an embedding scale,
    per-layer recomputation."""
    from mxnet_tpu.gluon.model_zoo.language import llama

    mx.random.seed(0)
    net = llama.LlamaForCausalLM(llama.LlamaConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        num_kv_heads=1, intermediate_size=32, num_experts=4,
        moe_capacity_factor=None, moe_top_k=2, num_dense_layers=1,
        attention_types=("window", "full"), attention_window=4,
        rope_attention_types=("window",), moe_shared_intermediate_size=32,
        moe_intermediate_size=32, attention_gate=True, post_norms=True,
        embed_scale=2.0, qk_norm=True, remat=True))
    net.initialize()
    return net


@pytest.fixture(autouse=True)
def chunks_of_16(monkeypatch):
    """``toy_hybrid``'s rows are 16 tokens: one chunk of the delta rule."""
    from mxnet_tpu.gluon.model_zoo.language import llama

    monkeypatch.setattr(llama, "KDA_CHUNK", 16)


@pytest.fixture(scope="module")
def toy_hybrid():
    """The mixers that are no softmax attention over per-head K and V (PR
    38): a dense delta-rule layer, then a latent-attention layer of routed
    experts chosen inside groups, beside a shared one; two of four heads
    held, per-layer recomputation."""
    from mxnet_tpu.gluon.model_zoo.language import llama

    mx.random.seed(0)
    net = llama.LlamaForCausalLM(llama.LlamaConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=4, head_dim=16, intermediate_size=32, num_experts=8,
        moe_capacity_factor=None, moe_top_k=2, moe_groups=(4, 2),
        num_dense_layers=1, attention_types=("kda", "mla"),
        attention_gate="head_wise", attention_heads_held=(2, 2),
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_interleave=True,
        moe_shared_intermediate_size=32, moe_intermediate_size=32,
        remat=True))
    net.initialize()
    return net


def _token_loss(logits, labels):
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def _decoder_batch():
    rng = np.random.default_rng(0)
    return (rng.integers(0, 64, (2, 16), dtype=np.int32),
            rng.integers(0, 64, (2, 16), dtype=np.int32))


@pytest.fixture(params=["decoder", "hybrid", "bert"])
def net_of(request):
    """``(net, loss, batch, the parts it has)``."""
    if request.param == "decoder":   # softmax attention alone
        return (request.getfixturevalue("toy_decoder"), _token_loss,
                _decoder_batch(), set(PART_SCOPES) - {
                    profiler.SCOPE_KDA, profiler.SCOPE_MIXER_GATE,
                    profiler.SCOPE_SSM_SCAN})
    if request.param == "hybrid":
        # every part but the state-space scan's, which no layer here walks
        # (its rows of the table: tests/test_ssm_diff_decoder.py)
        return (request.getfixturevalue("toy_hybrid"), _token_loss,
                _decoder_batch(), set(PART_SCOPES) - {
                    profiler.SCOPE_SSM_SCAN})
    # the encoder has no RoPE and no experts; its toy runs under the gate
    return (request.getfixturevalue("toy_bert"), _loss, _batch(), {
        profiler.SCOPE_ATTENTION_PROJ, profiler.SCOPE_FFN,
        profiler.SCOPE_NORM, profiler.SCOPE_EMBED, profiler.SCOPE_HEAD,
        profiler.SCOPE_LOSS, profiler.SCOPE_ATTENTION_BWD,
        profiler.SCOPE_ATTENTION_PLAIN_FWD})


def _lowered(net, loss, batch, debug_info=False):
    step = TrainStep(net, loss, optimizer="adam",
                     optimizer_params={"learning_rate": 1e-4})
    args = (step.train_params, step.rest_params, step.opt_state,
            jax.random.PRNGKey(0), *batch)
    lowered = step._step.lower(*args)
    return lowered.as_text(dialect="hlo", debug_info=True) if debug_info \
        else lowered.as_text()


def test_every_part_of_the_net_is_in_the_compiled_steps_table(
        net_of, fresh_compiles):
    net, loss, batch, has = net_of
    step = TrainStep(net, loss, optimizer="adam",
                     optimizer_params={"learning_rate": 1e-4})
    step(*batch)
    (_, table), = profiler.op_scopes().items()
    scopes = {row["scope"] for row in table.values()}
    pattern = re.compile("|".join(map(re.escape, PART_SCOPES)))
    backward = f"transpose(jvp({profiler.SCOPE_FORWARD}))/"
    for part in has:
        mine = {s for s in scopes if part in s}
        assert mine, part
        if part in BLOCK_SCOPES:    # forward and derived backward alike
            assert any(backward in s for s in mine), part
            assert any("transpose(" not in s for s in mine), part
    # under a layer's checkpoint the names stay, computed again or not
    if profiler.SCOPE_ROPE in has:
        for part in (profiler.SCOPE_ATTENTION_PROJ, profiler.SCOPE_NORM,
                     profiler.SCOPE_ROPE, profiler.SCOPE_FFN,
                     profiler.SCOPE_MOE_SHARED):
            for under in ("checkpoint/", "checkpoint/rematted_computation/"):
                assert any(under + part in s for s in scopes), under + part
    assert {row["part"] for row in table.values()} >= has
    # no op's own name holds two parts (one part twice is one part; where
    # the compiler merged instructions it joined their names by ";")
    for scope in scopes:
        for one in scope.split(";"):
            assert len(set(pattern.findall(one))) <= 1, scope
    for part in set(PART_SCOPES) - has:
        assert not any(part in s for s in scopes), part


def test_the_scopes_change_nothing_but_names(net_of, monkeypatch):
    """The step's lowered program with every ``jax.named_scope`` a null
    context is the real one's, line for line."""
    import contextlib

    class NoScope(contextlib.ContextDecorator, contextlib.nullcontext):
        """``jax.named_scope`` is a context and a decorator."""

    net, loss, batch, has = net_of
    named = _lowered(net, loss, batch)
    assert profiler.scope_digest() in named      # the module's name
    with_names = _lowered(net, loss, batch, debug_info=True)
    assert all(part in with_names for part in has - {
        profiler.SCOPE_ATTENTION_BWD}), "the real one carries the names"
    monkeypatch.setattr(jax, "named_scope", lambda name: NoScope())
    assert _lowered(net, loss, batch) == named
    bare = _lowered(net, loss, batch, debug_info=True)
    assert not any(part in bare for part in PART_SCOPES)


def test_the_jitted_functions_name_follows_the_scope_names(toy_bert,
                                                           monkeypatch):
    digest = profiler.scope_digest()
    assert re.fullmatch(r"[0-9a-f]{8}", digest)
    assert profiler.scope_digest() == digest
    name = lambda: _step(toy_bert)._step.__name__
    assert name() == name() == f"train_step_{digest}"
    monkeypatch.setattr(profiler, "SCOPE_ROPE", "mx_rotary")
    assert profiler.scope_digest() != digest
    assert name() == f"train_step_{profiler.scope_digest()}"
    monkeypatch.undo()
    assert name() == f"train_step_{digest}"
    # a constant added later moves it too
    monkeypatch.setattr(profiler, "SCOPE_LATER", "mx_later", raising=False)
    assert profiler.scope_digest() != digest


@pytest.mark.parametrize("text", [
    RuntimeError("no text for a deserialised executable"), "", None,
    re.sub(r", metadata=\{[^}]*\}", "", HAND_HLO)],
    ids=["raises", "empty", "none", "no-metadata"])
def test_no_text_or_no_metadata_is_an_empty_table(text):
    assert profiler.register_executable("train_step:Gone", _Text(text)) == {}
    assert profiler.op_scopes() == {"train_step:Gone": {}}


def test_registry_keeps_tables_and_lets_the_executable_go():
    compiled = _Text(HAND_HLO)
    gone = weakref.ref(compiled)
    table = profiler.register_executable("train_step:Hand", compiled)
    del compiled
    gc.collect()
    assert gone() is None
    assert profiler.op_scopes()["train_step:Hand"] is table


def test_registry_is_bounded_and_the_newest_stay():
    for i in range(profiler._OP_SCOPES_CAP + 3):
        profiler.register_executable(f"train_step:N{i}", _Text(HAND_HLO))
    profiler.register_executable("train_step:N5", _Text(HAND_HLO))
    names = list(profiler.op_scopes())
    assert len(names) == profiler._OP_SCOPES_CAP
    # oldest first, and a name registered again moves to the end
    assert names[0] == "train_step:N3" and names[-1] == "train_step:N5"


# -- the host phases of a call -----------------------------------------------

def test_a_call_observes_prepare_and_execute_and_compile_when_fresh(toy_bert):
    step = _step(toy_bert)
    step(*_batch())
    assert _phase_counts() == (1, 1, 1)
    step(*_batch())
    assert _phase_counts() == (2, 2, 1)
    step(*_batch(rows=2))          # a fresh signature compiles again
    assert _phase_counts() == (3, 3, 2)


def test_inside_a_step_the_phases_still_sum_to_the_wall(toy_bert):
    step = _step(toy_bert)
    step(*_batch())
    with telemetry.step_scope():
        with telemetry.phase("forward_backward"):
            step(*_batch())
    rec = telemetry.timeline()[-1]
    assert {data_parallel.PHASE_PREPARE, data_parallel.PHASE_EXECUTE,
            "forward_backward"} <= set(rec["phases"])
    assert sum(rec["phases"].values()) == pytest.approx(rec["wall_s"])


def test_the_text_is_read_once_a_compile_and_never_a_step(toy_bert,
                                                          monkeypatch):
    reads = []
    real = jax.stages.Compiled.as_text
    monkeypatch.setattr(jax.stages.Compiled, "as_text",
                        lambda self, *a, **kw: reads.append(1) or real(
                            self, *a, **kw))
    step = _step(toy_bert)
    for _ in range(10):
        step(*_batch())
    assert len(reads) == 1


def test_a_phase_on_another_thread_leaves_the_steps_stack_alone():
    """The step's stack belongs to the thread that opened the step: a
    phase on a producer thread used to pause and charge the main thread's
    open phase."""
    entered, leave = threading.Event(), threading.Event()

    def producer():
        with telemetry.phase("producer.stage"):
            entered.set()
            assert leave.wait(10)

    telemetry.step_begin()
    thread = threading.Thread(target=producer)
    with telemetry.phase("forward_backward"):
        thread.start()
        assert entered.wait(10)
        time.sleep(0.02)
        leave.set()
        thread.join(10)
        assert not thread.is_alive()
    rec = telemetry.step_end()
    assert set(rec["phases"]) <= {"forward_backward", "other"}
    assert rec["phases"]["forward_backward"] >= 0.02
    assert sum(rec["phases"].values()) == pytest.approx(rec["wall_s"])
    fam = telemetry.snapshot()["metrics"]["mxnet_step_phase_seconds"]
    seen = {s["labels"]["phase"]: s["count"] for s in fam["samples"]}
    assert seen["producer.stage"] == 1


# -- on the profiler's clock -------------------------------------------------

def test_phases_and_prefetch_spans_land_in_a_jax_trace(toy_bert, tmp_path):
    from jax.profiler import ProfileData

    step = _step(toy_bert)
    step(*_batch())
    stage0 = telemetry.snapshot()["metrics"][
        "mxnet_prefetch_stage_seconds"]["samples"][0]["count"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        with PrefetchIterator(iter([_batch(), _batch()])) as feed:
            for x, y in feed:
                jax.block_until_ready(step(x, y))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    assert {"mx:train_step.prepare", "mx:train_step.execute",
            "mx:prefetch.wait", "mx:prefetch.stage"} <= names
    assert "mx:train_step.compile" not in names
    stage = telemetry.snapshot()["metrics"]["mxnet_prefetch_stage_seconds"]
    assert stage["samples"][0]["count"] == stage0 + 2
