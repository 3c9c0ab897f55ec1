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
    args = (TrainStep._plain_tree(step.train_params),
            TrainStep._plain_tree(step.rest_params),
            TrainStep._plain_tree(step.opt_state), jax.random.PRNGKey(0),
            x, y)
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
                               "classes": ["forward"]}
    # a weight gradient's product with the optimizer's update as epilogue:
    # its own name says backward, what it fuses says both
    assert table["fusion.6"]["scope"].endswith("/dot_general")
    assert table["fusion.6"]["classes"] == ["backward", "optimizer"]
    # a fusion nested in a fusion counts too
    assert table["fusion.8"]["classes"] == ["backward", "forward"]
    assert table["copy.4"] == {"scope": "", "classes": []}
    # the loop and the ops of its body and condition are all on the line
    assert table["while.5"]["classes"] == ["backward"]
    assert profiler.SCOPE_ATTENTION_BWD in table["exp.3"]["scope"]
    assert "lt" in table
    # what a reducer applies and what a fusion fused never run by name
    assert not {"hidden", "dot.1", "sub.1", "c"} & set(table)


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
