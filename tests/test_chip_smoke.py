"""What ``chip_smoke.py`` rests on, checked without a chip: its inner legs
at toy widths, its refusal to run off the chip, the Pallas flash forward's
arithmetic under the TPU interpreter, and where the compile cache goes."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx

sys.path.insert(0, __file__.rsplit("/", 2)[0])
import chip_smoke


@pytest.fixture(scope="module")
def toy_bert():
    from mxnet_tpu.gluon.model_zoo.language import bert

    mx.random.seed(0)
    # one width everywhere: initialisation compiles a program per shape
    net = bert.BertForPretraining(bert.BertConfig(
        vocab_size=64, hidden_size=64, num_layers=1, num_heads=1,
        intermediate_size=64, max_position=256))
    net.initialize()
    net(mx.nd.zeros((1, 16), dtype="int32"))
    return net


def test_bert_leg_one_device_and_dp_mesh(toy_bert):
    from mxnet_tpu.parallel.mesh import make_mesh

    one = chip_smoke.bert_leg(toy_bert, 4, 16, 5)
    many = chip_smoke.bert_leg(toy_bert, 16, 16, 5,
                               mesh=make_mesh(devices=jax.devices()[:4]))
    for r in (one, many):
        assert r["platforms"] == ["cpu"] and not r["mosaic_call"]
    # 64 against 256 tokens here, where main() compares 8k against 32k
    assert abs(one["losses"][0] - many["losses"][0]) < 0.2


def test_label_and_device_agree_off_the_default_device():
    """Where they used to part: a creation op and a parameter asked for
    on a device that is not JAX's default sit on it, like ``nd.array``."""
    from mxnet_tpu import gluon

    ctx = mx.cpu(1)
    dense = gluon.nn.Dense(2, in_units=3)
    dense.initialize(ctx=ctx)
    for arr in (mx.nd.zeros((2, 2), ctx=ctx),
                mx.nd.random.uniform(shape=(2,), ctx=ctx),
                mx.nd.array([1.0], ctx=ctx), dense.weight.data(),
                dense(mx.nd.ones((1, 3), ctx=ctx))):
        chip_smoke._check_placed(arr, ctx)
    chip_smoke._check_placed(mx.nd.zeros((2, 2)), mx.current_context())


def test_main_refuses_without_a_chip(capsys):
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err


# float32 inputs keep float32 operands: the one-pass softmax where the K
# row is one block, and with a smaller K tile handed in the online update,
# unrolled and (causal) skipping.  bf16 inputs feed the MXU as they are,
# against the plain path on the same bf16 inputs.
@pytest.mark.parametrize("lq,lk,dim,causal,dtype,block_k", [
    (256, 256, 64, False, "float32", None),
    (512, 512, 64, True, "float32", None),
    (256, 512, 128, True, "float32", None),
    (512, 512, 64, False, "float32", 128),
    (512, 512, 64, True, "float32", 128),
    (512, 512, 64, False, "bfloat16", None),
    (1024, 1024, 64, False, "bfloat16", 256),
    (1024, 1024, 64, True, "bfloat16", 256),
    (256, 512, 64, True, "bfloat16", None),
    (512, 512, 128, False, "bfloat16", None),
    (256, 512, 128, True, "bfloat16", None)])
def test_flash_forward_interpreted_matches_reference(lq, lk, dim, causal,
                                                     dtype, block_k):
    from jax.experimental.pallas import tpu as pltpu

    from mxnet_tpu.ops.flash_attention import (_fa_block_sizes,
                                               _fa_forward_pallas,
                                               _mha_with_lse)

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 1, lq, dim).astype("f")).astype(dtype)
    k = jnp.asarray(rs.randn(1, 1, lk, dim).astype("f")).astype(dtype)
    v = jnp.asarray(rs.randn(1, 1, lk, dim).astype("f")).astype(dtype)
    scale = 1.0 / np.sqrt(dim)
    if block_k is None:      # these shapes take the whole K row by the rule
        assert _fa_block_sizes(lq, lk, dim, q.dtype.itemsize)[1] == lk
    with pltpu.force_tpu_interpret_mode():
        o, lse = _fa_forward_pallas(q, k, v, causal, scale, block_k=block_k)
    ref_o, ref_lse = _mha_with_lse(q, k, v, causal, scale)
    assert o.dtype == q.dtype and lse.dtype == jnp.float32
    if dtype == "float32":
        np.testing.assert_allclose(o, ref_o, atol=2e-6)
        np.testing.assert_allclose(lse, ref_lse, atol=2e-6)
    else:
        # two units in bf16's last place at the outputs' size
        np.testing.assert_allclose(o.astype("float32"),
                                   ref_o.astype("float32"),
                                   rtol=2e-2, atol=1.6e-2)
        np.testing.assert_allclose(lse, ref_lse, atol=1e-2)


@pytest.mark.parametrize("lq,lk,dim,dtype,given,tiles", [
    # from the shape: the q tile as large as divides, the whole K row while
    # the score tile fits the budget, else the largest that divides and fits
    (512, 512, 64, "bfloat16", {}, (512, 512)),
    (512, 512, 64, "float32", {}, (512, 512)),
    (1024, 1024, 64, "bfloat16", {}, (512, 1024)),
    (2048, 2048, 128, "bfloat16", {}, (512, 512)),
    (256, 512, 128, "bfloat16", {}, (256, 512)),
    (384, 384, 64, "bfloat16", {}, (128, 384)),
    # a tile the caller hands in wins, each by itself
    (512, 512, 64, "bfloat16", {"block_q": 128}, (128, 512)),
    (512, 512, 64, "bfloat16", {"block_k": 256}, (512, 256)),
    (512, 512, 64, "bfloat16", {"block_q": 128, "block_k": 128},
     (128, 128))])
def test_flash_tiles_come_from_the_shape_unless_handed_in(lq, lk, dim, dtype,
                                                          given, tiles,
                                                          monkeypatch):
    from mxnet_tpu.ops import flash_attention as fa

    if not given:
        assert fa._fa_block_sizes(lq, lk, dim,
                                  jnp.dtype(dtype).itemsize) == tiles
    # what the kernel is traced with: its q block and its K tile
    traced = []
    kernel = fa._fa_fwd_kernel

    def spy(q_ref, *refs, block_k, **kw):
        traced.append((q_ref.shape[0], block_k))
        return kernel(q_ref, *refs, block_k=block_k, **kw)

    monkeypatch.setattr(fa, "_fa_fwd_kernel", spy)
    q = jax.ShapeDtypeStruct((1, 2, lq, dim), dtype)
    k = jax.ShapeDtypeStruct((1, 2, lk, dim), dtype)
    o, lse = jax.eval_shape(
        lambda q, k: fa._fa_forward_pallas(q, k, k, False, 0.125, **given),
        q, k)
    assert traced == [tiles]
    assert o.shape == q.shape and lse.shape == (1, 2, lq)


def test_sharded_step_runs_the_kernel_per_batch_shard(toy_bert, monkeypatch):
    """GSPMD cannot partition a Mosaic kernel.  Lowered for the TPU, a step
    over a dp mesh holds the Pallas forward inside a shard_map; without
    the scope TrainStep opens, the same lowering is refused.  Interpreted
    on the virtual mesh, the sharded kernel agrees with the reference."""
    from jax.experimental.pallas import tpu as pltpu

    from mxnet_tpu.ops import flash_attention as fa
    from mxnet_tpu.parallel.data_parallel import TrainStep
    from mxnet_tpu.parallel.mesh import make_mesh

    # what the gate answers where the default backend is the chip
    monkeypatch.setattr(fa, "_use_pallas", lambda q, v=None: q.shape[-2] >= 256)
    mesh = make_mesh(devices=jax.devices()[:4])

    step = TrainStep(toy_bert, chip_smoke.pretrain_loss, optimizer="adam",
                     dtype="bfloat16", mesh=mesh, batch_axes=("dp",))
    x = step._stage_batch(np.zeros((4, 256), "int32"))
    y = step._stage_batch(np.zeros((4, 257), "int32"))
    text = step._step.trace(
        step.train_params, step.rest_params, step.opt_state,
        jax.random.PRNGKey(0), x, y,
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "sdy.manual_computation" in text

    rs = np.random.RandomState(0)
    q, k, v = (jax.device_put(rs.randn(4, 1, 256, 64).astype("f"),
                              step._batch_shard) for _ in range(3))
    attend = jax.jit(fa.flash_attention)
    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        attend.trace(q, k, v).lower(lowering_platforms=("tpu",))
    with fa.batch_sharded(mesh, ("dp",)), pltpu.force_tpu_interpret_mode():
        o = jax.jit(fa.flash_attention)(q, k, v)
    np.testing.assert_allclose(
        o, fa._mha_reference(q, k, v, False, 0.125), atol=2e-6)


def test_sharded_backward_runs_the_kernel_per_batch_shard(monkeypatch):
    """The backward is traced after ``batch_sharded`` has closed (the
    transpose follows the forward's return), so the op keeps the scope it
    was called under: lowered for the TPU over a dp mesh, the gradient
    holds both kernels inside shard_maps; interpreted on the virtual mesh,
    the sharded backward kernel agrees with autodiff through the plain
    path and is the path counted."""
    from jax.experimental.pallas import tpu as pltpu

    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import flash_attention as fa
    from mxnet_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(fa, "_use_pallas", lambda q, v=None: q.shape[-2] >= 256)
    mesh = make_mesh(devices=jax.devices()[:4])
    rs = np.random.RandomState(1)
    q, k, v, g = (jnp.asarray(rs.randn(4, 2, 256, 64).astype("f")).astype(
        "bfloat16") for _ in range(4))

    def grads(attend):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(attend(q, k, v) * g), (0, 1, 2)))

    def sharded(q, k, v):
        with fa.batch_sharded(mesh, ("dp",)):
            return fa.flash_attention(q, k, v, causal=True)

    def taken():
        return {s["labels"]["path"]: s["value"] for s in telemetry.snapshot()[
            "metrics"]["mxnet_flash_attention_bwd_calls_total"]["samples"]}

    text = grads(sharded).trace(q, k, v).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("sdy.manual_computation") == 2
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 2
    before = taken()
    with pltpu.force_tpu_interpret_mode():
        got = grads(sharded)(q, k, v)
    assert taken()["pallas"] == before["pallas"] + 1
    want = grads(lambda q, k, v: fa._mha_reference(q, k, v, True, 0.125))(
        *(x.astype("float32") for x in (q, k, v)))
    # bf16 against the float32 answer on the same inputs: p, ds and the
    # results are rounded (tests/test_flash_attention_bwd.py)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a.astype("float32"), b, rtol=2 ** -6,
                                   atol=2 ** -5)


def test_compile_cache_placement(monkeypatch, tmp_path):
    import os

    from mxnet_tpu import engine

    before = jax.config.jax_compilation_cache_dir
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(mx.__file__)))
    try:
        monkeypatch.chdir(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        jax.config.update("jax_compilation_cache_dir", None)
        assert engine._place_compile_cache() == \
            os.path.join(checkout, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(checkout, ".jax_cache")
        # placed from outside: nothing is set in code
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", "/untouched")
        assert engine._place_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == "/untouched"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
