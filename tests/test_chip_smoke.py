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


@pytest.mark.parametrize("lq,lk,dim,causal", [
    (256, 256, 64, False), (512, 512, 64, True), (256, 512, 128, True)])
def test_flash_forward_interpreted_matches_reference(lq, lk, dim, causal):
    from jax.experimental.pallas import tpu as pltpu

    from mxnet_tpu.ops.flash_attention import (_fa_forward_pallas,
                                               _mha_with_lse)

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 1, lq, dim).astype("f"))
    k = jnp.asarray(rs.randn(1, 1, lk, dim).astype("f"))
    v = jnp.asarray(rs.randn(1, 1, lk, dim).astype("f"))
    scale = 1.0 / np.sqrt(dim)
    with pltpu.force_tpu_interpret_mode():
        o, lse = _fa_forward_pallas(q, k, v, causal, scale)
    ref_o, ref_lse = _mha_with_lse(q, k, v, causal, scale)
    np.testing.assert_allclose(o, ref_o, atol=2e-6)
    np.testing.assert_allclose(lse, ref_lse, atol=2e-6)


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
    monkeypatch.setattr(fa, "_use_pallas", lambda q: q.shape[-2] >= 256)
    mesh = make_mesh(devices=jax.devices()[:4])

    step = TrainStep(toy_bert, chip_smoke.pretrain_loss, optimizer="adam",
                     dtype="bfloat16", mesh=mesh, batch_axes=("dp",))
    x = step._stage_batch(np.zeros((4, 256), "int32"))
    y = step._stage_batch(np.zeros((4, 257), "int32"))
    text = step._step.trace(
        step._plain_tree(step.train_params),
        step._plain_tree(step.rest_params),
        step._plain_tree(step.opt_state), jax.random.PRNGKey(0), x, y,
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
