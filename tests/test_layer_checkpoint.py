"""What a decoder layer's checkpoint keeps (``LlamaConfig(remat=True)``): the
attention op's output and row statistics (the delta rule's output and chunk
states), named inside the ops' ``custom_vjp`` rules and asked for by the
layer's policy, so that the backward holds one forward of the op a layer and
not two.  The comparison is the parent's plain ``jax.checkpoint``, built here:
the library has no switch back to it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.gluon.model_zoo.language import llama
from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.ops import kda
from mxnet_tpu.parallel.functional import functionalize

L = 32
WIDTHS = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
              num_kv_heads=2, head_dim=8, intermediate_size=48,
              max_seq_len=2 * L, remat=True)
# the four masks an attention call of a decoder runs under, and the delta
# rule beside latent attention
CASES = {
    "causal": {},
    "window": dict(attention_types=("window", "full"), attention_window=8),
    "block_diffusion": dict(block_diffusion=4),
    "segment_ids": dict(attention_types=("window", "full"),
                        attention_window=8),
    "kda_mla": dict(num_kv_heads=4, head_dim=16,
                    attention_types=("kda", "mla"),
                    attention_gate="head_wise", kv_lora_rank=16,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                    rope_interleave=True),
}
# the named values a layer: bytes of o and lse in float32 (batch 2, 4 heads,
# L rows; the block-diffusion rows are [xt ; x0], 2 L), and of the delta
# rule's o and states (L / 16 chunks of 16 x 16) beside the latent call's
KEPT = {
    "causal": {fa.KEPT_O: 2 * (2 * 4 * L * 8 * 4),
               fa.KEPT_LSE: 2 * (2 * 4 * L * 4)},
    "block_diffusion": {fa.KEPT_O: 2 * (2 * 4 * 2 * L * 8 * 4),
                        fa.KEPT_LSE: 2 * (2 * 4 * 2 * L * 4)},
    "kda_mla": {fa.KEPT_O: 2 * 4 * L * 16 * 4, fa.KEPT_LSE: 2 * 4 * L * 4,
                kda.KEPT_O: 2 * 4 * L * 16 * 4,
                kda.KEPT_STATES: 2 * 4 * (L // 16) * 16 * 16 * 4},
}
KEPT["window"] = KEPT["segment_ids"] = KEPT["causal"]


@pytest.fixture(autouse=True)
def chunks_of_16(monkeypatch):
    """The decoder's delta-rule chunk, short enough for ``L`` rows."""
    monkeypatch.setattr(llama, "KDA_CHUNK", 16)


def _loss_of(case):
    """``(loss(params), params)`` of the case's two-layer net on one batch:
    the mean square of its logits, float32."""
    net = llama.LlamaForCausalLM(llama.LlamaConfig(**dict(WIDTHS,
                                                          **CASES[case])))
    net.initialize()
    rs = np.random.RandomState(3)
    rows = 2 * L if case == "block_diffusion" else L
    inputs = [rs.randint(0, 64, (2, rows)).astype("int32")]
    if case == "segment_ids":    # documents of 13, 9, 6 and 4 rows
        inputs.append(np.tile(np.repeat(np.arange(4), (13, 9, 6, 4)), (2, 1))
                      .astype("int32"))
    apply_fn, params = functionalize(net)

    def loss(params):
        return jnp.mean(jnp.square(apply_fn(params, jax.random.PRNGKey(0),
                                            *inputs)))

    return loss, params


def _plain_checkpoint(monkeypatch):
    """The parent's layer: ``jax.checkpoint`` with no policy."""
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("case", list(CASES))
def test_loss_and_gradients_are_the_plain_checkpoints(case, jit, monkeypatch):
    """The kept values are those the second forward would have produced, op
    for op: with no jit the loss and every gradient are the plain
    checkpoint's bit for bit; under jit XLA may fuse the two programs
    differently, so to float32 rounding."""
    loss, params = _loss_of(case)

    def value_and_grad():
        if jit:
            return jax.jit(jax.value_and_grad(loss))(params)
        with jax.disable_jit():
            return jax.value_and_grad(loss)(params)

    got, grads = value_and_grad()
    with monkeypatch.context() as patch:
        _plain_checkpoint(patch)
        want, plain = value_and_grad()
    assert set(grads) == set(params)
    for name in grads:
        assert float(jnp.abs(grads[name]).max()) > 0, name
        if jit:
            scale = float(jnp.abs(plain[name]).max())
            # (a sum of terms that cancel, such as the decay's ``a_log``,
            # moves in its fifth digit with the order of the sum)
            np.testing.assert_allclose(grads[name], plain[name], rtol=1e-4,
                                       atol=1e-5 * scale, err_msg=name)
        else:
            np.testing.assert_array_equal(grads[name], plain[name], name)
    if jit:
        np.testing.assert_allclose(got, want, rtol=1e-6)
    else:
        assert float(got) == float(want)


def _forward_calls(jaxpr):
    """How often the attention op's and the delta rule's forward stand in a
    jaxpr: their rules name the output once a call."""
    text = str(jaxpr)
    return {name: text.count(f"name={name}]")
            for name in (fa.KEPT_O, kda.KEPT_O)}


@pytest.mark.parametrize("case", list(CASES))
def test_the_backward_holds_one_forward_of_the_op_a_layer(case, monkeypatch):
    """The gradient's jaxpr holds each op's forward once a layer that calls
    it, where the plain checkpoint's holds it twice (the forward pass and
    its recomputation); everything else of the layer is still recomputed;
    and ``mxnet_layer_checkpoint_kept_bytes_total{name}`` reads the bytes of
    the named values, once a layer."""
    loss, params = _loss_of(case)
    telemetry.reset()
    kept = jax.make_jaxpr(jax.grad(loss))(params)
    counted = {name: telemetry.LAYER_CHECKPOINT_KEPT_BYTES.labels(
        name=name).value for name in KEPT[case]}
    with monkeypatch.context() as patch:
        _plain_checkpoint(patch)
        plain = jax.make_jaxpr(jax.grad(loss))(params)
    once = {name: (1 if case == "kda_mla" else 2) if name in KEPT[case] else 0
            for name in (fa.KEPT_O, kda.KEPT_O)}
    assert _forward_calls(kept) == once
    assert _forward_calls(plain) == {k: 2 * v for k, v in once.items()}
    # the statistics (the chunk states) are kept beside the output
    for name in KEPT[case]:
        assert str(kept).count(f"name={name}]") == (
            1 if case == "kda_mla" else 2), name
    assert counted == KEPT[case]
    # the projections before the op are computed again in both: as many
    # checkpoints (the layers', and the delta mixer's small ops' own), and
    # as many matrix products less those of the op's dropped second forward
    assert str(kept).count("remat2[") == str(plain).count("remat2[") >= 2
    assert 0 < str(kept).count("dot_general") < str(plain).count(
        "dot_general")


def test_a_checkpoint_without_the_policy_keeps_nothing_of_the_names():
    """The names alone keep nothing: ``TrainStep(remat=True)``'s whole-net
    checkpoint, or any ``jax.checkpoint`` without the policy, recomputes the
    op as before (two forward calls a layer inside it)."""
    loss, params = _loss_of("causal")
    whole = jax.make_jaxpr(jax.grad(jax.checkpoint(loss)))(params)
    assert _forward_calls(whole)[fa.KEPT_O] == 4
