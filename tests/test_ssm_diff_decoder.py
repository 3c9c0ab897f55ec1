"""What ISSUE 45 added for a decoder-hybrid-decoder (Phi-4-mini-flash): a
small net of the five kinds of layer (window, state-space, full, gated
memory, cross; differential attention, LayerNorm, tied embeddings) through
``TrainStep`` against the configuration's plain reference, with each part
left out in turn; what the layers hand on through their checkpoints against a
net without them; the vocabulary's slices against the uncut head; export; and
what refuses the kinds.  The scan itself is ``test_selective_scan.py``'s.  All
on the CPU, seeded random weights."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.gluon.model_zoo.language import llama
from mxnet_tpu.ops import selective_scan as ss
from mxnet_tpu.parallel.functional import functionalize

import decoder_parity as parity

NAME = "phi4_mini_flash"


@pytest.fixture(autouse=True)
def chunks_of_16(monkeypatch):
    """The small nets' rows are 32 tokens long: the decoder's scan goes in
    chunks of 16 rows here (``F.selective_scan`` is tested at its own)."""
    monkeypatch.setattr(llama, "SSM_CHUNK", 16)


# --------------------------------------------------------------------------
# the configuration at a small size (``decoder_parity.ROWS``)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("amp,tolerance", [
    # float32 against float32: the order of the sums (0 / 4e-7 / 1.4e-6 /
    # 7e-5 measured at the seed)
    (None, {"loss_gap": 1e-5, "first_gradient_gap": 5e-5,
            "first_gradient_error": 5e-5, "change_gap": 1e-3}),
    # bf16 operands: three decimal digits a product, through a norm of a
    # difference of two maps that brings a small output back to size 1
    ("bfloat16", {"loss_gap": 2e-3, "first_gradient_gap": 0.15,
                  "first_gradient_error": 0.5, "change_gap": 0.1}),
])
def test_program_matches_the_reference_loss_and_every_gradient(amp,
                                                               tolerance):
    from mxnet_tpu import profiler

    _, metrics = parity.matches(NAME, amp, tolerance)
    value = lambda family, **labels: next(
        s["value"] for s in metrics[family]["samples"]
        if s["labels"] == labels)
    # one scan a trace (the checkpoint traces its layer once), two chunks of
    # 16 rows; the memory (2 x 32 x 128) and the pair (k 2 x 2 x 32 x 16, v 2
    # x 1 x 32 x 32) cross their layers' checkpoints in the step's dtype
    assert value("mxnet_selective_scan_fwd_calls_total", path="scan") == 1
    assert value("mxnet_selective_scan_chunks_total") == 2
    size = 2 if amp else 4
    assert value("mxnet_layer_handed_on_bytes_total", name="memory") \
        == 2 * 32 * 128 * size
    assert value("mxnet_layer_handed_on_bytes_total", name="kv") \
        == 2 * (2 * 2 * 32 * 16) * size
    assert value("mxnet_layer_checkpoint_kept_bytes_total",
                 name=ss.KEPT_STATES) == 2 * 2 * 4 * 128 * 4
    table = [t for name, t in profiler.op_scopes().items()
             if name.startswith("train_step:")][-1]
    parts = {row["part"] for row in table.values()}
    assert {profiler.SCOPE_SSM_SCAN, profiler.SCOPE_MIXER_GATE} <= parts
    scopes = " ".join(row["scope"] for row in table.values())
    assert profiler.KERNEL_SSM_SCAN_FWD in scopes \
        and profiler.KERNEL_SSM_SCAN_BWD in scopes


def _larger_steps(weights):
    """The seed draws steps of 0.001 to 0.1, at which 32 rows decay little:
    three more on ``dt_proj``'s bias, and the decay shapes the state."""
    return {name: v + 3.0 if name.endswith("ssm.dt_b") else v
            for name, v in weights.items()}


def _biased_norms(weights):
    k = jax.random.PRNGKey(1)
    return {name: 0.3 * jax.random.normal(k, v.shape)
            if name.endswith("norm.b") else v for name, v in weights.items()}


@functools.lru_cache(maxsize=None)
def _sound_step():
    """``(cfg, start, got)``: the sound program's first step from weights
    under which every part matters (larger steps, norms with a bias), driven
    once for the cases that plant their fault in the reference."""
    cfg, _, reference, _ = parity.small(NAME)
    start = _biased_norms(_larger_steps(
        reference.init_params(cfg, parity.SEED)))
    return cfg, start, parity.program(NAME, cfg, start, 1)[0]


# planted in the reference by its ``drop`` (``faults.py``'s five), or, the
# LayerNorms' biases, in the program: its weights' at zero
LEFT_OUT = ("decay", "memory_gate", "lambda", "cross_kv", "memory_after_gate",
            "norm_bias")


@pytest.mark.parametrize("left_out", LEFT_OUT)
def test_the_parity_test_sees_each_part_left_out(left_out):
    """One mechanism left out on one side no longer agrees: the float32
    comparison above would fail by ``first_gradient_error`` or ``loss_gap``,
    over ten times its tolerance."""
    from chipbench.harness import check

    cfg, start, got = _sound_step()
    if left_out == "norm_bias":
        got = parity.program(NAME, cfg, parity.zeroed("norm.b")(start), 1)[0]
    else:
        cfg = dict(cfg, drop=[left_out])
    stats = check.compare(got, parity.followed(NAME, cfg, start))
    assert max(stats["first_gradient_error"][0],
               stats["loss_gap"][0]) > 5e-4, stats


def test_counts_of_the_configuration():
    cfg, counts = parity.published(NAME)
    reference = parity.small(NAME)[2]
    assert counts.layer_kinds(cfg) == ["window", "ssm", "full", "gmu",
                                       "cross"]
    whole = dict(cfg, layers_first=0, num_hidden_layers=32)
    kinds = counts.layer_kinds(whole)
    assert kinds[:17:2] == ["ssm"] * 9 and kinds[18::2] == ["gmu"] * 7
    assert kinds[1:16:2] == ["window"] * 8 and kinds[17] == "full" \
        and kinds[19::2] == ["cross"] * 7
    assert counts.ssm_sizes(cfg) == (5120, 16, 4, 160)
    n = sum(int(np.prod(shape)) for shape, _ in
            reference.param_shapes(cfg).values())
    # the issue's reckoning: 577.1M parameters (and the biases and norms)
    assert n == pytest.approx(577.1e6, rel=1e-3)
    # the pairs by brute force: a window of 512 shows 12% of the causal ones
    assert counts.window_pairs(64, 8) == sum(min(i + 1, 8) for i in range(64))
    assert counts.window_pairs(8192, 512) / counts.causal_pairs(8192) \
        == pytest.approx(0.121, abs=1e-3)
    assert counts.attention_fwd_flops(cfg, 8192, "full") \
        == 2 * 40 * counts.causal_pairs(8192) * (64 + 128)
    assert counts.attention_fwd_bytes(cfg, 8192, 2) \
        == 40 * 8192 * ((64 + 64 + 128 + 128) * 2 + 4)
    # the scan a row a channel by a loop over the state
    row = sum(1 + 3 + 2 for _ in range(16)) + 3
    assert counts.ssm_scan_fwd_flops(cfg, 8192) == 8192 * 5120 * row
    assert counts.ssm_scan_fwd_bytes(cfg, 8192, 2) == (
        8192 * 5120 * (2 + 2 + 4) + 8192 * 32 * 2 + (5120 * 16 + 5120) * 4
        + 128 * 5120 * 16 * 4)
    # the SwiGLUs are three fifths of the step's operations
    swiglu = 5 * 6 * 8192 * 2560 * 10240
    assert swiglu / counts.forward_flops_per_sample(cfg, 8192) \
        == pytest.approx(0.6, abs=0.05)


# --------------------------------------------------------------------------
# what the layers hand on, through their checkpoints
# --------------------------------------------------------------------------
def _tiny(**overrides):
    kw = dict(vocab_size=64, hidden_size=32, num_layers=5, num_heads=4,
              num_kv_heads=2, head_dim=8, intermediate_size=48,
              attention_types=("window", "ssm", "full", "gmu", "cross"),
              attention_window=8, rope_attention_types=(), differential=True,
              norm="layer", attention_bias=True, ssm_state_size=4,
              ssm_dt_rank=4, first_layer_index=15, tie_embeddings=True)
    kw.update(overrides)
    return llama.LlamaConfig(**kw)


def _loss_of(remat, seed=3):
    """``(loss(params), params)`` of the five-layer net on one batch, the
    mean square of its logits; the same weights whatever ``remat``."""
    mx.random.seed(seed)
    net = llama.LlamaForCausalLM(_tiny(remat=remat))
    net.initialize(mx.init.Normal(0.1))
    ids = np.random.RandomState(3).randint(0, 64, (1, 16)).astype("int32")
    apply_fn, params = functionalize(net)
    order = sorted(params)
    return (lambda values: jnp.mean(jnp.square(apply_fn(
        dict(zip(order, values)), jax.random.PRNGKey(0), ids))),
        [params[k] for k in order])


def test_handed_on_values_cross_the_checkpoints_bit_for_bit():
    """The memory and the K/V pair leave one layer's checkpoint as outputs
    and enter later ones as inputs, their cotangents coming back from every
    reader: with no jit the loss and every gradient are those of the net
    without ``remat``, bit for bit."""
    loss, params = _loss_of(True)
    plain, same = _loss_of(False)
    for a, b in zip(params, same):
        np.testing.assert_array_equal(a, b)
    with jax.disable_jit():
        got, grads = jax.value_and_grad(loss)(params)
        want, wanted = jax.value_and_grad(plain)(same)
    assert float(got) == float(want)
    for g, w in zip(grads, wanted):
        assert float(jnp.abs(g).max()) > 0
        np.testing.assert_array_equal(g, w)


def test_the_gradient_walks_one_scan_a_layer(monkeypatch):
    """The gradient's jaxpr names the scan's output once: the layer's
    checkpoint keeps it and the chunk states, where the plain checkpoint
    walks the scan again."""
    loss, params = _loss_of(True)
    kept = str(jax.make_jaxpr(jax.grad(loss))(params))
    assert [kept.count(f"name={n}]") for n in (ss.KEPT_Y, ss.KEPT_STATES)] \
        == [1, 1]
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)
    loss, params = _loss_of(True)
    plain = str(jax.make_jaxpr(jax.grad(loss))(params))
    assert plain.count(f"name={ss.KEPT_Y}]") == 2


def test_the_vocabulary_slices_side_by_side_are_the_uncut_head():
    """The model-configs guide's test of the cut: a net that holds one slice
    of eight of the tied embedding gives, for ids of its slice, the uncut
    net's logits over that slice; the eight side by side are the whole."""
    mx.random.seed(0)
    whole = llama.LlamaForCausalLM(_tiny())
    whole.initialize(mx.init.Normal(0.1))
    weights = {n[len(whole.prefix):]: p.data().asnumpy()
               for n, p in whole.collect_params().items()}
    rs = np.random.RandomState(1)
    for s in range(8):
        part = llama.LlamaForCausalLM(_tiny(vocab_size=8))
        part.initialize()
        for n, p in part.collect_params().items():
            value = weights[n[len(part.prefix):]]
            p.set_data(nd.array(value[8 * s:8 * s + 8]
                                if n.endswith("embed_tokens_weight")
                                else value))
        ids = rs.randint(0, 8, (1, 16)).astype("int32")
        np.testing.assert_allclose(
            part(nd.array(ids, dtype="int32")).asnumpy(),
            whole(nd.array(ids + 8 * s, dtype="int32")).asnumpy()[
                ..., 8 * s:8 * s + 8], rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# the zoo: kinds, export, refusals
# --------------------------------------------------------------------------
def test_hybridize_and_export_round_trip(tmp_path):
    net = llama.LlamaForCausalLM(_tiny())
    net.initialize(mx.init.Normal(0.1))
    ids = nd.array(np.random.RandomState(0).randint(0, 64, (2, 32)),
                   dtype="int32")
    eager = net(ids).asnumpy()
    net.hybridize()
    np.testing.assert_allclose(net(ids).asnumpy(), eager, rtol=2e-5,
                               atol=2e-6)
    path = str(tmp_path / "phi")
    net.export(path)
    back = gluon.SymbolBlock.imports(path + "-symbol.json", ["data"],
                                     path + "-0000.params")
    np.testing.assert_allclose(back(ids).asnumpy(), eager, rtol=2e-5,
                               atol=2e-6)


def test_kinds_hand_overs_and_what_refuses_them():
    cfg = _tiny()
    net = llama.LlamaForCausalLM(cfg)
    assert [type(layer.self_attn).__name__ for layer in net.model.layers] \
        == ["LlamaAttention", "LlamaStateSpace", "LlamaAttention",
            "LlamaGatedMemory", "LlamaCrossAttention"]
    assert [(layer.hands_on, layer.reads) for layer in net.model.layers] \
        == [(None, None), ("memory", None), ("kv", None), (None, "memory"),
            (None, "kv")]
    assert (cfg.memory_layer, cfg.kv_layer) == (1, 2)
    shapes = {n[len(net.prefix):]: p.shape
              for n, p in net.collect_params().items()}
    assert "lm_head_weight" not in shapes      # tied: the embedding's
    assert shapes["model_layers_1_self_attn_in_proj_weight"] == (128, 32)
    assert shapes["model_layers_1_self_attn_x_proj_weight"] == (12, 64)
    assert shapes["model_layers_1_self_attn_a_log"] == (64, 4)
    assert shapes["model_layers_3_self_attn_in_proj_weight"] == (64, 32)
    assert shapes["model_layers_4_self_attn_maps_subln_weight"] == (16,)
    assert "model_layers_4_self_attn_k_proj_weight" not in shapes
    assert shapes["model_layers_0_input_layernorm_beta"] == (32,)
    # lambda_init by the published index
    assert net.model.layers[4].self_attn.maps._lambda_init \
        == pytest.approx(0.8 - 0.6 * np.exp(-0.3 * 19))
    assert not cfg.layers_alike()
    for apply in (lambda: llama.prefill_apply({}, cfg, None),
                  lambda: llama.decode_apply({}, cfg, None, None, None)):
        with pytest.raises(mx.MXNetError,
                           match="'ssm', 'gmu' or 'cross' layers"):
            apply()
    plain = _tiny(num_layers=1, attention_types=("full",))
    with pytest.raises(mx.MXNetError, match="differential attention, "
                       "LayerNorm, projection biases or tied embeddings"):
        llama.prefill_apply({}, plain, None)
    # packed documents: refused by the layer's name
    net.initialize()
    ids = nd.array(np.zeros((1, 16)), dtype="int32")
    with pytest.raises(mx.MXNetError, match="'ssm' layer does not take "
                       "segment_ids"):
        net(ids, ids)
    # the plain forms of the same kinds: one softmax map, RMSNorm, a head
    plain = llama.LlamaForCausalLM(_tiny(
        differential=False, norm="rms", attention_bias=False,
        tie_embeddings=False))
    plain.initialize(mx.init.Normal(0.1))
    ids = nd.array(np.random.RandomState(0).randint(0, 64, (2, 16)),
                   dtype="int32")
    eager = plain(ids).asnumpy()
    plain.hybridize()
    np.testing.assert_allclose(plain(ids).asnumpy(), eager, rtol=2e-5,
                               atol=2e-6)
    for bad, match in (
            ({"rope_attention_types": ("full",)},
             "'cross' layer's queries take no positions"),
            ({"attention_types": ("window", "gmu", "full", "ssm", "cross")},
             "'gmu' layer reads what an earlier layer"),
            ({"attention_types": ("cross", "ssm", "full", "gmu", "cross")},
             "'cross' layer reads what an earlier layer"),
            ({"attention_types": ("window", "ssm", "full", "gmu", "linear")},
             "'ssm', 'gmu' or 'cross'"),
            ({"norm": "batch"}, "norm is 'rms' or 'layer'"),
            ({"num_kv_heads": 1}, "differential attention pairs"),
            ({"block_diffusion": 4, "differential": False},
             "block-diffusion layout")):
        with pytest.raises(mx.MXNetError, match=match):
            _tiny(**bad)
