"""What ISSUE 30 added for a decoder of window and full layers with a shared
expert beside sigmoid-routed ones: the window mask in the attention op
(predicate, the forward's tile range, kernel under the TPU interpreter, plain
path, both backward paths) against a dense boolean mask; sigmoid routing
with a selection bias, renormalisation and a scale against a loop over
tokens; the shares of an expert-parallel deployment with the shared expert
counted once against the uncut reference; and a small net of the same shape
of layer through ``TrainStep`` against the configuration's plain reference.
All on the CPU, seeded random weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu.gluon.model_zoo.language import llama
from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.parallel.expert_parallel import moe_apply

import decoder_parity as parity
from test_block_diffusion_moe import _expert_weights, _grouped, dense_attention


# --------------------------------------------------------------------------
# the mask
# --------------------------------------------------------------------------
def dense_window(lq, lk, window):
    """The mask as the issue words it, entry by entry (no shared code with
    ``_visible``): query i, at position i + lk - lq of the keys, may see key
    j iff it lies among the last ``window`` up to that position."""
    out = np.zeros((lq, lk), bool)
    for i in range(lq):
        for j in range(lk):
            at = i + lk - lq
            out[i, j] = at - window < j <= at
    return out


# (lq, lk, window, block_q, block_k): the window smaller than, equal to and
# larger than a tile and than the row; lq == lk and lq < lk, the latter with
# K tiles that no query sees; a length that is no whole number of tiles
WINDOW_CASES = [(256, 256, 32, 128, 128), (256, 256, 128, 128, 128),
                (384, 384, 200, 128, 128), (256, 256, 1000, 128, 128),
                (128, 384, 64, 128, 128), (128, 512, 128, 128, 128),
                (256, 512, 300, 128, 256), (40, 40, 7, None, None)]


@pytest.mark.parametrize("lq,lk,window,block_q,block_k", WINDOW_CASES)
def test_window_predicate_tile_range_and_pairs_table(lq, lk, window, block_q,
                                                     block_k):
    mask = fa._mask_key("window", 0, False, window)
    assert mask == (fa.WINDOW, window)
    want = dense_window(lq, lk, window)
    seen = fa._visible(np, np.arange(lq)[:, None], np.arange(lk)[None, :],
                       False, mask, lq, lk)
    assert (seen == want).all()
    if block_q is None:
        return
    # the forward kernel's one range of K tiles a q tile is exactly the
    # tiles in which some pair is visible
    some, every = fa._tile_visibility(False, mask, lq, lk, block_q, block_k)
    tiles = want.reshape(lq // block_q, block_q, lk // block_k, block_k)
    assert (some == tiles.any(axis=(1, 3))).all()
    assert (every == tiles.all(axis=(1, 3))).all()
    for i in range(lq // block_q):
        lo, hi = fa._window_tile_range(i * block_q, (i + 1) * block_q,
                                       lk - lq, window, block_k,
                                       lk // block_k)
        assert list(np.flatnonzero(some[i])) == list(range(int(lo), int(hi)))
    # the backward's table: every live pair, K tile by K tile, and every K
    # tile at least once (one that no query sees with its first q tile,
    # wholly hidden, so that it is written as zeros)
    qt, kt, _ = fa._fa_bwd_pairs(False, mask, lq, lk, block_q, block_k)
    dead = ~some.any(axis=0)
    assert set(kt) == set(range(lk // block_k)) and (np.diff(kt) >= 0).all()
    assert len(qt) == some.sum() + dead.sum()
    assert (some[qt, kt] | dead[kt]).all()
    assert (qt[dead[kt]] == 0).all()


@pytest.mark.parametrize("lq,lk,window,block_q,block_k", WINDOW_CASES)
def test_window_attention_forward_matches_dense_mask(lq, lk, window, block_q,
                                                     block_k):
    """The interpreted kernel (at the tiles given and at those the call's
    shape gives) and the plain path against softmax under the dense mask,
    float32: 2e-6, a few units in the last place of outputs of size 1."""
    from jax.experimental.pallas import tpu as pltpu

    rs = np.random.RandomState(lq + window)
    q, k, v = (jnp.asarray(rs.randn(1, 2, n, 64).astype("f"))
               for n in (lq, lk, lk))
    mask = (fa.WINDOW, window)
    want = dense_attention(q, k, v, dense_window(lq, lk, window), 0.125)
    plain, plain_lse = fa._mha_with_lse(q, k, v, False, 0.125, mask)
    np.testing.assert_allclose(plain, want, atol=2e-6)
    if block_q is None:
        return
    with pltpu.force_tpu_interpret_mode():
        for bq, bk in ((block_q, block_k), (None, None)):
            o, lse = fa._fa_forward_pallas(q, k, v, False, 0.125, block_q=bq,
                                           block_k=bk, mask=mask)
            np.testing.assert_allclose(o, want, atol=2e-6)
            np.testing.assert_allclose(lse, plain_lse, atol=2e-6)


@pytest.mark.parametrize("lq,lk,window,block_q,block_k", WINDOW_CASES)
def test_window_attention_backward_matches_dense_mask(lq, lk, window, block_q,
                                                      block_k):
    """The scan over the live tile pairs and the interpreted backward kernel
    against autodiff through the dense mask: 3e-5, the float32 noise of
    sums over up to 512 keys in another order."""
    from jax.experimental.pallas import tpu as pltpu

    rs = np.random.RandomState(lk + window)
    q, k, v, g = (jnp.asarray(rs.randn(1, 2, n, 64).astype("f"))
                  for n in (lq, lk, lk, lq))
    mask = (fa.WINDOW, window)
    seen = dense_window(lq, lk, window)
    want = jax.jit(jax.grad(
        lambda *a: jnp.sum(dense_attention(*a, seen, 0.125) * g),
        (0, 1, 2)))(q, k, v)
    o, lse = fa._mha_with_lse(q, k, v, False, 0.125, mask)
    got = [fa._fa_backward_blockwise(q, k, v, o, lse, g, False, 0.125,
                                     block_k=block_k or lk, mask=mask,
                                     block_q=block_q or lq)]
    if block_q is not None:
        with pltpu.force_tpu_interpret_mode():
            got.append(fa._fa_backward_pallas(q, k, v, o, lse, g, False,
                                              0.125, mask))
    for grads in got:
        for a, b in zip(grads, want):
            np.testing.assert_allclose(a, b, atol=3e-5)


@pytest.mark.parametrize("path", ["blockwise", "pallas"])
def test_flash_attention_op_takes_the_window_with_gqa(monkeypatch, path):
    """``flash_attention(mask="window")`` with 4 query heads over 2
    key-value heads, forward and gradients, by each backward path (the
    kernel interpreted), against the dense form; the traced calls are
    counted by path and mask."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(fa, "_use_pallas_bwd", lambda q, k, v=None: path == "pallas")
    rs = np.random.RandomState(3)
    q, g = (jnp.asarray(rs.randn(2, 4, 256, 64).astype("f")) for _ in "qg")
    k, v = (jnp.asarray(rs.randn(2, 2, 256, 64).astype("f")) for _ in "kv")
    seen = dense_window(256, 256, 100)

    def dense(q, k, v):
        return dense_attention(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1),
                               seen, 0.125)

    telemetry.reset()
    with pltpu.force_tpu_interpret_mode():
        o, vjp = jax.vjp(lambda *a: fa.flash_attention(
            *a, mask="window", window=100), q, k, v)
        got = vjp(g)
    np.testing.assert_allclose(o, dense(q, k, v), atol=2e-6)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * g), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=3e-5)
    metrics = telemetry.snapshot()["metrics"]
    for name, labels in (
            ("mxnet_flash_attention_fwd_calls_total",
             {"path": "plain", "mask": "window"}),
            ("mxnet_flash_attention_bwd_calls_total", {"path": path})):
        assert [s["labels"] for s in metrics[name]["samples"]
                if s["value"]] == [labels]


def test_flash_attention_op_refuses_a_window_misused():
    rs = np.random.RandomState(0)
    q = nd.array(rs.randn(1, 2, 64, 16).astype("f"))
    o = nd.flash_attention(q, q, q, mask="window", window=9)
    want = dense_attention(q._get(), q._get(), q._get(),
                           dense_window(64, 64, 9), 0.25)
    np.testing.assert_allclose(o.asnumpy(), want, atol=2e-6)
    with pytest.raises(mx.MXNetError):
        nd.flash_attention(q, q, q, mask="window")            # no length
    with pytest.raises(mx.MXNetError):
        nd.flash_attention(q, q, q, mask="window", window=9, causal=True)
    with pytest.raises(mx.MXNetError):                        # lq > lk
        nd.flash_attention(q, q[:, :, :32], q[:, :, :32], mask="window",
                           window=9)


def test_kernel_names_tell_a_window_call_from_the_others():
    assert fa._kernel_name("mxnet_flash_attention_fwd", (fa.WINDOW, 8)) \
        == "mxnet_flash_attention_fwd_window"
    for mask in (None, (fa.BLOCK_DIFFUSION, 4)):
        assert fa._kernel_name("mxnet_flash_attention_bwd", mask) \
            == "mxnet_flash_attention_bwd"


# --------------------------------------------------------------------------
# the router
# --------------------------------------------------------------------------
def _loop_moe(x, router, p, top_k, bias, scale, eps, experts):
    """A token at a time, as the issue's equations read: sigmoid scores,
    the ``top_k`` largest of score + bias, gates of the scores alone."""
    x, router = np.asarray(x, np.float64), np.asarray(router, np.float64)
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    out = np.zeros_like(x)
    chosen = []
    for t, row in enumerate(x):
        s = 1.0 / (1.0 + np.exp(-(row @ router)))
        picked = np.argsort(-(s + bias), kind="stable")[:top_k]
        chosen.append(sorted(picked))
        norm = s[picked].sum() + eps
        for e in picked:
            if e in experts:
                a = row @ p["g"][e]
                hidden = a / (1.0 + np.exp(-a)) * (row @ p["u"][e])
                out[t] += scale * s[e] / norm * (hidden @ p["d"][e])
    return out, chosen


@pytest.mark.parametrize("held", [None, (4, 8)])
def test_sigmoid_routing_with_bias_renormalisation_and_scale(held):
    """Against the loop over tokens, float64: 2e-5 on outputs of size 1."""
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(80, 24).astype("f"))
    router = jnp.asarray(rs.randn(24, 16).astype("f"))
    bias = rs.rand(16).astype("f") * (rs.rand(16) < 0.5)
    p = _expert_weights(rs, 16, 24, 12)
    first, count = held or (0, 16)
    mine = {k: v[first:first + count] for k, v in p.items()}
    kw = dict(capacity_factor=None, top_k=4, renormalize=True, held=held,
              score="sigmoid", scale=2.826, renorm_eps=1e-20)
    out, aux = moe_apply(_grouped, mine, router, x,
                         select_bias=jnp.asarray(bias), **kw)
    want, chosen = _loop_moe(x, router, p, 4, bias, 2.826, 1e-20,
                             range(first, first + count))
    np.testing.assert_allclose(out, want, atol=2e-5)
    assert int(aux["routed_pairs"]) == sum(
        first <= e < first + count for row in chosen for e in row)
    # the bias moves the choice ...
    _, unbiased = _loop_moe(x, router, p, 4, 0.0 * bias, 2.826, 1e-20, ())
    assert sum(a != b for a, b in zip(chosen, unbiased)) > 10
    # ... and never a gate: the same bias on every expert changes nothing
    same, _ = moe_apply(_grouped, mine, router, x,
                        select_bias=jnp.full(16, 0.7), **kw)
    plain, _ = moe_apply(_grouped, mine, router, x, **kw)
    np.testing.assert_array_equal(same, plain)
    # and the gates are the scale's multiples: without it, 2.826 times less
    unscaled, _ = moe_apply(_grouped, mine, router, x,
                            select_bias=jnp.asarray(bias),
                            **dict(kw, scale=1.0))
    np.testing.assert_allclose(2.826 * unscaled, out, rtol=1e-6, atol=1e-6)


def test_the_switch_layer_refuses_the_dropless_router_options():
    rs = np.random.RandomState(0)
    x, router = (jnp.asarray(rs.randn(*s).astype("f"))
                 for s in ((8, 4), (4, 2)))
    for kw in ({"score": "sigmoid"}, {"scale": 2.0},
               {"select_bias": jnp.zeros(2)}):
        with pytest.raises(mx.MXNetError):
            moe_apply(lambda p, t: t, {}, router, x, capacity_factor=1.0,
                      **kw)
    with pytest.raises(mx.MXNetError):
        moe_apply(_grouped, {}, router, x, capacity_factor=None,
                  score="tanh")


# --------------------------------------------------------------------------
# the decoder by configuration, against the configuration's reference
# --------------------------------------------------------------------------
def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_block():
    """32 routed experts in 16 shares of 2, 4 a token, random routers and a
    random bias, the shared expert counted once."""
    cfg, _, _, _ = parity.small("trinity_mini")
    parity.shares_add_up(
        "trinity_mini", 32, 2, 4, 2e-5, moe_renorm_eps=1e-20,
        moe_score="sigmoid", moe_route_scale=cfg["route_scale"],
        moe_select_bias=True, moe_shared_intermediate_size=32)


@pytest.mark.parametrize("amp,tolerance", [
    # float32 against float32: the gap is the order of the sums: 0 / 8e-8 /
    # 6e-7 / 1.2e-5 measured at seed 5, 1e-5 allowed (the change's gap is of
    # differences of float32 weights a step of 1e-6 apart: 1e-3)
    (None, {"loss_gap": 1e-5, "first_gradient_gap": 1e-5,
            "first_gradient_error": 1e-5, "change_gap": 1e-3}),
    # bf16 operands: three decimal digits a product, through norms that
    # bring every sublayer's small output back to size 1, and a router
    # near-tie may pick another expert of the share for a token: 4.3e-4 /
    # 0.019 / 0.25 / 0.0046 measured at seed 5 (the error at an expert's
    # leaf of two experts and 64 tokens); a float32 result reads a
    # thousand times less
    ("bfloat16", {"loss_gap": 2e-3, "first_gradient_gap": 0.1,
                  "first_gradient_error": 0.4, "change_gap": 0.05}),
])
def test_program_matches_the_reference_loss_and_every_gradient(amp,
                                                               tolerance):
    from mxnet_tpu import profiler

    # every leaf got a gradient of its own: the gate's, the shared
    # expert's, the router's and the norms' after a sublayer too
    _, metrics = parity.matches("trinity_mini", amp, tolerance)
    # the assumed routers send this share exactly one pair a token a sparse
    # layer: 2 steps x 4 layers x 64 tokens, whatever the seed
    pairs = metrics["mxnet_moe_routed_pairs_total"]["samples"][0]["value"]
    assert pairs == 2 * 4 * 64
    # 64 tokens choose 8: one part of 512 sorted rows a layer, and it holds
    # pairs
    for name in ("mxnet_moe_live_parts_total", "mxnet_moe_parts_total"):
        assert metrics[name]["samples"][0]["value"] == 2 * 4
    # the forward's calls by mask (the trace's and the checkpoints' of four
    # window layers and one full), and the shared expert's scope in the
    # step's table
    calls = {s["labels"]["mask"]: s["value"] for s in metrics[
        "mxnet_flash_attention_fwd_calls_total"]["samples"]}
    assert set(calls) == {"window", "causal"}
    assert calls["window"] == 4 * calls["causal"]
    table = [t for name, t in profiler.op_scopes().items()
             if name.startswith("train_step:")][-1]
    assert any(profiler.SCOPE_MOE_SHARED in row["scope"]
               for row in table.values())


LEFT_OUT = {
    "window": dict(broken={"sliding_window": 32}),  # every key: a full layer
    "gate": dict(mistaken=parity.zeroed("attn.z")),  # sigmoid(z) is a half
    "shared": dict(mistaken=parity.zeroed("shared.down")),  # adds nothing
    # the third share of four, which the bias favours and the order of the
    # router's tied columns does not
    "bias": dict(
        cfg={"experts_first": 4,
             "assumed": {"expert_bias": {"value": 1.0, "shares": [2, 3]}}},
        broken={"assumed": {"expert_bias": {"value": 0.0, "shares": []}}}),
    "scale": dict(broken={"route_scale": 1.0}),
}


@pytest.mark.parametrize("left_out", list(LEFT_OUT))
def test_the_parity_test_sees_each_part_left_out(left_out):
    """The reference with one part of the layer left out of the *program's*
    configuration no longer agrees: the float32 comparison above would fail
    by ``first_gradient_error`` or ``loss_gap``, a hundred times over its
    tolerance.  One sparse window layer holds every part."""
    stats = parity.left_out("trinity_mini", **LEFT_OUT[left_out])
    assert max(stats["first_gradient_error"][0], stats["loss_gap"][0]) > 1e-3


def test_counts_of_the_configuration():
    cfg, counts = parity.published("trinity_mini")
    for length, window in ((96, 40), (64, 64), (16, 64)):
        assert counts.window_pairs(length, window) \
            == dense_window(length, length, window).sum()
        assert counts.causal_pairs(length) \
            == dense_window(length, length, length).sum()
    # the issue's numbers: the band holds 14,681,088 of the causal
    # 33,558,528 pairs at L = 8192, W = 2048; 18.1 TFLOP a step
    assert counts.window_pairs(8192, 2048) == 14_681_088
    assert counts.causal_pairs(8192) == 33_558_528
    assert counts.sparse_layers(cfg) == 4
    assert counts.pairs_per_token(cfg) == 1.0
    assert counts.train_flops_per_sample(cfg, 8192) \
        == pytest.approx(18.1e12, rel=5e-3)


# --------------------------------------------------------------------------
# the decoder module
# --------------------------------------------------------------------------
DENSE_LAYER = ["input_layernorm_weight", "self_attn_q_proj_weight",
               "self_attn_k_proj_weight", "self_attn_v_proj_weight",
               "self_attn_o_proj_weight", "post_attention_layernorm_weight",
               "mlp_gate_proj_weight", "mlp_up_proj_weight",
               "mlp_down_proj_weight"]
SPARSE_LAYER = DENSE_LAYER[:4] + [
    "self_attn_o_proj_weight", "self_attn_q_norm_weight",
    "self_attn_k_norm_weight", "post_attention_layernorm_weight",
    "mlp_router_weight", "mlp_gate_proj_weight", "mlp_up_proj_weight",
    "mlp_down_proj_weight"]


@pytest.mark.parametrize("overrides,layer", [
    ({}, DENSE_LAYER),
    # the block-diffusion cell's kind of net: every layer sparse, no shared
    # expert, no bias, no gate, no norm after
    ({"num_experts": 8, "moe_capacity_factor": None, "moe_top_k": 2,
      "moe_renormalize": True, "moe_experts_held": (4, 4), "qk_norm": True,
      "moe_intermediate_size": 32}, SPARSE_LAYER),
])
def test_default_config_builds_todays_parameters_name_for_name(overrides,
                                                               layer):
    net = llama.llama_tiny(**overrides)
    prefix = net.prefix
    names = [n[len(prefix):] for n in net.collect_params()]
    want = ["model_embed_tokens_weight"] + [
        f"model_layers_{i}_{name}" for i in range(2) for name in layer] + [
        "model_norm_weight", "lm_head_weight"]
    assert names == want
    cfg = net.config
    assert cfg.attention_types == ("full", "full") and cfg.layers_alike()
    assert all(p.grad_req == "write" for p in net.collect_params().values())


def test_layers_of_kinds_and_what_refuses_them():
    cfg = llama.LlamaConfig(
        vocab_size=64, hidden_size=32, num_layers=3, num_heads=2,
        num_kv_heads=1, intermediate_size=48, num_experts=4,
        moe_capacity_factor=None, moe_top_k=2, num_dense_layers=1,
        attention_types=("window", "full", "window"), attention_window=4,
        rope_attention_types=("window",), moe_shared_intermediate_size=16,
        moe_intermediate_size=16, moe_select_bias=True, attention_gate=True,
        post_norms=True, embed_scale=2.0)
    net = llama.LlamaForCausalLM(cfg)
    layers = net.model.layers
    assert [type(layer.mlp).__name__ for layer in layers] \
        == ["LlamaMLP", "LlamaMoEMLP", "LlamaMoEMLP"]
    assert [layer.self_attn._kind for layer in layers] \
        == ["window", "full", "window"]
    bias = [p for n, p in net.collect_params().items()
            if n.endswith("select_bias")]
    assert len(bias) == 2 and all(p.grad_req == "null" and p.shape == (4,)
                                  for p in bias)
    assert not cfg.layers_alike()
    with pytest.raises(mx.MXNetError, match="several kinds"):
        net.pipeline_decompose(1)
    with pytest.raises(mx.MXNetError):
        llama.prefill_apply({}, cfg, np.zeros((1, 4), "int32"))
    # the serving forwards name what they lack, experts apart
    plain = llama.LlamaConfig(
        vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
        num_kv_heads=1, attention_types=("window",), attention_window=4)
    for apply in (lambda: llama.prefill_apply({}, plain, None),
                  lambda: llama.decode_apply({}, plain, None, None, None)):
        with pytest.raises(mx.MXNetError, match="window layers"):
            apply()
    # a window layer needs its length and the causal layout
    for bad in ({"attention_window": 0}, {"block_diffusion": 4},
                {"attention_types": ("window", "full")}):
        with pytest.raises(mx.MXNetError):
            llama.LlamaConfig(**{"num_layers": 1, "attention_window": 4,
                                 "attention_types": ("window",), **bad})
