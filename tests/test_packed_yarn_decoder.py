"""What ISSUE 32 added for a decoder trained on packed documents with RoPE by
layer kind (segment ids in the attention op are
``test_segment_attention.py``'s): YaRN's inverse frequencies and magnitude
against values computed by hand, and ``rope``'s default against the formula
it always had; a packed row against its documents run alone; the shares of an
expert-parallel deployment against the uncut layer; ``TrainStep`` with a
tuple as ``x``; and a small net of the same shape of layer through
``TrainStep`` against the configuration's plain reference.  All on the CPU,
seeded random weights."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon.model_zoo.language import llama
from mxnet_tpu.ops import attention_ops
from mxnet_tpu.parallel.data_parallel import TrainStep

import decoder_parity as parity

# the published rope_parameters of the full layers
YARN = dict(head_dim=128, base=500000.0, factor=16,
            original_max_position=8192, beta_fast=32, beta_slow=1,
            attention_factor=1.2772588722239782)


# --------------------------------------------------------------------------
# RoPE by kind
# --------------------------------------------------------------------------
def test_yarn_against_values_computed_by_hand():
    """``dim_of(r) = 128 ln(8192 / (2 pi r)) / (2 ln 500000)``: 18.08 for 32
    rotations and 34.98 for one, so the ramp runs over dimensions 18..35.
    Below it the frequencies are the base's own, above it a sixteenth of
    them, inside it the blend: dimension 20 has ramp 2 / 17."""
    inv_freq, magnitude = attention_ops.yarn_rope_parameters(**YARN)
    assert len(inv_freq) == 64 and magnitude == 1.2772588722239782
    assert magnitude == pytest.approx(0.1 * math.log(16) + 1)
    # the same from the factor alone, where the config gives none
    assert attention_ops.yarn_rope_parameters(
        **dict(YARN, attention_factor=None))[1] == pytest.approx(magnitude)
    assert 128 * math.log(8192 / (64 * math.pi)) / (2 * math.log(5e5)) \
        == pytest.approx(18.0811, abs=1e-3)
    assert 128 * math.log(8192 / (2 * math.pi)) / (2 * math.log(5e5)) \
        == pytest.approx(34.9841, abs=1e-3)
    own = [500000.0 ** (-2 * n / 128) for n in range(64)]
    assert inv_freq[0] == 1.0
    assert inv_freq[18] == pytest.approx(own[18], rel=1e-12)        # low
    assert inv_freq[18] == pytest.approx(0.0249554087, rel=1e-8)
    assert inv_freq[20] == pytest.approx(
        own[20] * (15 / 17 + 2 / 17 / 16), rel=1e-12)
    assert inv_freq[20] == pytest.approx(0.0147339210, rel=1e-8)
    assert inv_freq[35] == pytest.approx(own[35] / 16, rel=1e-12)   # high
    assert inv_freq[63] == pytest.approx(1.5344629945e-07, rel=1e-8)
    # not truncated, the ramp's ends are the fractions themselves
    loose, _ = attention_ops.yarn_rope_parameters(**YARN, truncate=False)
    assert loose[18] == own[18] and loose[35] == inv_freq[35]
    assert loose[19] == pytest.approx(own[19] * (1 - 15 / 16 * (
        19 - 18.081135) / (34.984119 - 18.081135)), rel=1e-7)
    assert loose[19] > inv_freq[19]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("positions", [None, "row", "batch"])
def test_rope_default_is_the_formula_it_always_had(positions, dtype):
    """Bit for bit: the inverse frequencies ``base ** (-n / (d / 2))`` in
    float32, cos and sin cast to the input's dtype, no magnitude."""
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(2, 3, 10, 16).astype("f")).astype(dtype)
    pos = {None: None, "row": jnp.arange(10) * 3,
           "batch": jnp.asarray(rs.randint(0, 999, (2, 10)))}[positions]

    def parent(x, positions, base, scale=1.0):
        d = x.shape[-1]
        positions = jnp.arange(x.shape[2]) if positions is None \
            else positions
        freqs = base ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
        angles = (jnp.asarray(positions) * scale)[..., None] * freqs
        angles = angles[None, None] if angles.ndim == 2 else angles[:, None]
        cos = jnp.cos(angles).astype(x.dtype)
        sin = jnp.sin(angles).astype(x.dtype)
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    for base, scale in ((10000.0, 1.0), (500000.0, 0.25)):
        got = attention_ops.rope(x, pos, base=base, scale=scale)
        want = parent(x, pos, base, scale)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got.astype("float32")),
                                      np.asarray(want.astype("float32")))


def test_rope_takes_inverse_frequencies_and_a_magnitude():
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(1, 2, 6, 8).astype("f"))
    pos = jnp.asarray([[0, 1, 2, 0, 1, 0]])
    freqs = (1.0, 0.3, 0.05, 0.001)
    got = np.asarray(attention_ops.rope(x, pos, inv_freq=freqs,
                                        magnitude=1.25))
    angles = np.asarray(pos)[0][:, None] * np.asarray(freqs)
    cos, sin = 1.25 * np.cos(angles), 1.25 * np.sin(angles)
    x1, x2 = np.asarray(x)[..., :4], np.asarray(x)[..., 4:]
    np.testing.assert_allclose(got, np.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1), atol=1e-6)
    # through the op table too, the frequencies a static attribute
    same = nd.rope(nd.array(x), nd.array(pos, dtype="int32"), inv_freq=freqs,
                   magnitude=1.25).asnumpy()
    np.testing.assert_allclose(same, got, atol=1e-6)
    with pytest.raises(mx.MXNetError, match="inv_freq"):
        attention_ops.rope(x, pos, inv_freq=freqs[:3])


def test_config_gives_each_kind_its_rope():
    given = {"full": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                      "original_max_position_embeddings": 8192,
                      "beta_fast": 32, "beta_slow": 1,
                      "attention_factor": 1.2772588722239782},
             "window": {"rope_type": "default", "rope_theta": 250000}}
    cfg = llama.LlamaConfig(hidden_size=512, num_heads=4, num_kv_heads=2,
                            head_dim=128, rope_base=10000.0,
                            rope_parameters=given)
    assert cfg.rope_kwargs("window") == {"base": 250000.0}
    full = cfg.rope_kwargs("full")
    want = attention_ops.yarn_rope_parameters(**YARN)
    assert (full["inv_freq"], full["magnitude"]) == want
    # a kind the mapping leaves out, and every kind without a mapping
    assert llama.LlamaConfig(rope_base=123.0, rope_parameters={
        "full": given["full"]}).rope_kwargs("window") == {"base": 123.0}
    assert llama.LlamaConfig(rope_base=123.0).rope_kwargs("full") \
        == {"base": 123.0}
    for bad in ({"sliding": {}}, {"full": {"rope_type": "llama3"}}):
        with pytest.raises(mx.MXNetError, match="rope_parameters"):
            llama.LlamaConfig(rope_parameters=bad)


# --------------------------------------------------------------------------
# the decoder by configuration
# --------------------------------------------------------------------------
SPEC = parity.spec("mellum2_12b_a2p5b")


def _net_with(cfg, build, weights):
    net = build.build_net(cfg, mx.current_context())
    params = net.collect_params()
    for leaf, name in build.leaf_names(cfg, net).items():
        params[name].set_data(nd.array(np.asarray(weights[leaf])))
    return net


def test_the_references_yarn_is_the_programs():
    """Two hands, one function: the reference's float32 blend against the
    program's float64 one, to float32's last places; the ramp's ends."""
    cfg, build, reference, _ = parity.small("mellum2_12b_a2p5b")
    real, _ = parity.published("mellum2_12b_a2p5b")
    assert reference.yarn_range(
        real["rope_parameters"]["full_attention"], 128) == (18, 35)
    inv_freq, magnitude = reference.rope_of(real, "full_attention")
    want = attention_ops.yarn_rope_parameters(**YARN)
    np.testing.assert_allclose(inv_freq, want[0], rtol=3e-6)
    assert magnitude == want[1]
    plain, one = reference.rope_of(real, "sliding_attention")
    np.testing.assert_allclose(
        plain, [500000.0 ** (-2 * n / 128) for n in range(64)], rtol=3e-6)
    assert one == 1.0
    # the toy's ramp lies inside its 8 pairs, so every part of it is run
    low, high = reference.yarn_range(
        cfg["rope_parameters"]["full_attention"], 16)
    assert (low, high) == (2, 6)


@pytest.mark.parametrize("kinds", [["sliding_attention"] * 3
                                   + ["full_attention"],
                                   ["full_attention"] * 4,
                                   ["sliding_attention"] * 4])
def test_a_packed_row_equals_its_documents_run_alone(kinds):
    """Logits of each document of a packed row against the same document by
    itself, through the net as the benchmark builds it: mask, positions and
    RoPE by kind tied together (a document that saw its neighbour, or kept
    the row's positions, would differ by the size of a logit).  2e-5:
    float32 sums in another order through four layers."""
    cfg, build, reference, _ = parity.small("mellum2_12b_a2p5b",
                                            layer_types=kinds)
    net = _net_with(cfg, build, reference.init_params(cfg, 11))
    rng = np.random.default_rng(11)
    (ids, seg), _ = build.make_batch(cfg, SPEC, rng)
    packed = net(nd.array(ids, dtype="int32"),
                 nd.array(seg, dtype="int32")).asnumpy()
    assert np.abs(packed).max() > 0.01
    for row in range(ids.shape[0]):
        starts = np.r_[0, np.flatnonzero(np.diff(seg[row])) + 1, 32]
        assert len(starts) == 6
        for a, b in zip(starts[:-1], starts[1:]):
            alone = net(nd.array(ids[row:row + 1, a:b], dtype="int32"))
            np.testing.assert_allclose(packed[row, a:b], alone.asnumpy()[0],
                                       atol=2e-5)
    # one document a row is the net without ids
    whole = net(nd.array(ids, dtype="int32"),
                nd.array(np.zeros_like(seg), dtype="int32")).asnumpy()
    np.testing.assert_allclose(whole, net(nd.array(ids, dtype="int32"))
                               .asnumpy(), atol=2e-5)
    assert np.abs(whole - packed).max() > 1e-3


def test_the_shares_parts_add_up_to_the_uncut_layer():
    """32 softmax-routed experts in 8 shares of 4, 8 a token renormalised,
    random routers."""
    parity.shares_add_up("mellum2_12b_a2p5b", 32, 4, 8, 2e-5)


@pytest.mark.parametrize("amp,tolerance", [
    # float32 against float32: the gap is the order of the sums: 1e-7 /
    # 1.3e-7 / 5.3e-7 / 2e-5 measured at seed 5, 1e-5 allowed (the change's
    # gap is of differences of float32 weights a step of 1e-6 apart: 1e-3)
    (None, {"loss_gap": 1e-5, "first_gradient_gap": 1e-5,
            "first_gradient_error": 1e-5, "change_gap": 1e-3}),
    # bf16 operands: three decimal digits a product, and a router near-tie
    # may pick another expert of the share for a token; a float32 result
    # reads a thousand times less
    ("bfloat16", {"loss_gap": 2e-3, "first_gradient_gap": 0.1,
                  "first_gradient_error": 0.4, "change_gap": 0.05}),
])
def test_program_matches_the_reference_loss_and_every_gradient(amp,
                                                               tolerance):
    """The toy net through ``TrainStep`` (a tuple as ``x``, the prefetcher's
    staging, the step's first gradient and one Adam update) against the
    configuration's plain reference and the harness's plain Adam."""
    runner, metrics = parity.matches("mellum2_12b_a2p5b", amp, tolerance)
    assert runner.compiles() == 1       # two orders of documents, one program
    cfg, build, _, _ = parity.small("mellum2_12b_a2p5b")
    # the assumed routers send this share exactly one pair a token a layer
    pairs = metrics["mxnet_moe_routed_pairs_total"]["samples"][0]["value"]
    assert pairs == 2 * 4 * 64
    # the pairs the masks show inside the documents, two samples, two steps
    shown = 3 * build.counts.visible_pairs(
        cfg, SPEC["documents"], "sliding_attention") \
        + build.counts.visible_pairs(cfg, SPEC["documents"], "full_attention")
    visible, walked = (metrics[name]["samples"][0]["value"] for name in (
        "mxnet_attention_visible_pairs_total",
        "mxnet_attention_walked_pairs_total"))
    assert (visible, walked) == (2 * 2 * shown, 2 * 2 * 4 * 32 * 32)
    calls = {s["labels"]["mask"]: s["value"] for s in metrics[
        "mxnet_flash_attention_fwd_calls_total"]["samples"]}
    assert set(calls) == {"window_segments", "causal_segments"}
    assert calls["window_segments"] == 3 * calls["causal_segments"]


def _step_without_ids(runner, batch, span):
    (ids, _), labels = batch
    return runner._step(ids, labels)


LEFT_OUT = {
    "segments": dict(step=_step_without_ids),
    "yarn": dict(broken={"rope_parameters": {"full_attention": {
        "rope_type": "default", "rope_theta": parity.YARN["rope_theta"]}}}),
    "magnitude": dict(broken={"rope_parameters": {"full_attention": dict(
        parity.YARN, attention_factor=1.0)}}),
    "window": dict(broken={"sliding_window": 32}),
}


@pytest.mark.parametrize("left_out", list(LEFT_OUT))
def test_the_parity_test_sees_each_part_left_out(left_out):
    """The reference against a program with one part of the issue left out
    no longer agrees: the float32 comparison above would fail by
    ``first_gradient_error`` or ``loss_gap``, a hundred times over its
    tolerance; a window layer and a full one hold every part.  (Positions
    that run on through the row are not such a part: RoPE's products depend
    on the difference of two positions of one document alone, so restarting
    them moves the rounding and nothing else.)"""
    stats = parity.left_out("mellum2_12b_a2p5b", **LEFT_OUT[left_out])
    assert max(stats["first_gradient_error"][0], stats["loss_gap"][0]) > 1e-3


# --------------------------------------------------------------------------
# TrainStep with several arrays as x
# --------------------------------------------------------------------------
def _next_token(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0].mean(-1)


def test_train_step_takes_a_tuple_as_x_and_compiles_once_a_signature():
    from mxnet_tpu.gluon.data.prefetcher import PrefetchIterator

    cfg, build, reference, _ = parity.small("mellum2_12b_a2p5b")
    net = _net_with(cfg, build, reference.init_params(cfg, 3))
    step = TrainStep(net, _next_token, optimizer="adam",
                     optimizer_params={"learning_rate": 1e-3})
    rng = np.random.default_rng(3)
    batches = [build.make_batch(cfg, SPEC, rng) for _ in range(4)]
    losses = [float(step(x, y)) for x, y in batches[:2]]
    assert len(step._seen_sigs) == 1 and len(step._compiled) == 1
    # a list is a tuple; NDArrays are arrays; the prefetcher's staging (it
    # keeps the nesting and wraps the leaves) passes through
    ids, seg = batches[2][0]
    losses.append(float(step([nd.array(ids, dtype="int32"), seg],
                             batches[2][1])))
    feed = PrefetchIterator(iter(batches[3:]))
    try:
        x, y = next(feed)
        assert isinstance(x, tuple) and len(x) == 2
        losses.append(float(step(x, y)))
    finally:
        feed.close()
    assert len(step._seen_sigs) == 1 and len(step._compiled) == 1
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    # another length is another signature
    short = dict(SPEC, seq=16, documents=[9, 4, 3])
    x, y = build.make_batch(cfg, short, rng)
    step(x, y)
    assert len(step._seen_sigs) == 2
    (sig,) = [s for s in step._seen_sigs if s[0] == (2, 16)]
    assert sig == ((2, 16), "int32", (2, 16), "int32", (2, 16), "int32")


def test_train_step_with_one_array_is_as_before():
    """One array as ``x``: the signature it always had, the same loss as the
    same ids in a tuple of one document a row."""
    cfg, build, reference, _ = parity.small("mellum2_12b_a2p5b")
    rng = np.random.default_rng(4)
    (ids, seg), labels = build.make_batch(cfg, SPEC, rng)
    got = []
    for x in (ids, (ids, np.zeros_like(seg))):
        net = _net_with(cfg, build, reference.init_params(cfg, 4))
        step = TrainStep(net, _next_token, optimizer="adam",
                         optimizer_params={"learning_rate": 1e-3})
        got.append([float(step(x, labels)) for _ in range(2)])
        if x is ids:
            assert step._seen_sigs == {((2, 32), "int32", (2, 32), "int32")}
    np.testing.assert_allclose(got[0], got[1], rtol=1e-5)


# --------------------------------------------------------------------------
# what stays as it was, and what refuses
# --------------------------------------------------------------------------
def test_rope_by_kind_adds_no_parameter_and_changes_no_name():
    plain = llama.llama_tiny(qk_norm=True)
    by_kind = llama.llama_tiny(qk_norm=True, rope_parameters={
        "full": {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                 "original_max_position_embeddings": 64}})
    strip = lambda net: [(n[len(net.prefix):], p.shape)
                         for n, p in net.collect_params().items()]
    assert strip(plain) == strip(by_kind)
    assert plain.config.rope_parameters == {}
    # and a default net's forward is the one it was: no ids, base alone
    ids = nd.array(np.arange(12).reshape(1, 12) % 7, dtype="int32")
    plain.initialize()
    jaxpr = str(jax.make_jaxpr(lambda v: plain(
        nd.NDArray._from_jax(v, None))._get())(ids._get()))
    assert "cummax" not in jaxpr and "pow" in jaxpr


def test_serving_and_the_pipeline_refuse_the_new_kinds_by_name():
    cfg = llama.LlamaConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        num_kv_heads=1, rope_parameters={"full": {
            "rope_type": "yarn", "rope_theta": 10000, "factor": 4,
            "original_max_position_embeddings": 64}})
    for apply in (lambda: llama.prefill_apply({}, cfg, None),
                  lambda: llama.decode_apply({}, cfg, None, None, None)):
        with pytest.raises(mx.MXNetError, match="rope_parameters"):
            apply()
    with pytest.raises(mx.MXNetError, match="rope_parameters"):
        llama._refuse_unserved(llama.LlamaConfig(rope_parameters={
            "window": {"rope_theta": 5.0}}))
    # block diffusion takes neither ids (the op refuses them by name) nor
    # RoPE by kind (the configuration does)
    with pytest.raises(mx.MXNetError, match="block-diffusion layout"):
        llama.LlamaConfig(block_diffusion=4, rope_parameters={
            "full": {"rope_theta": 5.0}})
    net = llama.llama_tiny(block_diffusion=4)
    net.initialize()
    ids = nd.array(np.zeros((1, 16)), dtype="int32")
    with pytest.raises(mx.MXNetError, match="segment_ids"):
        net(ids, ids)
    # a pipelined step streams one array
    step = TrainStep.__new__(TrainStep)
    step._pipeline = {"axis": "pp"}
    with pytest.raises(mx.MXNetError, match="one array"):
        step((ids, ids), ids)
