"""What ISSUE 38 added for a decoder of Kimi-delta-attention and
latent-attention layers over group-limited sigmoid-routed experts, with a
share of the heads: a small net of the same shape through ``TrainStep``
against the configuration's plain reference, with each part left out in
turn.  The ops and the layers over them are ``test_kda_mla_layers.py``'s (one
file until PR 41, which split it by subject for tier-1's ``--dist
loadfile``).  All on the CPU, seeded random weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.gluon.model_zoo.language import llama

import decoder_parity as parity


@pytest.fixture(autouse=True)
def chunks_of_16(monkeypatch):
    """The small nets' rows are tens of tokens long: the decoder's delta
    rule goes in chunks of 16 rows here (``F.kda`` itself is tested at its
    own chunk sizes)."""
    monkeypatch.setattr(llama, "KDA_CHUNK", 16)


# --------------------------------------------------------------------------
# the configuration at a small size (``decoder_parity.ROWS``)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("amp,tolerance", [
    # float32 against float32: the order of the sums (1e-7 / 4e-6 / 5e-6 /
    # 1.4e-4 measured over seeds 1-3; the change's gap is of differences of
    # float32 weights a step of 1e-6 apart)
    (None, {"loss_gap": 1e-5, "first_gradient_gap": 5e-5,
            "first_gradient_error": 5e-5, "change_gap": 1e-3}),
    # bf16 operands: three decimal digits a product through norms that
    # bring a mixer's small output back to size 1 (the delta rule's output
    # norm, the latent's), and a router near-tie may pick another expert
    ("bfloat16", {"loss_gap": 2e-3, "first_gradient_gap": 0.15,
                  "first_gradient_error": 0.5, "change_gap": 0.1}),
])
def test_program_matches_the_reference_loss_and_every_gradient(amp,
                                                               tolerance):
    from mxnet_tpu import profiler

    _, metrics = parity.matches("ling3_flash_vl", amp, tolerance)
    # the assumed routers (independent columns) send this share half a pair
    # a token a sparse layer in the mean: 2 steps x 6 layers x 128 tokens x
    # 4 held of 32 x 4 a token; every sparse layer chose inside its groups,
    # and the six delta-rule layers walk 4 chunks each (counted once a
    # trace: a checkpoint traces its layer once)
    value = lambda name: metrics[name]["samples"][0]["value"]
    assert value("mxnet_moe_routed_pairs_total") == pytest.approx(
        2 * 6 * 128 * 0.5, rel=0.25)
    assert value("mxnet_moe_group_limited_calls_total") == 6
    assert value("mxnet_kda_chunks_total") == 6 * 4
    assert value("mxnet_kda_calls_total") == 6
    table = [t for name, t in profiler.op_scopes().items()
             if name.startswith("train_step:")][-1]
    parts = {row["part"] for row in table.values()}
    assert {profiler.SCOPE_KDA, profiler.SCOPE_MIXER_GATE} <= parts
    scopes = " ".join(row["scope"] for row in table.values())
    assert profiler.KERNEL_KDA_FWD in scopes \
        and profiler.KERNEL_KDA_BWD in scopes


def _random_routers(weights):
    k = jax.random.PRNGKey(0)
    return {name: 0.5 * jax.random.normal(k, v.shape)
            if name.endswith("moe.router") else v
            for name, v in weights.items()}


LEFT_OUT = {
    # the delta rule's two terms: planted in the reference by its ``drop``
    "decay": ("decay",), "delta": ("delta",),
    # the fourth share, in the group 1 that the bias favours: without the
    # groups the choice is by the bias alone and by tied columns
    "groups": dict(
        cfg={"experts_first": 12, "assumed": {"expert_bias": {
            "value": 0.25, "shares": [0, 1, 2, 3]}}},
        broken={"n_group": 1, "topk_group": 1}, weights=_random_routers),
    # the last tap alone: no convolution
    "conv": dict(mistaken=lambda weights: {
        name: v.at[:3].set(0.0) if name.endswith("_conv") else v
        for name, v in weights.items()}),
    # sigmoid is a half for every head
    "head_gate": dict(mistaken=parity.zeroed("mla.gate")),
    "interleave": dict(broken={"rope_interleave": False}),
}


@pytest.mark.parametrize("left_out", list(LEFT_OUT))
def test_the_parity_test_sees_each_part_left_out(left_out):
    """One mechanism left out of the program's side (or, for the delta
    rule's two terms, planted in the reference by its ``drop``) no longer
    agrees: the float32 comparison above would fail by
    ``first_gradient_error`` or ``loss_gap``, over ten times its
    tolerance.  A delta layer and a latent one, both sparse, hold every
    part."""
    case = LEFT_OUT[left_out]
    if isinstance(case, dict):
        stats = parity.left_out("ling3_flash_vl", **case)
        assert max(stats["first_gradient_error"][0],
                   stats["loss_gap"][0]) > 5e-4
        return
    from chipbench.harness.precision import ops as make_ops

    # the seed draws ``dt_bias`` in (-6.9, -2.5), where one layer's decay over
    # 64 rows is all but 1: two more, and leaving either term out moves the
    # loss by 1.4e-3
    cfg, _, reference, _ = parity.small("ling3_flash_vl", few=True)
    weights = {name: v + 2.0 if name.endswith("kda.dt_bias") else v
               for name, v in reference.init_params(cfg, parity.SEED).items()}
    got, _ = parity.program("ling3_flash_vl", cfg, weights, 1)
    ids, labels = (jnp.asarray(x) for x in parity.pool("ling3_flash_vl")[0])
    with jax.default_matmul_precision("highest"):
        loss = np.mean([float(reference.loss_fn(
            cfg, make_ops("float32"), weights, ids[i], labels[i], case))
            for i in range(2)])
    assert abs(got["losses"][0] - loss) / loss > 1e-4


def test_counts_of_the_configuration():
    cfg, counts = parity.published("ling3_flash_vl")
    reference = parity.small("ling3_flash_vl")[2]
    assert counts.layer_kinds(cfg) == ["kda"] * 5 + ["mla", "kda"]
    assert reference.layer_kinds(cfg) == [
        (i == 5, i >= 1) for i in range(7)]
    assert counts.sparse_layers(cfg) == 6
    # the even load: 8 a token over 512 outputs, 8 of them here
    assert counts.pairs_per_token(cfg) == 0.125
    assert 8192 * counts.pairs_per_token(cfg) == 1024
    n = sum(int(np.prod(shape)) for shape, _ in
            reference.param_shapes(cfg).values())
    # the issue's reckoning: 577.9M parameters
    assert n == pytest.approx(577.9e6, rel=1e-3)
    # a chunk a head by brute force: pairs j <= i, the solve's
    # multiply-adds, the four products with the state
    c, k = 64, 128
    pairs = sum(1 for i in range(c) for j in range(i + 1))
    solve = sum(2 * i for i in range(c)) * 2 * k
    chunk = 2 * 2 * k * pairs + solve + 3 * 2 * c * k * k + 2 * k * pairs
    assert counts.kda_fwd_flops(cfg, 8192) == 8 * 128 * chunk
    assert counts.mla_attention_fwd_flops(cfg, 8192) \
        == 2 * 8 * counts.causal_pairs(8192) * (192 + 128)
    assert counts.mla_attention_fwd_bytes(cfg, 8192, 2) \
        == 8 * 8192 * (2 * 320 * 2 + 4)
