"""The ops ISSUE 38 added for a decoder of Kimi-delta-attention and
latent-attention layers over group-limited sigmoid-routed experts, and the
layers over them: the chunked gated delta rule (``F.kda``) against the
row-by-row recurrence, outputs and every gradient, at the decay's bound too;
the short convolution against a loop; attention at q/k 192 beside v 128, the
plain path, the scan and both kernels under the TPU interpreter; RoPE over
pairs of neighbours; the group-limited choice against a loop over tokens; the
shares of heads and of experts against the uncut layers; export; and what
refuses what.  The whole step against the configuration's reference is
``test_kda_mla_decoder.py``'s (one file until PR 41, which split it by
subject for tier-1's ``--dist loadfile``).  All on the CPU, seeded random
weights."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, telemetry
from mxnet_tpu.gluon.model_zoo.language import llama
from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.ops import kda
from mxnet_tpu.parallel import expert_parallel

import decoder_parity as parity


@pytest.fixture(autouse=True)
def chunks_of_16(monkeypatch):
    """The small layers' rows are tens of tokens long: the decoder's delta
    rule goes in chunks of 16 rows here (``F.kda`` itself is tested at its
    own chunk sizes)."""
    monkeypatch.setattr(llama, "KDA_CHUNK", 16)


# --------------------------------------------------------------------------
# the delta rule
# --------------------------------------------------------------------------
def _kda_inputs(seed, rows, dtype, at_bound=False, heads=2, kd=32, vd=16):
    """q (scaled) and k l2-normed as the mixer hands them, a log-decay in
    (-5, 0) (with ``at_bound`` all but -5 in every channel of every row), a
    beta in (0, 1)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (2, heads, rows, kd))) / math.sqrt(kd)
    k = unit(jax.random.normal(keys[1], (2, heads, rows, kd)))
    v = jax.random.normal(keys[2], (2, heads, rows, vd))
    g = -5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(
        keys[3], (2, heads, rows, kd)) + (20.0 if at_bound else -1.0))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (2, heads, rows)))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g,
            beta.astype(dtype))


def _rel(a, b):
    a, b = (np.asarray(x, np.float64) for x in (a, b))
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


def _kda_paths():
    """``{path: calls}`` of ``mxnet_kda_calls_total``, the paths taken."""
    samples = telemetry.snapshot()["metrics"]["mxnet_kda_calls_total"][
        "samples"]
    return {s["labels"]["path"]: s["value"] for s in samples if s["value"]}


# float32: the order of float32 sums (measured 3e-6 at most, the decay's own
# gradient at the bound, where it is 1e-3 of the others, 1e-4); bf16
# operands: three decimal digits a product (measured 4e-3 to 2e-2)
KDA_TOLERANCE = {"float32": 2e-5, "bfloat16": 5e-2}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("chunks", [1, 3, 5])
def test_kda_chunked_is_the_recurrence_outputs_and_gradients(chunks, dtype):
    x = _kda_inputs(chunks, 64 * chunks, dtype)
    cot = jax.random.normal(jax.random.PRNGKey(9), x[2].shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * cot)

    # (jitted: eagerly each of the four traces, compiles and runs op by op,
    # three quarters of a case's seconds)
    got, want = jax.jit(kda.kda)(*x), jax.jit(kda.kda_recurrent)(*x)
    assert got.dtype == x[2].dtype and got.shape == x[2].shape
    assert _rel(got, want) < KDA_TOLERANCE[dtype]
    grads = jax.jit(jax.grad(loss(kda.kda), argnums=(0, 1, 2, 3, 4)))(*x)
    wants = jax.jit(jax.grad(loss(kda.kda_recurrent),
                             argnums=(0, 1, 2, 3, 4)))(*x)
    for name, a, b, like in zip("q k v g beta".split(), grads, wants, x):
        assert a.dtype == like.dtype and a.shape == like.shape, name
        assert _rel(a, b) < KDA_TOLERANCE[dtype], name


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kda_at_the_decays_bound_for_whole_chunks(dtype):
    """Every channel of every row at -5 but for a hair: ``exp(-b)`` over a
    chunk would be ``e^320``; the sub-blocks keep every factor inside
    float32, and the result is the recurrence's."""
    x = _kda_inputs(7, 192, dtype, at_bound=True)
    assert float(x[3].max()) < -4.99
    got, want = jax.jit(kda.kda)(*x), jax.jit(kda.kda_recurrent)(*x)
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    assert _rel(got, want) < KDA_TOLERANCE[dtype]
    cot = jax.random.normal(jax.random.PRNGKey(9), x[2].shape)
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(kda.kda(*a).astype(jnp.float32) * cot),
        argnums=(0, 1, 2, 3, 4)))(*x)
    wants = jax.jit(jax.grad(lambda *a: jnp.sum(kda.kda_recurrent(*a) * cot),
                             argnums=(0, 1, 2, 3, 4)))(*x)
    floor = 0.05 * float(jnp.linalg.norm(wants[1]))
    for name, a, b in zip("q k v g beta".split(), grads, wants):
        assert bool(jnp.isfinite(a.astype(jnp.float32)).all()), name
        # the decay's own gradient is a thousandth of the others' here, the
        # difference of terms that all but cancel: held against a twentieth
        # of k's, under which a bf16 product's rounding is what is left
        error = np.linalg.norm(np.asarray(a, np.float64) - np.asarray(b))
        assert error / max(float(jnp.linalg.norm(b)), floor) \
            < KDA_TOLERANCE[dtype], name


# --------------------------------------------------------------------------
# the chunk solve by doubling the diagonal blocks (PRs 47 and 48)
# --------------------------------------------------------------------------
def _row_at_a_time_solve(a, rhs):
    """``(I + strictly_lower(a))^-1 rhs`` as ``F.kda`` had it until PR 48:
    the plain reference of ``kda._solve``."""
    return jax.lax.linalg.triangular_solve(
        jnp.tril(a, -1), rhs, left_side=True, lower=True, unit_diagonal=True)


def _solve_inputs(kind, chunk, heads=3, kd=32, vd=16):
    """A chunk's ``(q, k, v, g, beta)`` of ``heads`` heads, float32:
    ``"independent"`` unit keys with beta 1 and no decay; ``"alike"`` keys
    near one direction (the mean entry of ``A`` about 0.9: the case in which
    a series in powers of ``A`` cancels thousands against thousands);
    ``"bound"`` every channel's decay at -5 a row but for a hair."""
    keys = jax.random.split(jax.random.PRNGKey(chunk), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    k = jax.random.normal(keys[1], (heads, chunk, kd))
    if kind == "alike":
        k = jax.random.normal(keys[0], (heads, 1, kd)) + 0.3 * k
    q = unit(jax.random.normal(keys[2], k.shape)) / math.sqrt(kd)
    v = jax.random.normal(keys[3], (heads, chunk, vd))
    g = jnp.zeros(k.shape)
    beta = jnp.ones(k.shape[:2])
    if kind == "bound":
        g = -5.0 * jax.nn.sigmoid(jax.random.normal(keys[4], k.shape) + 20.0)
        beta = jax.nn.sigmoid(jax.random.normal(keys[0], k.shape[:2]))
    return q, unit(k), v, g, beta


@pytest.mark.parametrize("kind", ["independent", "alike", "bound"])
@pytest.mark.parametrize("chunk", [16, 32, 48, 64, 128])
def test_the_chunk_solve_by_blocks_is_the_row_at_a_time_solve(chunk, kind,
                                                              monkeypatch):
    """``kda._solve`` against float64 and against ``triangular_solve`` on
    the chunk's own ``A``, and ``_prepare``'s gradients through it against
    the same through the old solve."""
    x = _solve_inputs(kind, chunk)
    q, k, v, g, beta = x
    b = jnp.cumsum(g, axis=-2)
    a, = jax.jit(lambda k, b: kda._pairwise(
        [k * beta[..., None]], k, b, jnp.float32))(k, b)
    rhs = jnp.concatenate([k * jnp.exp(b), v], -1) * beta[..., None]
    mean = float(jnp.abs(jnp.tril(a, -1)).sum()) / (
        a.shape[0] * chunk * (chunk - 1) / 2)
    assert {"independent": 0.1 < mean < 0.2, "alike": 0.85 < mean < 0.95,
            "bound": mean < 1e-2}[kind], mean
    want = np.linalg.solve(
        np.eye(chunk) + np.tril(np.asarray(a, np.float64), -1),
        np.asarray(rhs, np.float64))
    top = np.abs(want).max()
    error = lambda got: np.abs(np.asarray(got, np.float64) - want).max() / top
    new = error(jax.jit(kda._solve)(a, rhs))
    old = error(jax.jit(_row_at_a_time_solve)(a, rhs))
    # float32: 2e-6 of the largest entry, and no worse than three times the
    # old solve (or two units in float32's last place of that entry, where
    # the old solve is exact to the digit: ``A`` at the bound is all but 0)
    assert new < 2e-6 and new <= max(3 * old, 2.0 ** -22), (new, old)

    cots = [jax.random.normal(jax.random.PRNGKey(i), o.shape)
            for i, o in enumerate(jax.eval_shape(kda._prepare, *x))]

    def grads():
        return jax.jit(jax.grad(lambda *x: sum(
            jnp.sum(o * c) for o, c in zip(kda._prepare(*x), cots)),
            argnums=(0, 1, 2, 3, 4)))(*x)

    got = grads()
    monkeypatch.setattr(kda, "_solve", _row_at_a_time_solve)
    for name, one, other in zip("q k v g beta".split(), got, grads()):
        assert _rel(one, other) < KDA_TOLERANCE["float32"], name


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, nested ones too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _kda_gradient(shape, dtype):
    """``jax.grad`` of ``F.kda`` jitted, and shapes to lower it at."""
    x = [jax.ShapeDtypeStruct(shape, dtype)] * 3 + [
        jax.ShapeDtypeStruct(shape, "float32"),
        jax.ShapeDtypeStruct(shape[:3], dtype)]
    return jax.jit(jax.grad(
        lambda *a: jnp.sum(kda.kda(*a).astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4))), x


def test_kda_and_its_gradient_lower_no_triangular_solve():
    """The lowered module of ``jax.grad`` of ``F.kda`` holds no
    ``triangular_solve`` (the forward's, ``_backward``'s own ``_prepare``
    and the runs' way back all take ``_solve``) and no loop but the two
    scans over the chunks and the map over the runs; the solve's products,
    its way back's too, are float32 at ``HIGHEST``."""
    grad, x = _kda_gradient((1, 2, 256, 128), "bfloat16")
    text = grad.lower(*x).as_text()
    assert "dot_general" in text and "triangular_solve" not in text
    assert text.count("stablehlo.while") == 3

    # the product with the right-hand side and the two of the solve's own
    # way back: the inverse itself is element-wise float32
    a = jnp.zeros((4, 64, 64), jnp.float32)
    found = [e for e in _eqns(jax.make_jaxpr(jax.grad(
        lambda a, rhs: jnp.sum(kda._solve(a, rhs)), argnums=(0, 1)))(
            a, a).jaxpr) if e.primitive.name == "dot_general"]
    assert len(found) == 1 + 2
    for eqn in found:
        assert set(eqn.params["precision"]) == {jax.lax.Precision.HIGHEST}
        assert {v.aval.dtype for v in eqn.invars + eqn.outvars} == {
            jnp.dtype("float32")}


def test_the_chunk_solve_stays_a_small_program(monkeypatch):
    """What a step's set-up pays for the solve before XLA sees it, held on
    the CPU: written with a multiply-add an inner index and a slice a pair,
    ``_inverse`` was 1,210 equations a site and the op's gradient 6,030
    lines at the Ling cell's shape, and the cell's ``setup_s`` rose by 23 s
    (PERF.md section 6, PR 47).  A level is a few whole-array ops, and the
    sites share one jitted entry."""
    a = jnp.zeros((4, 64, 64), jnp.float32)
    assert sum(1 for _ in _eqns(jax.make_jaxpr(kda._inverse)(a).jaxpr)) <= 300

    grad, x = _kda_gradient((1, 8, 8192, 128), "bfloat16")
    assert grad.lower(*x).as_text().count("\n") <= 2100

    # two sites at one shape trace ``_inverse`` once: the entry is made
    # once a process and its identity holds the avals alone
    traces = []
    inverse = kda._inverse
    monkeypatch.setattr(kda, "_inverse",
                        lambda a: traces.append(a.shape) or inverse(a))
    kda._entries.cache_clear()
    try:
        a = jnp.zeros((5, 32, 32), jnp.float32)
        jaxpr = jax.make_jaxpr(lambda a, rhs: kda._solve(
            a, kda._solve(a, rhs)))(a, a).jaxpr
        assert traces == [a.shape]
        sites = [e for e in _eqns(jaxpr)
                 if e.primitive.name in ("jit", "pjit")]
        assert len(sites) == 2
        assert sites[0].params["jaxpr"] is sites[1].params["jaxpr"]
    finally:
        kda._entries.cache_clear()


def test_kda_refuses_a_length_that_is_no_whole_number_of_chunks():
    x = _kda_inputs(0, 96, "float32")
    with pytest.raises(mx.MXNetError, match="no whole number of chunks"):
        kda.kda(*x)
    assert kda.kda(*x, chunk=32).shape == x[2].shape
    with pytest.raises(mx.MXNetError, match="a multiple of 16"):
        kda.kda(*x, chunk=24)
    with pytest.raises(mx.MXNetError, match=r"beta \(2, 2, 96, 1\)"):
        kda.kda(*x[:4], x[4][..., None], chunk=32)


def test_kda_counts_its_calls_and_chunks():
    telemetry.reset()
    kda.kda(*_kda_inputs(0, 128, "float32"))
    assert _kda_paths() == {"scan": 1}
    assert telemetry.snapshot()["metrics"]["mxnet_kda_chunks_total"][
        "samples"][0]["value"] == 2


def test_short_conv_against_a_loop_over_rows_and_taps():
    rs = np.random.RandomState(3)
    x, w = rs.randn(2, 9, 6).astype("f"), rs.randn(4, 6).astype("f")
    want = np.zeros_like(x)
    for t in range(9):
        for i in range(4):
            if t - 3 + i >= 0:
                want[:, t] += w[i] * x[:, t - 3 + i]
    got = kda.short_conv(jnp.asarray(x), jnp.asarray(w), activation="")
    np.testing.assert_allclose(got, want, atol=1e-6)
    got = nd.short_conv(nd.array(x), nd.array(w)).asnumpy()
    np.testing.assert_allclose(got, want / (1 + np.exp(-want)), atol=1e-6)
    with pytest.raises(mx.MXNetError, match="unknown activation"):
        kda.short_conv(jnp.asarray(x), jnp.asarray(w), activation="relu")


def test_decay_and_l2_norm_ops():
    rs = np.random.RandomState(4)
    f = rs.randn(2, 5, 6).astype("f")
    a_log, dt = rs.randn(2).astype("f"), rs.randn(6).astype("f")
    g = np.asarray(kda.kda_decay(jnp.asarray(f), jnp.asarray(a_log),
                                 jnp.asarray(dt), heads=2, lower_bound=-5.0))
    assert g.shape == (2, 2, 5, 3) and g.max() < 0 and g.min() >= -5
    want = -5.0 / (1 + np.exp(-np.exp(a_log)[:, None]
                              * (f + dt).reshape(2, 5, 2, 3)))
    np.testing.assert_allclose(g, want.transpose(0, 2, 1, 3), rtol=1e-5)
    x = np.asarray(kda.l2_norm_heads(jnp.asarray(f), heads=2, scale=0.5))
    np.testing.assert_allclose(np.linalg.norm(x, axis=-1), 0.5, rtol=1e-4)


# --------------------------------------------------------------------------
# attention at 192 beside 128
# --------------------------------------------------------------------------
def _plain_attention(q, k, v):
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    rows = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((rows, rows), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def _qkv(dtype, rows=512, heads=2):
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    shapes = ((1, heads, rows, 192), (1, heads, rows, 192),
              (1, heads, rows, 128), (1, heads, rows, 128))
    return [jax.random.normal(key, s).astype(dtype)
            for key, s in zip(keys, shapes)]


@pytest.mark.parametrize("dtype,tolerance", [("float32", 2e-6),
                                             ("bfloat16", 2e-2)])
def test_attention_takes_q_and_k_wider_than_v(dtype, tolerance):
    q, k, v, cot = _qkv(dtype)
    got = fa.flash_attention(q, k, v, causal=True)
    assert got.shape == v.shape
    assert _rel(got, _plain_attention(q, k, v)) < tolerance
    grads = jax.grad(lambda *a: jnp.sum(fa.flash_attention(
        *a, causal=True).astype(jnp.float32) * cot), argnums=(0, 1, 2))(
            q, k, v)
    wants = jax.grad(lambda *a: jnp.sum(_plain_attention(*a) * cot),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b, like in zip(grads, wants, (q, k, v)):
        assert a.shape == like.shape and _rel(a, b) < tolerance


def test_both_attention_kernels_at_192_and_128_under_the_tpu_interpreter():
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, g = _qkv("bfloat16")
    scale = 1 / math.sqrt(192)
    with pltpu.force_tpu_interpret_mode():
        o, lse = fa._fa_forward_pallas(q, k, v, True, scale)
        grads = fa._fa_backward_pallas(q, k, v, o, lse, g, True, scale)
    want, want_lse = fa._mha_with_lse(q, k, v, True, scale)
    assert o.shape == v.shape and _rel(o, want) < 1e-2
    np.testing.assert_allclose(lse, want_lse, atol=1e-5)
    wants = fa._fa_backward_blockwise(q, k, v, o, lse, g, True, scale)
    for a, b, like in zip(grads, wants, (q, k, v)):
        assert a.shape == like.shape and _rel(a, b) < 1e-2


def test_attention_gates_read_both_widths_and_mismatches_are_named(
        monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = lambda width, dtype="bfloat16": jax.ShapeDtypeStruct(
        (1, 2, 512, width), jnp.dtype(dtype))
    assert fa._padded_width(192) == 256 and fa._padded_width(128) == 128
    assert fa._padded_width(64) == 64 and fa._padded_width(96) is None
    assert fa._use_pallas(x(192), x(128)) and fa._use_pallas(x(128))
    assert not fa._use_pallas(x(192), x(192))     # v is never padded
    assert not fa._use_pallas(x(96), x(128))
    assert fa._use_pallas_bwd(x(192), x(192), x(128))
    assert not fa._use_pallas_bwd(x(192, "float32"), x(192, "float32"),
                                  x(128, "float32"))
    # equal widths read what they always read
    assert fa._fa_bwd_vmem_bytes(8192, 128, 2, 512, 512) \
        == fa._fa_bwd_vmem_bytes(8192, 128, 2, 512, 512, 128)
    assert fa._fa_bwd_vmem_bytes(8192, 256, 2, 512, 512, 128) \
        < fa._fa_bwd_vmem_bytes(8192, 256, 2, 512, 512)
    assert fa._fa_fwd_vmem_limit(16384, 128, 2, 512, False) \
        == fa._fa_fwd_vmem_limit(16384, 128, 2, 512, False, 128)
    q, k, v, _ = _qkv("float32", rows=8)
    with pytest.raises(mx.MXNetError, match=r"q \(1, 2, 8, 192\), k "
                       r"\(1, 2, 8, 128\)"):
        fa.flash_attention(q, v, v, causal=True)
    with pytest.raises(mx.MXNetError, match="share a head size"):
        fa.flash_attention(q, k, v[:, :, :4], causal=True)


def test_rope_over_pairs_of_neighbours():
    from mxnet_tpu.ops.attention_ops import rope

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 7, 8))
    got = rope(x, base=100.0, interleave=True)
    # the same turn as the half-split convention on the de-interleaved head
    halves = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    want = rope(halves, base=100.0)
    np.testing.assert_allclose(got[..., 0::2], want[..., :4], atol=1e-6)
    np.testing.assert_allclose(got[..., 1::2], want[..., 4:], atol=1e-6)


# --------------------------------------------------------------------------
# group-limited choice
# --------------------------------------------------------------------------
def _choice_by_loop(biased, n_group, topk_group, top_k):
    """A token at a time, in numpy: a group's score the sum of its two
    largest, the best groups (ties to the lower group), then the largest
    among theirs (ties to the lower index)."""
    out = []
    for row in np.asarray(biased, np.float64):
        groups = row.reshape(n_group, -1)
        score = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        kept = np.argsort(-score, kind="stable")[:topk_group]
        limited = np.full_like(row, -np.inf).reshape(n_group, -1)
        limited[kept] = groups[kept]
        out.append(np.argsort(-limited.reshape(-1), kind="stable")[:top_k])
    return np.asarray(out)


@pytest.mark.parametrize("ties", [False, True])
def test_group_limited_selection_against_a_loop(ties):
    rs = np.random.RandomState(5)
    scores = rs.rand(40, 32).astype("f")
    if ties:    # a few values only: groups tie, and experts inside them
        scores = np.round(scores * 3) / 3
    limited = expert_parallel.limit_to_groups(jnp.asarray(scores), 4, 2)
    chosen = jax.lax.top_k(limited, 4)[1]
    want = _choice_by_loop(scores, 4, 2, 4)
    np.testing.assert_array_equal(np.asarray(chosen), want)
    assert np.isinf(np.asarray(limited)).sum() == 40 * 16
    with pytest.raises(mx.MXNetError, match="do not fit a router"):
        expert_parallel.limit_to_groups(jnp.asarray(scores), 5, 2)


def test_the_expert_layer_chooses_inside_the_groups():
    """``moe_swiglu`` with ``n_group`` against the same layer computed a
    token at a time from the loop's choice; without groups another set."""
    rs = np.random.RandomState(6)
    h = jnp.asarray(rs.randn(1, 24, 16).astype("f"))
    router = jnp.asarray(rs.randn(16, 16).astype("f"))
    bias = jnp.asarray(rs.rand(16).astype("f"))
    w = [jnp.asarray(0.3 * rs.randn(*s).astype("f"))
         for s in ((16, 16, 8), (16, 16, 8), (16, 8, 16))]
    from mxnet_tpu.ops.attention_ops import moe_swiglu

    telemetry.reset()
    kwargs = dict(capacity_factor=0.0, top_k=3, renormalize=True,
                  score="sigmoid", route_scale=2.5, renorm_eps=1e-20)
    got = moe_swiglu(h, router, *w, bias, n_group=4, topk_group=2, **kwargs)
    scores = jax.nn.sigmoid(h[0] @ router)
    chosen = _choice_by_loop(scores + bias, 4, 2, 3)
    want = np.zeros((24, 16), np.float32)
    for t in range(24):
        s = np.asarray(scores[t])[chosen[t]]
        for e, gate in zip(chosen[t], 2.5 * s / (s.sum() + 1e-20)):
            x = np.asarray(h[0, t])
            hidden = np.asarray(jax.nn.silu(x @ w[0][e])) * (x @ w[1][e])
            want[t] += gate * (hidden @ np.asarray(w[2][e]))
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    assert telemetry.snapshot()["metrics"][
        "mxnet_moe_group_limited_calls_total"]["samples"][0]["value"] == 1
    free = moe_swiglu(h, router, *w, bias, **kwargs)
    assert float(jnp.abs(free - got).max()) > 1e-3


# --------------------------------------------------------------------------
# the shares of heads and of experts
# --------------------------------------------------------------------------
def _set(block, values):
    """Set a block's parameters by the name after its prefix."""
    for name, param in block.collect_params().items():
        param.set_data(nd.array(values[name[len(block.prefix):]]))


def _mixer_config(kind, held):
    return llama.LlamaConfig(
        hidden_size=64, num_layers=1, num_heads=4, num_kv_heads=4,
        head_dim=16, rms_eps=1e-6, rope_base=6e6, attention_types=(kind,),
        attention_gate="head_wise", attention_heads_held=held,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_interleave=True)


@pytest.mark.parametrize("kind", ["kda", "mla"])
def test_four_shares_of_the_heads_add_up_to_the_whole_mixer(kind):
    """The model-configs guide's test of the cut, for heads: the mixer of
    all four heads against the sum of four mixers of one head each, every
    one given its head's slice of the whole one's weights (what is not a
    head's own, the latent's projection and norm and the delta rule's
    output norm, given whole); and the whole mixer against the reference's
    (4e-6: float32 sums in another order)."""
    cfg, _, reference, _ = parity.small("ling3_flash_vl")
    rs = np.random.RandomState(8)
    whole = llama.MIXERS[kind](_mixer_config(kind, (0, 4)))
    whole.initialize()
    values = {}
    for name, param in whole.collect_params().items():
        value = rs.randn(*param.shape).astype("f")
        short = name[len(whole.prefix):]
        if "proj" in short:
            value *= 0.2
        elif "norm" in short:
            value = 1 + 0.1 * value
        values[short] = value
    _set(whole, values)
    x = nd.array(rs.randn(2, 32, 64).astype("f"))
    want = whole(x).asnumpy()

    # the rows of a head in a projection of `heads * width` outputs, the
    # columns of a head in the output projection, nothing of the shared
    per_head = {"q_proj_weight": 0, "k_proj_weight": 0, "v_proj_weight": 0,
                "f_proj_weight": 0, "b_proj_weight": 0, "gate_proj_weight": 0,
                "kv_b_proj_weight": 0, "a_log": 0, "dt_bias": 0,
                "q_conv_weight": 1, "k_conv_weight": 1, "v_conv_weight": 1,
                "o_proj_weight": 1}
    total = 0.0
    for head in range(4):
        share = llama.MIXERS[kind](_mixer_config(kind, (head, 1)))
        share.initialize()
        mine = {}
        for name, value in values.items():
            if name in per_head:
                axis = per_head[name]
                width = value.shape[axis] // 4
                value = np.take(value, range(head * width,
                                             (head + 1) * width), axis=axis)
            mine[name] = value
        _set(share, mine)
        total = total + share(x).asnumpy()
    np.testing.assert_allclose(total, want, atol=4e-6 * np.abs(want).max()
                               + 1e-6)

    names = {"kda": {"q_conv_weight": "kda.q_conv",
                     "k_conv_weight": "kda.k_conv",
                     "v_conv_weight": "kda.v_conv", "a_log": "kda.a_log",
                     "dt_bias": "kda.dt_bias", "q_proj_weight": "kda.q",
                     "k_proj_weight": "kda.k", "v_proj_weight": "kda.v",
                     "f_proj_weight": "kda.f", "b_proj_weight": "kda.b",
                     "gate_proj_weight": "kda.gate",
                     "o_norm_weight": "kda.o_norm", "o_proj_weight": "kda.o"},
             "mla": {"q_proj_weight": "mla.q", "kv_a_proj_weight": "mla.kv_a",
                     "kv_a_norm_weight": "mla.kv_a_norm",
                     "kv_b_proj_weight": "mla.kv_b", "o_proj_weight": "mla.o",
                     "gate_proj_weight": "mla.gate"}}[kind]
    p = {names[k]: jnp.asarray(v) for k, v in values.items()}
    mixer = reference.kda_mixer if kind == "kda" else reference.mla_mixer
    with jax.default_matmul_precision("highest"):
        ref = jnp.stack([mixer(cfg, lambda t: t, row, p)
                         for row in jnp.asarray(x.asnumpy())])
    np.testing.assert_allclose(want, ref, atol=2e-5 * np.abs(want).max())


@pytest.mark.parametrize("tokens,experts,held,parts", [
    # the three decoder cells that ran before: parts of 32,768 rows as ever
    (8192, 128, 8, 2), (16384, 128, 16, 4), (16384, 64, 8, 4),
    # 8 of 512: a part of eight even loads of 1,024 pairs
    (8192, 512, 8, 8),
])
def test_a_thin_share_walks_smaller_parts_and_no_other_does(tokens, experts,
                                                            held, parts):
    """``_PART_EVEN_LOADS`` sizes a part by the share where the share is
    thin; the shapes of the cells that ran before it keep their parts."""
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(tokens, 8).astype("f"))
    router = jnp.asarray(rs.randn(8, experts).astype("f"))
    w = jnp.asarray(0.1 * rs.randn(held, 8, 8).astype("f"))

    def expert_fn(p, rows, sizes):
        return jax.lax.ragged_dot(rows, p, sizes)

    out, aux = jax.jit(lambda x: expert_parallel.moe_apply(
        expert_fn, w, router, x, capacity_factor=None, top_k=8,
        held=(0, held)))(x)
    assert int(aux["parts"]) == parts
    assert int(aux["live_parts"]) == 1 and bool(jnp.isfinite(out).all())
    assert 0 < int(aux["routed_pairs"]) <= tokens * 8 // parts


def test_the_shares_of_experts_and_the_shared_expert_once_add_up():
    """64 routed experts in 8 groups of 8, 4 groups kept, 8 a token, in 16
    shares of 4; random routers and a random bias, the shared expert counted
    once."""
    parity.shares_add_up(
        "ling3_flash_vl", 64, 4, 8, 3e-5, moe_renorm_eps=1e-20,
        moe_score="sigmoid", moe_route_scale=2.5, moe_select_bias=True,
        moe_groups=(8, 4), moe_shared_intermediate_size=32)


# --------------------------------------------------------------------------
# the zoo: kinds, export, refusals
# --------------------------------------------------------------------------
def _tiny(**overrides):
    kw = dict(vocab_size=64, hidden_size=32, num_layers=3, num_heads=4,
              num_kv_heads=4, head_dim=16, intermediate_size=48,
              attention_types=("kda", "mla", "kda"),
              attention_gate="head_wise", attention_heads_held=(2, 2),
              kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
              v_head_dim=16, rope_interleave=True)
    kw.update(overrides)
    return llama.LlamaConfig(**kw)


def test_hybridize_and_export_round_trip(tmp_path):
    net = llama.LlamaForCausalLM(_tiny())
    net.initialize(mx.init.Normal(0.1))
    ids = nd.array(np.random.RandomState(0).randint(0, 64, (2, 32)),
                   dtype="int32")
    eager = net(ids).asnumpy()
    net.hybridize()
    np.testing.assert_allclose(net(ids).asnumpy(), eager, rtol=2e-5,
                               atol=2e-6)
    path = str(tmp_path / "ling")
    net.export(path)
    back = gluon.SymbolBlock.imports(path + "-symbol.json", ["data"],
                                     path + "-0000.params")
    np.testing.assert_allclose(back(ids).asnumpy(), eager, rtol=2e-5,
                               atol=2e-6)


def test_kinds_shares_and_what_refuses_them():
    cfg = _tiny()
    net = llama.LlamaForCausalLM(cfg)
    assert [type(layer.self_attn).__name__ for layer in net.model.layers] \
        == ["LlamaDeltaAttention", "LlamaLatentAttention",
            "LlamaDeltaAttention"]
    shapes = {n[len(net.prefix):]: p.shape
              for n, p in net.collect_params().items()}
    # two of four heads: a head's rows and columns, the latent whole
    assert shapes["model_layers_0_self_attn_q_proj_weight"] == (32, 32)
    assert shapes["model_layers_0_self_attn_b_proj_weight"] == (2, 32)
    assert shapes["model_layers_0_self_attn_o_proj_weight"] == (32, 32)
    assert shapes["model_layers_0_self_attn_q_conv_weight"] == (4, 32)
    assert shapes["model_layers_1_self_attn_q_proj_weight"] == (48, 32)
    assert shapes["model_layers_1_self_attn_kv_a_proj_weight"] == (24, 32)
    assert shapes["model_layers_1_self_attn_kv_b_proj_weight"] == (64, 16)
    assert shapes["model_layers_1_self_attn_gate_proj_weight"] == (2, 32)
    assert not cfg.layers_alike()
    assert _tiny(num_layers=2, attention_types=("kda", "kda")).layers_alike()
    with pytest.raises(mx.MXNetError, match=r"several kinds.*kda.*mla"):
        net.pipeline_decompose(1)
    for apply in (lambda: llama.prefill_apply({}, cfg, None),
                  lambda: llama.decode_apply({}, cfg, None, None, None)):
        with pytest.raises(mx.MXNetError, match="'kda' or 'mla' layers"):
            apply()
    # packed documents: refused by the layer's name
    net.initialize()
    ids = nd.array(np.zeros((1, 16)), dtype="int32")
    with pytest.raises(mx.MXNetError, match="'kda' layer does not take "
                       "segment_ids"):
        net(ids, ids)
    mla_first = llama.LlamaForCausalLM(_tiny(
        num_layers=1, attention_types=("mla",)))
    mla_first.initialize()
    with pytest.raises(mx.MXNetError, match="'mla' layer does not take "
                       "segment_ids"):
        mla_first(ids, ids)
    for bad, match in (
            ({"attention_types": ("kda", "mla", "linear")},
             "'full', 'window', 'kda', 'mla', 'ssm', 'gmu' or 'cross'"),
            ({"attention_heads_held": (3, 2)}, "no run of the 4 heads"),
            ({"attention_heads_held": (0, 0)}, "no run of the 4 heads"),
            ({"attention_types": ("kda", "full", "kda")},
             "written for the kinds 'kda' and 'mla'"),
            ({"block_diffusion": 4}, "block-diffusion layout"),
            ({"kv_lora_rank": 0}, "needs kv_lora_rank"),
            ({"attention_gate": "row_wise"}, "attention_gate is False"),
            ({"attention_types": ("full",) * 3, "attention_heads_held": None,
              "attention_gate": "head_wise"}, "attention_gate is False")):
        with pytest.raises(mx.MXNetError, match=match):
            _tiny(**bad)
    # groups belong to the dropless router
    with pytest.raises(mx.MXNetError, match="moe_groups"):
        llama.LlamaMoEMLP(llama.LlamaConfig(
            hidden_size=32, num_heads=2, num_kv_heads=1, num_experts=8,
            moe_groups=(4, 2)))
