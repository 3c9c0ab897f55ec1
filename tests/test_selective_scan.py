"""``ops/selective_scan.py`` (ISSUE 45): the chunked selective state-space
scan against the token-by-token recurrence, outputs and gradients; its Pallas
kernels under the TPU interpreter against the scan of scans; what the op
refuses by name; the counters that say which walk a call took; and the names a
layer's checkpoint keeps.  All on the CPU; ``tests/test_tpu_compile.py`` is
where the chip's compiler reads the kernels at the cell's shape."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.ops import selective_scan as ss

from test_grouped_matmul import as_on_a_tpu


def operands(b, l, d, n, dtype="float32", seed=0):
    """``(x, delta, a, b, c, skip)``: steps of 0.02 to 0.7 and rates of -0.2
    to -5, so that a chunk's decay spans everything from none to all."""
    rs = np.random.RandomState(seed)
    to = lambda v: jnp.asarray(v.astype("f")).astype(dtype)
    return (to(rs.randn(b, l, d)),
            jnp.asarray(np.log1p(np.exp(rs.randn(b, l, d) - 2)).astype("f")),
            -jnp.exp(jnp.asarray(rs.randn(d, n).astype("f"))),
            to(rs.randn(b, l, n)), to(rs.randn(b, l, n)),
            jnp.asarray(rs.randn(d).astype("f")))


def _value_and_grads(fn, args, seed=1):
    """The weighted sum of ``fn``'s output and its gradient for every
    operand, jitted."""
    weigh = jnp.asarray(np.random.RandomState(seed).randn(
        *args[0].shape).astype("f"))
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weigh),
        argnums=tuple(range(6))))(*args)


@pytest.mark.parametrize("dtype,rtol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("chunk", [8, 32])
def test_chunked_scan_is_the_recurrence_outputs_and_gradients(chunk, dtype,
                                                              rtol):
    """The hand-written backward over chunk states against autodiff of the
    recurrence a row at a time; bf16 operands round ``y`` and the cotangents
    of ``x``, ``B`` and ``C`` once."""
    args = operands(2, 64, 48, 4, dtype)
    want = ss.selective_scan_recurrent(*args)
    got = jax.jit(lambda *a: ss.selective_scan(*a, chunk=chunk))(*args)
    assert got.dtype == args[0].dtype and got.shape == args[0].shape
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=rtol,
                               atol=rtol * float(jnp.abs(want).max()))
    value, grads = _value_and_grads(
        lambda *a: ss.selective_scan(*a, chunk=chunk), args)
    plain, wanted = _value_and_grads(ss.selective_scan_recurrent, args)
    np.testing.assert_allclose(value, plain, rtol=rtol)
    for g, w, a in zip(grads, wanted, args):
        assert g.dtype == a.dtype and g.shape == a.shape
        np.testing.assert_allclose(
            g.astype(jnp.float32), w.astype(jnp.float32), rtol=rtol,
            atol=rtol * float(jnp.abs(w.astype(jnp.float32)).max()))


def test_kernels_interpreted_match_the_scan_of_scans(monkeypatch):
    """Both kernels under the TPU interpreter at one block of channels, two
    chunks and the cell's 16 states: ``y``, and the cotangent of every
    operand (``dB`` and ``dC`` summed over sublanes in the kernel and over
    lanes outside it, ``dA`` and the skip's over the chunks in VMEM)."""
    args = operands(1, 32, ss._BLOCK, 16)
    call = lambda *a: ss.selective_scan(*a, chunk=16)
    value, grads = _value_and_grads(call, args)
    telemetry.reset()
    with as_on_a_tpu(monkeypatch):
        ss._make_scan.cache_clear()
        got, kernel = _value_and_grads(call, args)
    ss._make_scan.cache_clear()
    assert telemetry.SSM_SCAN_CALLS.labels(path="pallas").value == 1
    np.testing.assert_allclose(got, value, rtol=1e-5)
    for g, w in zip(kernel, grads):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max()))


def test_the_gate_the_counters_and_the_registered_op():
    telemetry.reset()
    args = operands(1, 32, 24, 4)
    assert not ss.use_pallas(args[0])       # no TPU here, no whole block
    got = nd.selective_scan(*map(nd.array, args), chunk=16).asnumpy()
    np.testing.assert_allclose(got, ss.selective_scan_recurrent(*args),
                               rtol=2e-5, atol=1e-5)
    assert telemetry.SSM_SCAN_CALLS.labels(path="scan").value == 1
    assert telemetry.SSM_SCAN_CHUNKS.value == 2
    np.testing.assert_allclose(
        nd.ssm_delta(nd.array([-1.0, 0.0, 30.0])).asnumpy(),
        np.log1p(np.exp([-1.0, 0.0, 30.0])), rtol=1e-6)
    np.testing.assert_allclose(nd.ssm_rate(nd.array([0.0, 1.0])).asnumpy(),
                               [-1.0, -np.e], rtol=1e-6)


def test_what_the_op_refuses_by_name():
    x, delta, a, b, c, skip = operands(1, 32, 24, 4)
    with pytest.raises(mx.MXNetError, match="whole number of chunks of 24"):
        ss.selective_scan(x, delta, a, b, c, skip, chunk=24)
    with pytest.raises(mx.MXNetError, match="does not take segment_ids"):
        ss.selective_scan(x, delta, a, b, c, skip,
                          segment_ids=jnp.zeros((1, 32), jnp.int32), chunk=16)
    with pytest.raises(mx.MXNetError, match=r"a \(D, N\), b and c"):
        ss.selective_scan(x, delta, a, b[..., :3], c, skip, chunk=16)
    with pytest.raises(mx.MXNetError, match=r"a \(D, N\), b and c"):
        ss.selective_scan(x, delta[:, :16], a, b, c, skip, chunk=16)


def test_the_op_names_its_output_and_states_inside_a_keeping_checkpoint():
    """Outside ``checkpoint_keeps`` the program carries no name; inside it
    the rule names ``y`` and the chunk states, and a checkpoint whose policy
    keeps them walks the scan once in its gradient, not twice."""
    args = operands(1, 32, 24, 4)

    def make_loss():     # a function a trace: jax keeps a function's traces
        return lambda *a: jnp.sum(jnp.square(
            ss.selective_scan(*a, chunk=16)))

    names = (ss.KEPT_Y, ss.KEPT_STATES)
    plain = str(jax.make_jaxpr(jax.grad(jax.checkpoint(make_loss())))(*args))
    assert not any(f"name={n}]" in plain for n in names)
    policy = jax.checkpoint_policies.save_only_these_names(*names)
    telemetry.reset()
    with fa.checkpoint_keeps():
        kept = str(jax.make_jaxpr(jax.grad(
            jax.checkpoint(make_loss(), policy=policy)))(*args))
    assert [kept.count(f"name={n}]") for n in names] == [1, 1]
    # the scan of scans is a scan over chunks: the forward's, and the
    # backward's two (a chunk's states again, then its rows from the last);
    # the plain checkpoint walks the forward's a second time
    assert plain.count("scan[") - kept.count("scan[") == 2
    assert telemetry.LAYER_CHECKPOINT_KEPT_BYTES.labels(
        name=ss.KEPT_Y).value == 32 * 24 * 4
    assert telemetry.LAYER_CHECKPOINT_KEPT_BYTES.labels(
        name=ss.KEPT_STATES).value == 2 * 4 * 24 * 4


def test_short_conv_with_a_bias_and_the_pairs_combination():
    rs = np.random.RandomState(0)
    x, w, bias = (rs.randn(2, 12, 6).astype("f"), rs.randn(4, 6).astype("f"),
                  rs.randn(6).astype("f"))
    padded = np.concatenate([np.zeros((2, 3, 6), "f"), x], 1)
    y = sum(padded[:, i:i + 12] * w[i] for i in range(4)) + bias
    np.testing.assert_allclose(
        nd.short_conv(nd.array(x), nd.array(w), nd.array(bias)).asnumpy(),
        y / (1 + np.exp(-y)), rtol=1e-5, atol=1e-6)
    # two pairs of values 8 wide: (o1 - lambda o2), its RMSNorm, the scale
    o = rs.randn(2, 4, 5, 8).astype("f")
    l = [0.1 * rs.randn(3).astype("f") for _ in range(4)]
    scale = rs.rand(8).astype("f")
    lam = np.exp(l[0] @ l[1]) - np.exp(l[2] @ l[3]) + 0.7
    d = o[:, :2] - lam * o[:, 2:]
    d = d / np.sqrt((d * d).mean(-1, keepdims=True) + 1e-5) * scale * 0.3
    got = nd.diff_attn_combine(nd.array(o), *map(nd.array, l),
                               nd.array(scale), lambda_init=0.7, eps=1e-5)
    np.testing.assert_allclose(
        got.asnumpy(), d.transpose(0, 2, 1, 3).reshape(2, 5, 16), rtol=1e-5,
        atol=1e-6)
