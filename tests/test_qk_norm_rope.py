"""``F.qk_norm_rope`` (ISSUE 36): the Pallas kernels under the TPU interpreter
against the composition of ``rms_norm`` and ``rope`` in float32, the gate
between the two, and the counter that says which a call took.  All on the
CPU; ``tests/test_tpu_compile.py`` is where the chip's compiler reads the
kernels."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import qk_norm_rope as qnr
from mxnet_tpu.ops.attention_ops import rms_norm, rope, yarn_rope_parameters

EPS = 1e-6
YARN = dict(zip(("inv_freq", "magnitude"),
                yarn_rope_parameters(128, 10000.0, 16.0, 8192)))


def composition(x, gamma, positions, heads, turn):
    """Today's chain, op for op: the registered ``rms_norm`` and ``rope``
    on the projection reshaped and transposed."""
    b, l, width = x.shape
    y = x.reshape(b, l, heads, width // heads).transpose(0, 2, 1, 3)
    if gamma is not None:
        y = rms_norm(y, gamma, eps=EPS)
    return y if turn is None else rope(y, positions, **turn)


def the_op(x, gamma, positions, heads, turn):
    given = [a for a in (x, gamma, positions) if a is not None]
    return qnr.qk_norm_rope(*given, heads=heads, norm=gamma is not None,
                            eps=EPS, turn=turn is not None, **(turn or {}))


def inputs(b, l, heads, hd, dtype, norm, positions, seed=0):
    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(b, l, heads * hd).astype("f")).astype(dtype)
    g = jnp.asarray(rs.randn(b, heads, l, hd).astype("f")).astype(dtype)
    gamma = jnp.asarray(1 + 0.2 * rs.randn(hd).astype("f")) if norm else None
    if positions == "row":          # block diffusion's [xt ; x0]
        positions = jnp.concatenate([jnp.arange(l // 2)] * 2).astype("int32")
    elif positions == "documents":  # they start again mid-row, by sample
        starts = rs.randint(1, l - 1, size=(b, 1))
        index = np.arange(l)[None, :]
        positions = jnp.asarray(
            np.where(index < starts, index, index - starts).astype("int32"))
    return x, g, gamma, positions


def as_on_a_tpu(monkeypatch):
    """The gate asks JAX's default backend; the kernels then run under
    Pallas' TPU interpreter."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return pltpu.force_tpu_interpret_mode()


def grads(fn, x, g, gamma, positions, heads, turn):
    """``(out, dx[, dgamma])`` of ``fn`` as one jitted program (eagerly the
    forward and the gradient each trace, compile and run op by op: two
    thirds of a case's seconds)."""
    def loss(x, gamma):
        return jnp.sum((fn(x, gamma, positions, heads, turn) * g).astype(
            jnp.float32))

    @jax.jit
    def both(x, gamma):
        out = fn(x, gamma, positions, heads, turn)
        if gamma is None:
            return out, jax.grad(loss)(x, None)
        return (out, *jax.grad(loss, (0, 1))(x, gamma))

    return both(x, gamma)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads,l,norm,turn,positions", [
    # one row tile (256, 512, 1024) and several (3 x 256, 2 x 1024); the
    # cells' 32 query and 4 key-value heads
    (32, 256, True, {"base": 1e6}, None),
    (4, 512, True, {"base": 1e6}, "row"),
    (4, 768, True, {"base": 1e6}, "row"),
    (32, 512, True, {"base": 5e5}, "documents"),
    (4, 2048, True, {"base": 1e4, "scale": 0.5}, "documents"),
    (4, 1024, True, YARN, "documents"),
    (4, 512, True, YARN, None),
    # a configuration without q/k norm: the turn alone
    (4, 512, False, {"base": 5e5}, None),
    (32, 256, False, YARN, "documents"),
    # a layer outside rope_attention_types: the norm alone
    (4, 768, True, None, None),
    (32, 256, True, None, None),
], ids=lambda v: "yarn" if v is YARN else None)
def test_kernels_interpreted_match_the_composition_in_float32(
        monkeypatch, heads, l, norm, turn, positions, dtype):
    """Forward, ``dx`` and ``dgamma`` through the op and its ``custom_vjp``,
    against autodiff through the composition on the same (rounded) inputs in
    float32.  float32: the noise of sums in another order.  bf16: the kernel
    works in float32 and rounds once, so half a bf16 place of the result
    (the composition rounds after the norm and multiplies by rounded cos and
    sin: its own error is asserted to be no smaller)."""
    x, g, gamma, positions = inputs(2, l, heads, 128, dtype, norm, positions)
    with as_on_a_tpu(monkeypatch):
        assert qnr._use_pallas(x, 128)
        got = grads(the_op, x, g, gamma, positions, heads, turn)
    want = grads(composition, x.astype("float32"), g.astype("float32"), gamma,
                 positions, heads, turn)
    assert got[0].shape == (2, heads, l, 128) and got[0].dtype == x.dtype
    assert got[1].shape == x.shape and got[1].dtype == x.dtype
    if norm:    # float32 sums over up to 16,384 rows, of float32 products
        assert got[2].dtype == gamma.dtype
        np.testing.assert_allclose(got[2], want[2], rtol=1e-4, atol=1e-3)
    if dtype == "float32":
        for a, b in zip(got[:2], want[:2]):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
        return
    for a, b in zip(got[:2], want[:2]):
        np.testing.assert_allclose(a.astype("float32"), b, rtol=2 ** -8,
                                   atol=2 ** -10)
    composed = composition(x, gamma, positions, heads, turn)
    assert (np.abs(got[0].astype("float32") - want[0]).max()
            <= np.abs(composed.astype("float32") - want[0]).max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("hd,l", [(64, 512), (128, 320)])
def test_outside_the_gate_the_op_is_the_composition_bit_for_bit(
        monkeypatch, hd, l, dtype):
    """A head of half a lane tile, rows that are no whole tile: refused on a
    TPU too, and the result is then the registered ops' own."""
    x, g, gamma, positions = inputs(2, l, 4, hd, dtype, True, "documents")
    turn = {"base": 1e6}
    want = grads(composition, x, g, gamma, positions, 4, turn)
    with as_on_a_tpu(monkeypatch):
        assert not qnr._use_pallas(x, hd)
        got = grads(the_op, x, g, gamma, positions, 4, turn)
    on_the_cpu = grads(the_op, x, g, gamma, positions, 4, turn)
    for a, b, c in zip(got, want, on_the_cpu):
        assert a.dtype == b.dtype
        assert np.array_equal(a, b) and np.array_equal(c, b)


def _calls(path):
    s = [s for s in telemetry.snapshot()["metrics"].get(
        "mxnet_qk_norm_rope_calls_total", {"samples": []})["samples"]
        if s["labels"] == {"path": path}]
    return s[0]["value"] if s else 0


def test_the_counter_of_the_path_taken_and_a_mesh_being_traced(monkeypatch):
    """Once a trace, under the label of the path; a step traced over a mesh
    (``flash_attention.batch_sharded``) takes the composition, since GSPMD
    cannot partition a Mosaic kernel."""
    from mxnet_tpu.ops.flash_attention import batch_sharded

    x, _, gamma, _ = inputs(1, 256, 4, 128, "float32", True, None)
    call = jax.jit(lambda x, gamma: the_op(x, gamma, None, 4, {"base": 1e4}))
    before = _calls("pallas"), _calls("composed")
    call(x, gamma), call(x, gamma)      # the second call traces nothing
    assert (_calls("pallas"), _calls("composed")) == (before[0],
                                                      before[1] + 1)
    with as_on_a_tpu(monkeypatch):
        the_op(x, gamma, None, 4, {"base": 1e4})
        assert _calls("pallas") == before[0] + 1
        with batch_sharded(None, ("dp",)):
            assert not qnr._use_pallas(x, 128)
            the_op(x, gamma, None, 4, {"base": 1e4})
    assert (_calls("pallas"), _calls("composed")) == (before[0] + 1,
                                                      before[1] + 2)


def test_the_backward_keeps_the_projection_and_nothing_of_the_outputs_size(
        monkeypatch):
    """The residuals of the op's ``custom_vjp`` are its own inputs."""
    x, _, gamma, _ = inputs(1, 256, 4, 128, "bfloat16", True, None)
    with as_on_a_tpu(monkeypatch):
        _, vjp = jax.vjp(lambda x, gamma: the_op(x, gamma, None, 4,
                                                 {"base": 1e4}), x, gamma)
    kept = sorted((tuple(leaf.shape), str(leaf.dtype))
                  for leaf in jax.tree_util.tree_leaves(vjp)
                  if hasattr(leaf, "shape"))
    assert kept == sorted([((1, 256, 512), "bfloat16"), ((128,), "float32"),
                           ((256, 256), "float32")])
