"""A Gluon parameter's gradient buffer is made by the first that asks for it:
``grad()`` / ``list_grad()`` / ``data().grad`` make zeros, a backward stores
its cotangent, and a net trained through ``TrainStep`` never asks.  Every
result of the imperative path is what allocating at ``initialize`` gave."""
import gc

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel.data_parallel import TrainStep


def _made():
    """``(buffers, bytes)`` made since ``telemetry.reset()``."""
    return (telemetry.PARAMETER_GRAD_BUFFERS.value,
            telemetry.PARAMETER_GRAD_BYTES.value)


def _net(grad_req="write"):
    """A net of four trained parameters (101 numbers) and its batch."""
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu", in_units=6),
            gluon.nn.Dense(5, in_units=8))
    net.initialize(mx.init.Xavier())
    if grad_req != "write":
        net.collect_params().setattr("grad_req", grad_req)
    rs = np.random.RandomState(0)
    return net, nd.array(rs.randn(4, 6).astype("f"))


def _backward(net, x):
    with autograd.record():
        loss = (net(x) ** 2).mean()
    loss.backward()


def test_initialize_marks_the_weight_and_makes_no_buffer():
    telemetry.reset()
    net, x = _net()
    for p in net.collect_params().values():
        data = p.data()
        assert p.grad_req == "write"
        assert data._ag_entry is not None and data._ag_entry.variable is data
        assert data._grad is autograd.UNMADE
    assert _made() == (0, 0)
    net(x)                               # a forward asks for nothing either
    assert _made() == (0, 0)


def test_grad_gives_zeros_and_the_same_array_ever_after():
    telemetry.reset()
    net, _ = _net()
    p = net[0].weight
    g = p.grad()
    assert g.shape == p.shape and g.dtype == p.data().dtype
    assert g.context == p.data().context
    assert not g.asnumpy().any()
    assert p.grad() is g and p.list_grad()[0] is g and p.data().grad is g
    assert _made() == (1, 8 * 6 * 4)
    assert net[1].bias.data().grad is net[1].bias.grad()    # data first
    assert _made() == (2, 8 * 6 * 4 + 5 * 4)


def test_a_parameter_no_gradient_reaches_raises_and_has_no_buffer():
    telemetry.reset()
    net, x = _net()
    frozen = gluon.Parameter("frozen", grad_req="null", shape=(3,))
    frozen.initialize()
    weight = net[0].weight
    held = weight.grad()
    weight.grad_req = "null"
    for p in (frozen, weight):
        with pytest.raises(MXNetError, match="grad_req='null'"):
            p.grad()
        with pytest.raises(MXNetError, match="grad_req='null'"):
            p.list_grad()
        assert p.data().grad is None
        p.zero_grad()
    _backward(net, x)                     # fills the three others alone
    assert weight.data().grad is None and not held.asnumpy().any()
    assert _made() == (1 + 3, (48 + 8 + 40 + 5) * 4)
    weight.grad_req = "write"             # and back: a new buffer, of zeros
    assert weight.data()._grad is autograd.UNMADE
    assert weight.grad() is not held and not weight.grad().asnumpy().any()


CHANGES = {
    "zero_grad": lambda net, p: p.zero_grad(),
    "cast": lambda net, p: net.cast("float16"),
    "reset_ctx": lambda net, p: net.collect_params().reset_ctx(
        [mx.cpu(1), mx.cpu(2)]),
    "grad_req": lambda net, p: setattr(p, "grad_req", "add"),
    "set_data": lambda net, p: p.set_data(nd.ones(p.shape)),
}


@pytest.mark.parametrize("made", [False, True], ids=["unmade", "made"])
@pytest.mark.parametrize("change", list(CHANGES))
def test_a_change_to_the_parameter_before_and_after_the_buffer_exists(
        change, made):
    """What MXNet's eager allocation gave: after ``zero_grad`` or
    ``set_data`` the same buffer (zeros after the first), after ``cast``,
    ``reset_ctx`` or another ``grad_req`` a new one of zeros in the new
    dtype, on the new contexts or in the new mode; and a backward fills it
    either way."""
    net, x = _net()
    p = net[0].weight
    before = None
    if made:
        _backward(net, x)
        before = p.grad()
        assert before.asnumpy().any()
    CHANGES[change](net, p)
    g = p.grad()
    assert p.data().grad is g and p.grad() is g
    if change == "set_data":
        assert made == (g is before) and made == bool(g.asnumpy().any())
    else:
        assert not g.asnumpy().any()
        assert (g is before) == (made and change == "zero_grad")
    assert g.dtype == p.data().dtype == np.dtype(
        "float16" if change == "cast" else "float32")
    assert [g.context for g in p.list_grad()] == [
        d.context for d in p.list_data()]
    assert len(p.list_grad()) == (2 if change == "reset_ctx" else 1)
    assert p.data()._ag_entry.grad_req == p.grad_req == (
        "add" if change == "grad_req" else "write")
    _backward(net, x.astype("float16") if change == "cast" else x)
    assert p.grad() is g and g.asnumpy().any()


@pytest.mark.parametrize("asked", ["grad", "backward"])
def test_two_contexts_have_a_buffer_each(asked):
    telemetry.reset()
    ctxs = [mx.cpu(0), mx.cpu(1)]
    p = gluon.Parameter("w", shape=(3, 2))
    p.initialize(mx.init.One(), ctx=ctxs)
    assert _made() == (0, 0)
    if asked == "backward":
        for i, c in enumerate(ctxs):
            with autograd.record():
                y = (p.data(c) * (i + 2.0)).sum()
            y.backward()
        assert [g.asnumpy()[0, 0] for g in p.list_grad()] == [2.0, 3.0]
    else:
        assert p.grad(ctxs[1]) is p.list_grad()[1]
        assert not any(g.asnumpy().any() for g in p.list_grad())
    assert _made() == (2, 2 * 24)
    assert [g.context for g in p.list_grad()] == ctxs
    assert p.grad() is p.grad(ctxs[0]) is p.data(ctxs[0]).grad
    with pytest.raises(KeyError):
        p.grad(mx.cpu(2))


def _train(grad_req, touch_first):
    """Three steps of the imperative loop; the weights after them."""
    telemetry.reset()
    mx.random.seed(7)
    net, x = _net(grad_req)
    params = net.collect_params()
    if touch_first:
        for p in params.values():
            p.grad()
    trainer = gluon.Trainer(params, "sgd", {"learning_rate": 0.1,
                                            "momentum": 0.9})
    for _ in range(3):
        _backward(net, x)
        if grad_req == "add":             # a second backward adds to it
            _backward(net, x * 0.5)
        trainer.step(4)
        if grad_req == "add":
            params.zero_grad()
    return [p.data().asnumpy() for p in params.values()], _made()


@pytest.mark.parametrize("grad_req", ["write", "add"])
def test_the_imperative_loop_trains_as_with_buffers_made_at_the_start(
        grad_req):
    """Bit for bit the weights of the same loop with every ``grad()``
    touched before the first backward (MXNet 1.x's allocation), and a buffer
    a trained parameter, no more."""
    lazy, made = _train(grad_req, touch_first=False)
    eager, made_first = _train(grad_req, touch_first=True)
    assert made == made_first == (4, 101 * 4)
    for a, b in zip(lazy, eager):
        np.testing.assert_array_equal(a, b)
    start = _net(grad_req)[0].collect_params()
    assert any((a != p.data().asnumpy()).any()
               for a, p in zip(lazy, start.values()))


def test_a_weight_used_twice_in_one_backward_adds_up_from_the_first():
    """``"write"`` within one backward: the second use adds to the first,
    where the buffer was made by that first use too."""
    p = gluon.Parameter("w", shape=(3,))
    p.initialize(mx.init.One())
    with autograd.record():
        y = (p.data() * 2.0).sum() + (p.data() * p.data()).sum()
    y.backward()
    np.testing.assert_array_equal(p.grad().asnumpy(), [4.0, 4.0, 4.0])
    with autograd.record():
        y = (p.data() * 5.0).sum()
    y.backward()                          # and the next backward overwrites
    np.testing.assert_array_equal(p.grad().asnumpy(), [5.0, 5.0, 5.0])


def test_autograd_grad_leaves_the_parameter_as_it_found_it():
    telemetry.reset()
    net, x = _net()
    w = net[1].weight.data()
    with autograd.record():
        loss = (net(x) ** 2).mean()
    (g,) = autograd.grad(loss, [w], retain_graph=True)
    assert g.shape == w.shape and g.asnumpy().any()
    # (the backward it runs fills the other three parameters, as ever)
    assert w._grad is autograd.UNMADE and _made() == (3, (48 + 8 + 5) * 4)
    loss.backward()
    np.testing.assert_array_equal(net[1].weight.grad().asnumpy(),
                                  g.asnumpy())


def test_attach_grad_on_a_users_array_allocates_at_once():
    telemetry.reset()
    x = nd.array([1.0, 2.0])
    x.attach_grad()
    assert x._grad is not autograd.UNMADE and x.grad is x._grad
    assert not x.grad.asnumpy().any() and _made() == (0, 0)


def test_a_fused_run_makes_no_buffer_and_a_backward_after_it_still_does():
    """``TrainStep`` takes its gradients inside the executable: after two
    steps both counters read 0 and the arrays of a weight's shape are the
    net's copy, the master copy and the optimizer's two moments; the Gluon
    path on the same net afterwards fills ``grad()`` as ever."""
    telemetry.reset()
    net, x = _net()
    gc.collect()
    before = {id(a) for a in jax.live_arrays()}
    step = TrainStep(net, lambda out, y: (out ** 2).mean(-1),
                     optimizer="adam",
                     optimizer_params={"learning_rate": 1e-3})
    y = np.zeros((4,), "int32")
    for _ in range(2):
        assert np.isfinite(float(step(x.asnumpy(), y)))
    assert _made() == (0, 0)
    gc.collect()
    shape = net[0].weight.shape
    assert len([a for a in jax.live_arrays() if a.shape == shape]) == 4
    new = [a for a in jax.live_arrays()
           if id(a) not in before and a.shape == shape]
    assert len(new) == 3                  # master copy, m and v
    for p in net.collect_params().values():
        assert p.data()._grad is autograd.UNMADE
    _backward(net, x)
    assert _made() == (4, 101 * 4)
    assert all(p.grad().asnumpy().any()
               for p in net.collect_params().values())
