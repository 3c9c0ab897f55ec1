"""ResNet-18's layout and stem variants (NHWC, the space-to-depth stem) and
``TrainStep(remat=True)`` on it: the zoo's other families are
``test_model_zoo.py``'s (one file until PR 41, which split it by subject for
tier-1's ``--dist loadfile``)."""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import vision


def test_resnet_nhwc_matches_nchw():
    """layout='NHWC' (TPU-preferred channel-last) computes the same function
    as the reference NCHW layout: transpose inputs + remap conv weights
    OIHW->OHWI and outputs must agree."""
    net1 = vision.resnet18_v1()
    net1.initialize()
    x = mx.nd.array(np.random.RandomState(0).uniform(
        -1, 1, (2, 3, 32, 32)).astype("f"))
    y1 = net1(x)

    net2 = vision.resnet18_v1(layout="NHWC")
    net2.initialize()
    xt = mx.nd.transpose(x, (0, 2, 3, 1))
    net2(xt)  # settle deferred shapes
    p1, p2 = net1.collect_params(), net2.collect_params()
    for (k1, v1), (k2, v2) in zip(p1.items(), p2.items()):
        a = v1.data().asnumpy()
        if a.ndim == 4:  # conv weight OIHW -> OHWI
            a = a.transpose(0, 2, 3, 1)
        assert a.shape == tuple(v2.shape), (k1, k2, a.shape, v2.shape)
        v2.set_data(mx.nd.array(a))
    y2 = net2(xt)
    assert np.allclose(y1.asnumpy(), y2.asnumpy(), atol=1e-3), \
        np.abs(y1.asnumpy() - y2.asnumpy()).max()


def test_resnet_nhwc_trains():
    """NHWC network runs fwd+bwd under hybridize (the bench path)."""
    from mxnet_tpu import autograd

    net = vision.resnet18_v1(layout="NHWC", thumbnail=True)
    net.initialize()
    net.hybridize()
    x = mx.nd.ones((2, 32, 32, 3))
    with autograd.record():
        y = net(x)
        loss = y.sum()
    loss.backward()
    w = [p for p in net.collect_params().values()
         if p.grad_req != "null"][0]
    assert np.isfinite(w.grad().asnumpy()).all()


def test_resnet_s2d_stem_trains_and_matches_shapes():
    """The space-to-depth stem variant (PERF_NOTES escalation step 3)
    produces the same feature-map ladder as conv7 and takes gradient
    steps in both layouts."""
    from mxnet_tpu import autograd

    for layout in ("NCHW", "NHWC"):
        net = vision.resnet18_v1(classes=10, layout=layout, stem="s2d")
        net.initialize()
        shape = (2, 64, 64, 3) if layout == "NHWC" else (2, 3, 64, 64)
        x = mx.nd.array(np.random.RandomState(0).randn(*shape).astype("f"))
        with autograd.record():
            y = net(x)
            loss = (y * y).mean()
        loss.backward()
        assert y.shape == (2, 10)
        assert np.isfinite(y.asnumpy()).all()
        ref = vision.resnet18_v1(classes=10, layout=layout)
        ref.initialize()
        assert ref(x).shape == y.shape


def test_trainstep_remat_preserves_numerics():
    """TrainStep(remat=True) (escalation step 2) is numerics-preserving:
    identical loss trajectory to the non-remat step."""
    from mxnet_tpu.parallel.data_parallel import TrainStep

    def loss_fn(logits, labels):
        import jax
        import jax.numpy as jnp

        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1)

    rs = np.random.RandomState(1)
    x = rs.randn(4, 16, 16, 3).astype("f")
    y = rs.randint(0, 10, (4,)).astype("i")
    traj = {}
    w0 = None
    for remat in (False, True):
        net = vision.resnet18_v1(classes=10, layout="NHWC")
        net.initialize()
        net(mx.nd.zeros((1, 16, 16, 3)))
        # param names carry global layer counters that differ between
        # instances; construction order is the stable correspondence
        plist = list(net.collect_params().values())
        if w0 is None:
            w0 = [q.data().asnumpy() for q in plist]
        else:
            for q, v in zip(plist, w0):
                q.set_data(mx.nd.array(v))
        step = TrainStep(net, loss_fn, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1},
                         remat=remat)
        traj[remat] = [float(np.asarray(step(x, y))) for _ in range(3)]
    # the FIRST loss is computed before any remat-affected gradient ever
    # touched the weights: both programs run the same forward, so it must
    # match exactly — this is the systematic-error detector
    assert traj[True][0] == traj[False][0], (traj[True][0], traj[False][0])
    # the tail tolerance is pinned loose DELIBERATELY: jax.checkpoint
    # recomputes the forward inside the backward and XLA re-fuses that
    # recompute, so gradients differ at float32-reassociation level
    # (~1e-7 per op); each optimizer step compounds it through a
    # divergent lr=0.1 trajectory, and on the CPU mesh the observed drift
    # reaches ~2e-4 by step 3.  rtol=1e-5 here was a flake generator,
    # not a correctness bar — remat is numerics-preserving up to float
    # reassociation, never bitwise across step boundaries.
    np.testing.assert_allclose(traj[True], traj[False], rtol=5e-3)


def test_s2d_stem_channel_order_matches_across_layouts():
    """_SpaceToDepthInput emits the SAME (bh, bw, c) channel interleave in
    both layouts (NCHW delegates to the registered space_to_depth op), so
    the standard OIHW<->OHWI stem-weight remap stays valid for stem='s2d'
    nets (review finding r5)."""
    from mxnet_tpu.gluon.model_zoo.vision.resnet import _SpaceToDepthInput

    rs = np.random.RandomState(0)
    x_cf = rs.randn(2, 3, 8, 8).astype("f")
    a = _SpaceToDepthInput(layout="NCHW")
    a.initialize()
    b = _SpaceToDepthInput(layout="NHWC")
    b.initialize()
    y_cf = a(mx.nd.array(x_cf)).asnumpy()
    y_cl = b(mx.nd.array(x_cf.transpose(0, 2, 3, 1))).asnumpy()
    np.testing.assert_allclose(y_cl.transpose(0, 3, 1, 2), y_cf)
