"""Drives ``parallel.data_parallel.TrainStep`` over a mesh of the cell's
chips: ``fused_step``'s runner with ``TrainStep(mesh=make_mesh())``, every
chip on ``dp``, the weights replicated and the batch split over ``dp``
(XLA adds the gradients' all-reduce; the Pallas attention kernels run per
batch shard).  What the check reads is read as on one chip."""
from __future__ import annotations

import jax

from chipbench.drivers import fused_step


class Runner(fused_step.Runner):
    """The compiled step with its state, over ``cell["chips"]`` chips."""

    def __init__(self, cell, cfg, build, weights):
        import mxnet_tpu as mx
        from mxnet_tpu.ndarray.ndarray import NDArray
        from mxnet_tpu.parallel.data_parallel import TrainStep
        from mxnet_tpu.parallel.mesh import make_mesh

        ctx = mx.current_context()
        self._net = net = build.build_net(cfg, ctx)
        self.names = build.leaf_names(cfg, net)
        params = net.collect_params()
        for leaf, name in self.names.items():
            params[name].set_data(NDArray._from_jax(weights[leaf], ctx))
        self._hyper = cell["optimizer_params"]
        self._step = TrainStep(
            net, build.step_loss, optimizer=cell["optimizer"],
            optimizer_params=cell["optimizer_params"], train_mode=True,
            dtype=cell.get("amp_dtype"), batch_axes=("dp",),
            mesh=make_mesh(devices=jax.devices()[:cell["chips"]]))

    def change_sq_norms(self):
        """As on one chip, from the first chip's copy of each leaf (the
        net's initial values live there and every chip holds the same)."""
        now = {n: self._step.train_params[n].addressable_data(0)
               for n in self.names.values()}
        params = self._net.collect_params()
        then = {n: params[n].data()._get() for n in now}
        got = jax.device_get(fused_step._sq_gaps(now, then))
        return {leaf: float(got[name]) for leaf, name in self.names.items()}
