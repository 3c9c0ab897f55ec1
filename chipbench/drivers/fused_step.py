"""Drives ``parallel.data_parallel.TrainStep``: one fused executable a
step, on one chip.  The first gradient is read from Adam's state."""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def _sq_gaps(a, b):
    return {k: jnp.sum(jnp.square(a[k] - b[k])) for k in a}


class Runner:
    """The compiled step with its state.  Set-up drives it through its
    first steps and hands the same object to the window."""

    def __init__(self, cell, cfg, build, weights):
        import mxnet_tpu as mx
        from mxnet_tpu.ndarray.ndarray import NDArray
        from mxnet_tpu.parallel.data_parallel import TrainStep

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
            dtype=cell.get("amp_dtype"))

    def step(self, batch, span):
        """Dispatch one step; the loss comes back as a device scalar."""
        with span("dispatch_step"):
            return self._step(batch[0], batch[1])

    def first_gradient(self):
        """Each leaf's first gradient as the optimizer got it, on the host,
        from the optimizer's state after one step: Adam's first moment is
        (1 - beta1) g."""
        tree = self._step.opt_state["m"]
        scale = 1.0 - self._hyper.get("beta1", 0.9)
        got = jax.device_get({n: tree[n] for n in self.names.values()})
        return {leaf: got[name] / scale for leaf, name in self.names.items()}

    def change_sq_norms(self):
        """Squared norm of each leaf's change since the weights were set
        (the net keeps the initial values: the step works on a copy)."""
        now = {n: self._step.train_params[n] for n in self.names.values()}
        params = self._net.collect_params()
        then = {n: params[n].data()._get() for n in now}
        got = jax.device_get(_sq_gaps(now, then))
        return {leaf: float(got[name]) for leaf, name in self.names.items()}

    def compiles(self):
        """Batch signatures the step has compiled for."""
        return len(self._step._seen_sigs)
