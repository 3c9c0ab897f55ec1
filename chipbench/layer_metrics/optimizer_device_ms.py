"""Fused step (the update under ``jax.named_scope("mx_optimizer")``): device
milliseconds a step in ops whose only class is ``optimizer``.  An update
that the compiler fused into a weight gradient's matmul is not here but in
``scope_unsplit_device_pct``."""


def read(ctx):
    from chipbench.layer_metrics import _scopes

    got = _scopes.split(ctx)
    return got and got["optimizer"]
