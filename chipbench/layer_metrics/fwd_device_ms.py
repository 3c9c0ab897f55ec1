"""Fused step (``TrainStep``'s forward under ``jax.named_scope("mx_forward")``):
device milliseconds a step in ops whose only class is ``forward`` (their
own ``op_name``, and those of every instruction they fuse, hold
``mx_forward`` and no ``transpose(``), every instant counted once."""


def read(ctx):
    from chipbench.layer_metrics import _scopes

    got = _scopes.split(ctx)
    return got and got["forward"]
