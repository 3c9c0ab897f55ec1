"""Attention kernel (``ops/flash_attention.py::_fa_backward_blockwise``
under ``jax.named_scope("mxnet_flash_attention_bwd")``): device
milliseconds a step in ops whose own scope holds that name, whatever else
they fuse, every instant counted once."""


def read(ctx):
    from chipbench.layer_metrics import _scopes

    got = _scopes.split(ctx)
    return got and got["attention_bwd"]
