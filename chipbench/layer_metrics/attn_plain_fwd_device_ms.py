"""Attention (``ops/flash_attention.py::_mha_with_lse`` under
``jax.named_scope("mxnet_attention_plain_fwd")``, the forward of every call
the Pallas kernel's gate leaves out: rows under 256): device milliseconds a
step in ops whose own scope holds that name, through the op-to-scope table,
every instant counted once.  None where the step's table holds no such
scope (a step whose attention all takes the kernel)."""

SCOPE = "mxnet_attention_plain_fwd"


def read(ctx):
    from chipbench.layer_metrics import _moe

    return _moe.scope_ms(ctx, SCOPE)
