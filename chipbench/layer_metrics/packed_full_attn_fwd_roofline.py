"""Attention kernel (``ops/flash_attention.py``, the Pallas forward under
``causal=True`` and segment ids, named
``mxnet_flash_attention_fwd_segments``): the full layers' calls over packed
documents against the pairs their documents show (``_packed.py``)."""

KERNEL = "mxnet_flash_attention_fwd_segments"


def read(ctx):
    from chipbench.layer_metrics import _packed

    return _packed.fwd_roofline(ctx, KERNEL, "full_attention")
