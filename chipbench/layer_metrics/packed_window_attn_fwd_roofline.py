"""Attention kernel (``ops/flash_attention.py``, the Pallas forward under
``mask="window"`` and segment ids, named
``mxnet_flash_attention_fwd_window_segments``): the sliding layers' calls
over packed documents against the pairs the band shows inside their
documents (``_packed.py``)."""

KERNEL = "mxnet_flash_attention_fwd_window_segments"


def read(ctx):
    from chipbench.layer_metrics import _packed

    return _packed.fwd_roofline(ctx, KERNEL, "sliding_attention")
