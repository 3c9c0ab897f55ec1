"""State-space mixer (``ops/selective_scan.py``, the hand-written backward of
the selective scan, every op of it under the scope
``mxnet_selective_scan_bwd``: a chunk's states again, then its rows from the
last): the least time the chip could take for the backward walks of a step,
the walk forward again not counted, over the device time they took
(``_ssm.share``)."""


def read(ctx):
    from chipbench.layer_metrics import _ssm

    return _ssm.share(ctx, "bwd")
