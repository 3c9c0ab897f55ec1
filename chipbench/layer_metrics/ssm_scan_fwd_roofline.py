"""State-space mixer (``ops/selective_scan.py``, the forward walk of the
selective scan, every op of it under the scope ``mxnet_selective_scan_fwd``:
the Pallas kernel of that name and the copies around it, or the scan of
scans): the least time the chip could take for the forward walks of a step,
over the device time they took (``_ssm.share``)."""


def read(ctx):
    from chipbench.layer_metrics import _ssm

    return _ssm.share(ctx, "fwd")
