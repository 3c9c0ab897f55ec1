"""State-space mixer (``ops/selective_scan.py`` under
``jax.named_scope("mx_ssm_scan")``, the selective scan of a state-space
layer: its forward walk, ``mxnet_selective_scan_fwd``, and its hand-written
backward, ``mxnet_selective_scan_bwd``, with the copies that lay the
channels out in blocks for the kernels): device milliseconds a step in ops
the program's table resolves to that part.  None where the step has no such
layer (or the program no such scope)."""


def read(ctx):
    from chipbench.layer_metrics import _parts

    return _parts.part_ms(ctx, "mx_ssm_scan")
