"""Delta-rule mixer (``ops/kda.py`` under ``jax.named_scope("mx_kda")``, the
chunked gated delta rule of a Kimi-delta-attention layer: its forward,
``mxnet_kda_fwd``, what the checkpoints compute again, and its hand-written
backward, ``mxnet_kda_bwd``): device milliseconds a step in ops the
program's table resolves to that part.  None where the step has no such
layer (or the program no such scope)."""


def read(ctx):
    from chipbench.layer_metrics import _parts

    return _parts.part_ms(ctx, "mx_kda")
