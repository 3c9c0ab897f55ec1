"""Transformer block (a dense layer's FFN under
``jax.named_scope("mx_ffn")``: ``LlamaDecoderLayer``'s SwiGLU where the layer
is dense, ``BertLayer``'s ``intermediate`` / GELU / ``output``): device
milliseconds a step in ops the program's table resolves to that part.  None
where the step has no dense layer (the shared expert is
``moe_shared_device_ms``'s)."""


def read(ctx):
    from chipbench.layer_metrics import _parts

    return _parts.part_ms(ctx, "mx_ffn")
