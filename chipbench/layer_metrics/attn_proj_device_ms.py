"""Transformer block (``model_zoo/language/llama.py::LlamaAttention``,
``bert.py::BertSelfAttention`` under ``jax.named_scope("mx_attn_proj")``: the
q / k / v / o projections, the attention gate's projection and sigmoid, the
head reshapes and transposes beside them): device milliseconds a step in
ops the program's table resolves to that part, forward, backward and
recomputation alike, every instant counted once."""


def read(ctx):
    from chipbench.layer_metrics import _parts

    return _parts.part_ms(ctx, "mx_attn_proj")
