"""Transformer block (the embedding gathers and their scale under
``jax.named_scope("mx_embed")``, BERT's three and their sum; backward, the
scatter-add into the table's gradient): device milliseconds a step in ops
the program's table resolves to that part."""


def read(ctx):
    from chipbench.layer_metrics import _parts

    return _parts.part_ms(ctx, "mx_embed")
