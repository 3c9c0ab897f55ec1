"""Transformer block (every norm of a block under
``jax.named_scope("mx_norm")``, BERT's head's own apart; ``F.rope`` on q and k
and the positions' arithmetic under ``"mx_rope"``): device milliseconds a
step in ops the program's table resolves to either part; ``_parts.split``
prints the two apart."""


def read(ctx):
    from chipbench.layer_metrics import _parts

    return _parts.part_ms(ctx, "mx_norm", "mx_rope")
