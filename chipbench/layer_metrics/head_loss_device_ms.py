"""Transformer block (the head under ``jax.named_scope("mx_head")``:
``lm_head`` with the block-diffusion slice; BERT's MLM transform, decoder,
pooler and NSP; and ``TrainStep``'s call of the loss under ``"mx_loss"``: the
cast of the outputs to float32, the caller's loss function, its mean):
device milliseconds a step in ops the program's table resolves to either
part; ``_parts.split`` prints the two apart."""


def read(ctx):
    from chipbench.layer_metrics import _parts

    return _parts.part_ms(ctx, "mx_head", "mx_loss")
