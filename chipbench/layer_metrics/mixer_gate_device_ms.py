"""Delta-rule mixer (``model_zoo/language/llama.py`` under
``jax.named_scope("mx_mixer_gate")``: what stands around a mixer's core, a
Kimi-delta-attention layer's short convolutions with SiLU, the l2 norm of q
and k, decay and beta and the gated norm of the delta rule's output, and
latent attention's gate a head): device milliseconds a step in ops the
program's table resolves to that part, forward, recomputation and backward
alike.  None where the step has no such part."""


def read(ctx):
    from chipbench.layer_metrics import _parts

    return _parts.part_ms(ctx, "mx_mixer_gate")
