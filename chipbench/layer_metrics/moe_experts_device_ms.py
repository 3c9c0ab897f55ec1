"""Expert layer (``parallel/expert_parallel.py``, the grouped products under
``jax.named_scope("mx_moe_experts")``): device milliseconds a step in ops
whose own scope holds that name and in the grouped products themselves
(``_moe.py`` says how the table shows them), forward, backward and what the
checkpoints compute again, every instant counted once."""


def read(ctx):
    from chipbench.layer_metrics import _moe

    return _moe.scope_ms(ctx, "mx_moe_experts", grouped=True)
