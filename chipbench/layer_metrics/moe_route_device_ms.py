"""Expert layer (``parallel/expert_parallel.py``, router, top-k, sort, gather
and scatter under ``jax.named_scope("mx_moe_route")``): device milliseconds a
step in ops whose own scope holds that name, forward, backward and what the
checkpoints compute again, every instant counted once."""


def read(ctx):
    from chipbench.layer_metrics import _moe

    return _moe.scope_ms(ctx, "mx_moe_route")
