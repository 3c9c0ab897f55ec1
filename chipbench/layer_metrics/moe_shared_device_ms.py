"""Expert layer (``model_zoo/language/llama.py::LlamaMoEMLP``, the shared
expert under ``jax.named_scope("mx_moe_shared")``): device milliseconds a
step in ops whose own scope holds that name, forward, backward and what the
checkpoints compute again, every instant counted once.  None where the
step's table holds no such scope (a program without a shared expert)."""


def read(ctx):
    from chipbench.layer_metrics import _moe

    return _moe.scope_ms(ctx, "mx_moe_shared")
