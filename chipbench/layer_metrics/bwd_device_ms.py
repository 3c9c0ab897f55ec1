"""Fused step: device milliseconds a step in ops whose only class is
``backward`` (JAX names the ops it derives from the forward
``transpose(jvp(mx_forward))``), every instant counted once."""


def read(ctx):
    from chipbench.layer_metrics import _scopes

    got = _scopes.split(ctx)
    return got and got["backward"]
