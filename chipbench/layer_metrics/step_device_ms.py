"""Fused step: device busy milliseconds a step (the union of the ``XLA
Ops`` intervals in the traced window, averaged over chips, over the steps
completed inside it)."""


def read(ctx):
    if not ctx["steps"]:
        return None
    return ctx["summary"]["busy_s"] / ctx["steps"] * 1e3
