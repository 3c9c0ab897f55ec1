"""Collectives (the gradients' all-reduce that XLA adds to a fused step over
``dp``): device milliseconds a step a chip in ``all-reduce`` ops on the
``XLA Ops`` line, the asynchronous ones' ``-start`` and ``-done`` ops alike,
every instant given to the innermost op that covers it.  A ``-done`` op runs
for as long as the chip waits for the transfer, so this is the part of the
all-reduce that no compute hides; what overlaps compute lies on the ``Async
XLA Ops`` line and is not counted.  None on one chip's trace, which holds no
such op."""

OPS = "all-reduce"


def read(ctx):
    from chipbench.harness import trace
    from chipbench.layer_metrics import _scopes

    devices = ctx["trace"]["devices"].values()
    seconds = [s for dev in devices for name, s in _scopes.self_seconds(
        trace.clip(dev["ops"], *ctx["window"])).items()
        if name.startswith(OPS)]
    if not seconds or not ctx["steps"]:
        return None
    return sum(seconds) * 1e3 / len(devices) / ctx["steps"]
