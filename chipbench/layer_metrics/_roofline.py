"""What the roofline readers of PR 38 share: the least time the chip could
take for a call (the larger of its operations over the bf16 peak and its
bytes over the HBM peak, for the samples a chip holds) and the share of it
in the time taken, said on the way."""
from __future__ import annotations


def share(ctx, what, calls, seconds, flops, nbytes):
    """``calls`` calls of ``flops`` operations and ``nbytes`` bytes a sample
    took ``seconds`` in all: their least time over it, in percent."""
    samples = ctx["cell"]["batch"] // ctx["chips"]
    compute = samples * flops / ctx["peaks"]["flops_bf16"]
    memory = samples * nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    least = max(compute, memory)
    print(f"chipbench: {what}: {calls} calls, {seconds / calls * 1e6:.1f} us "
          f"a call, least {least * 1e6:.1f} us "
          f"({'compute' if compute >= memory else 'memory'} bound)",
          flush=True)
    return least * calls / seconds * 100.0
