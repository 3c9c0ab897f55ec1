"""Attention kernel (``ops/flash_attention.py``, the Pallas kernel named
``mxnet_flash_attention_fwd``): the least time the chip could take for the
kernel's calls in the traced window, over the time they took.  The least
time is the larger of operations over the bf16 peak and bytes over the HBM
peak, from ``harness/counts.py`` and the call's shape: the chip's rows
times the heads, the sequence, the head size, bfloat16."""

KERNEL = "mxnet_flash_attention_fwd"


def read(ctx):
    from chipbench.harness import counts, trace

    cfg, cell = ctx["cfg"], ctx["cell"]
    if "num_attention_heads" not in cfg or "seq" not in cell:
        return None
    found = trace.kernel_events(ctx["trace"], ctx["window"], KERNEL)
    calls = sum(len(v) for v in found.values())
    taken = sum(dur for v in found.values() for _, _, dur in v)
    if not calls:
        return None
    heads = cfg["num_attention_heads"]
    shape = (cell["batch"] // ctx["chips"] * heads, cell["seq"], cell["seq"],
             cfg["hidden_size"] // heads)
    compute = counts.flash_fwd_flops(*shape) / ctx["peaks"]["flops_bf16"]
    memory = counts.flash_fwd_bytes(*shape, 2) / ctx["peaks"]["hbm_bytes_per_s"]
    bound = "compute" if compute >= memory else "memory"
    print(f"chipbench: {KERNEL}: {calls} calls, {taken / calls * 1e6:.1f} us "
          f"a call, least {max(compute, memory) * 1e6:.1f} us ({bound} bound)",
          flush=True)
    return max(compute, memory) * calls / taken * 100.0
