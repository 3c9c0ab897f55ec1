"""Attention kernel (``ops/flash_attention.py``, the Pallas forward under
``mask="window"``, named ``mxnet_flash_attention_fwd_window``): the least
time the chip could take for the window calls of the traced window, over
the time they took.  The least time is the larger of the operations of the
pairs the band shows (4 x heads x pairs x head size a sample) over the bf16
peak and the bytes of q, k, v, o and the log-sum-exp over the HBM peak, from
the configuration's ``counts.py``; the kernel computes whole tiles.  None
where the trace holds no kernel of that name (a program without the
window)."""

KERNEL = "mxnet_flash_attention_fwd_window"
KIND = "sliding_attention"


def read(ctx):
    from chipbench.harness import trace

    cfg, cell = ctx["cfg"], ctx["cell"]
    counts = getattr(ctx["build"], "counts", None)
    if "sliding_window" not in cfg or counts is None or "seq" not in cell:
        return None
    found = trace.kernel_events(ctx["trace"], ctx["window"], KERNEL)
    calls = sum(len(v) for v in found.values())
    taken = sum(dur for v in found.values() for _, _, dur in v)
    if not calls:
        return None
    samples = cell["batch"] // ctx["chips"]
    compute = samples * counts.attention_fwd_flops(cfg, cell["seq"], KIND) \
        / ctx["peaks"]["flops_bf16"]
    memory = samples * counts.attention_fwd_bytes(cfg, cell["seq"], 2) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    bound = "compute" if compute >= memory else "memory"
    print(f"chipbench: {KERNEL}: {calls} calls, {taken / calls * 1e6:.1f} us "
          f"a call, least {max(compute, memory) * 1e6:.1f} us ({bound} "
          "bound)", flush=True)
    return max(compute, memory) * calls / taken * 100.0
