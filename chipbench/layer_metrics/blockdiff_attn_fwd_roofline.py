"""Attention kernel (``ops/flash_attention.py``, the Pallas kernel named
``mxnet_flash_attention_fwd``) under the block-diffusion mask: the least
time the chip could take for the kernel's calls in the traced window, over
the time they took.  The least time is the larger of the operations of the
pairs the mask shows (4 x heads x pairs x head size a sample) over the bf16
peak and the bytes of q, k, v, o and the log-sum-exp over the HBM peak, from
the configuration's ``counts.py``; the kernel computes whole tiles."""

KERNEL = "mxnet_flash_attention_fwd"


def read(ctx):
    from chipbench.harness import trace

    cfg, cell = ctx["cfg"], ctx["cell"]
    counts = getattr(ctx["build"], "counts", None)
    block = cfg.get("assumed", {}).get("block_length")
    if not block or counts is None or "seq" not in cell:
        return None
    found = trace.kernel_events(ctx["trace"], ctx["window"], KERNEL)
    calls = sum(len(v) for v in found.values())
    taken = sum(dur for v in found.values() for _, _, dur in v)
    if not calls:
        return None
    samples = cell["batch"] // ctx["chips"]
    compute = samples * counts.attention_fwd_flops(cfg, cell["seq"], block) \
        / ctx["peaks"]["flops_bf16"]
    memory = samples * counts.attention_fwd_bytes(cfg, cell["seq"], 2) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    bound = "compute" if compute >= memory else "memory"
    print(f"chipbench: {KERNEL} (block diffusion): {calls} calls, "
          f"{taken / calls * 1e6:.1f} us a call, least "
          f"{max(compute, memory) * 1e6:.1f} us ({bound} bound)", flush=True)
    return max(compute, memory) * calls / taken * 100.0
