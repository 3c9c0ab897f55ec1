"""Attention kernel (``ops/flash_attention.py``, the Pallas causal forward
of a latent-attention layer: q and k of 192 a head, padded to 256 for the
kernel's tiles, v and the output of 128; named ``mxnet_flash_attention_fwd``
like every causal call without a window or ids, of which this configuration
has no other): the least time the chip could take for those calls of the
traced window, over the time they took.  The least time is the larger of
the operations of the causal pairs (``2 x heads x pairs x (192 + 128)`` a
sample) over the bf16 peak and the bytes of q, k, v, o and the log-sum-exp
over the HBM peak, from the configuration's ``counts.py``; the kernel
computes whole tiles and the padded width.  None where the configuration
has no latent attention or the trace no kernel of that name."""

KERNEL = "mxnet_flash_attention_fwd"


def read(ctx):
    from chipbench.harness import trace
    from chipbench.layer_metrics import _roofline

    cfg, cell = ctx["cfg"], ctx["cell"]
    counts = getattr(ctx["build"], "counts", None)
    if not hasattr(counts, "mla_attention_fwd_flops") or "seq" not in cell:
        return None
    found = trace.kernel_events(ctx["trace"], ctx["window"], KERNEL)
    calls = sum(len(v) for v in found.values())
    if not calls:
        return None
    return _roofline.share(
        ctx, KERNEL + " (latent)", calls,
        sum(dur for v in found.values() for _, _, dur in v),
        counts.mla_attention_fwd_flops(cfg, cell["seq"]),
        counts.mla_attention_fwd_bytes(cfg, cell["seq"], 2))
