"""Attention kernel (``ops/flash_attention.py``, the Pallas causal forward of
differential attention at full length: q and k of 64 a head beside v and the
output of 128, a pair's values side by side; named
``mxnet_flash_attention_fwd``, which the window calls' name holds too, so
those are left out by their suffix): the least time the chip could take for
those calls of the traced window (the full layer's, and the cross layer's on
the full layer's K and V), over the time they took.  The least time is the
larger of the operations of the causal pairs (``2 x heads x pairs x (64 +
128)`` a sample) over the bf16 peak and the bytes of q, k, v, o and the
log-sum-exp over the HBM peak, from the configuration's ``counts.py``; the
kernel computes whole tiles.  None where the configuration counts no
differential attention or the trace holds no such kernel."""

KERNEL = "mxnet_flash_attention_fwd"
WINDOW = "_window"


def read(ctx):
    from chipbench.harness import trace
    from chipbench.layer_metrics import _roofline

    cfg, cell = ctx["cfg"], ctx["cell"]
    counts = getattr(ctx["build"], "counts", None)
    if not hasattr(counts, "diff_attention_fwd_flops") or "seq" not in cell:
        return None
    found = [dur for events in trace.kernel_events(
        ctx["trace"], ctx["window"], KERNEL).values()
        for name, _, dur in events if WINDOW not in name]
    if not found:
        return None
    return _roofline.share(
        ctx, KERNEL + " (differential, full length)", len(found), sum(found),
        counts.diff_attention_fwd_flops(cfg, cell["seq"]),
        counts.diff_attention_fwd_bytes(cfg, cell["seq"], 2))
