"""What the packed-document attention readers share: a forward kernel's
share of its roofline when the row is documents packed end to end and the
kernel runs under their segment ids.  The least time is the larger of the
operations of the pairs the mask shows inside the documents (4 x heads x
pairs x head size a sample) over the bf16 peak and the bytes of q, k, v, o,
the log-sum-exp and the ids over the HBM peak, from the configuration's
``counts.py`` and the cell's document lengths; the kernel walks the tiles
the mask alone would, whole, so pairs of two documents are in the time and
not in the count.  None where the cell packs no documents or the trace
holds no kernel of that name (a program without segment ids)."""
from __future__ import annotations


def fwd_roofline(ctx, kernel, kind):
    from chipbench.harness import trace

    cfg, cell = ctx["cfg"], ctx["cell"]
    counts = getattr(ctx["build"], "counts", None)
    if "documents" not in cell or counts is None:
        return None
    found = trace.kernel_events(ctx["trace"], ctx["window"], kernel)
    calls = sum(len(v) for v in found.values())
    taken = sum(dur for v in found.values() for _, _, dur in v)
    if not calls:
        return None
    samples = cell["batch"] // ctx["chips"]
    compute = samples * counts.attention_fwd_flops(
        cfg, cell["documents"], kind) / ctx["peaks"]["flops_bf16"]
    memory = samples * counts.attention_fwd_bytes(cfg, cell["seq"], 2) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    bound = "compute" if compute >= memory else "memory"
    print(f"chipbench: {kernel}: {calls} calls, {taken / calls * 1e6:.1f} us "
          f"a call, least {max(compute, memory) * 1e6:.1f} us ({bound} "
          "bound)", flush=True)
    return max(compute, memory) * calls / taken * 100.0
