"""Delta-rule mixer (``ops/kda.py``, the forward pass of the chunked gated
delta rule, every op of it under the scope ``mxnet_kda_fwd``): the least
time the chip could take for the forward passes of a step, over the device
time they took.  A pass's least time is the larger of its operations
(``counts.kda_fwd_flops``: the decayed products, the triangular solve, the
state and output products of every chunk) over the bf16 peak and its bytes
(``counts.kda_fwd_bytes``: q, k, v, o, the float32 decay, beta and the
chunk states) over the HBM peak; the passes of a step are one a
delta-rule layer, and one more each where the table shows the layers'
checkpoints computing it again.  None where the table holds no op of that
scope (a program without the op) or the configuration counts no such
layer."""

SCOPE = "mxnet_kda_fwd"
AGAIN = "rematted_computation"


def read(ctx):
    from chipbench.layer_metrics import _moe, _roofline, _scopes

    cfg, cell = ctx["cfg"], ctx["cell"]
    counts = getattr(ctx["build"], "counts", None)
    if not hasattr(counts, "kda_fwd_flops") or "seq" not in cell:
        return None
    layers = counts.layer_kinds(cfg).count("kda")
    taken = _moe.scope_ms(ctx, SCOPE)
    if not layers or not taken:
        return None
    again = any(SCOPE in row["scope"] and AGAIN in row["scope"]
                for row in _scopes.step_table().values())
    return _roofline.share(
        ctx, SCOPE + " (passes a step)", layers * (2 if again else 1),
        taken * 1e-3, counts.kda_fwd_flops(cfg, cell["seq"]),
        counts.kda_fwd_bytes(cfg, cell["seq"], 2))
