"""What the selective scan's two roofline readers share: the least time the
chip could take for a walk of every state-space layer's scan (the larger of
``counts.ssm_scan_<way>_flops`` over the bf16 peak and
``counts.ssm_scan_<way>_bytes`` over the HBM peak; the work is element-wise,
so the bytes bound it, and a low share says the vector unit sets the pace)
over the device time of the ops under that walk's scope.  One walk a layer,
and one more where the table shows the layers' checkpoints walking it again.
None where the configuration counts no scan or the table holds no op of the
scope."""
from __future__ import annotations

AGAIN = "rematted_computation"


def share(ctx, way):
    from chipbench.layer_metrics import _moe, _roofline, _scopes

    cfg, cell = ctx["cfg"], ctx["cell"]
    counts = getattr(ctx["build"], "counts", None)
    if not hasattr(counts, f"ssm_scan_{way}_flops") or "seq" not in cell:
        return None
    scope = f"mxnet_selective_scan_{way}"
    layers = counts.layer_kinds(cfg).count("ssm")
    taken = _moe.scope_ms(ctx, scope)
    if not layers or not taken:
        return None
    again = any(scope in row["scope"] and AGAIN in row["scope"]
                for row in _scopes.step_table().values())
    return _roofline.share(
        ctx, scope + " (walks a step)", layers * (2 if again else 1),
        taken * 1e-3, getattr(counts, f"ssm_scan_{way}_flops")(
            cfg, cell["seq"]),
        getattr(counts, f"ssm_scan_{way}_bytes")(cfg, cell["seq"], 2))
