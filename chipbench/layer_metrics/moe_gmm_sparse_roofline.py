"""Expert layer of a net whose leading layers are dense: the least time the
chip could take for a step's grouped products, over
``moe_experts_device_ms``.  As ``moe_gmm_roofline`` reads it, with the
layers that hold an expert layer (``counts.sparse_layers``) in place of
every layer: for the forward of one such layer the larger of the operations
of the pairs routed here (``mxnet_moe_routed_pairs_total``; 6 x hidden x
width a pair) over the bf16 peak and of the bytes (the held experts' weights
and each pair's token and result once, bfloat16) over the HBM peak; twice
that again for the backward; times the sparse layers.  What the checkpoints
compute again is in the time and not in the count.  None where the
configuration's ``counts.py`` has no ``sparse_layers`` beside the pairs'
operations and bytes (``moe_gmm_roofline`` is for those), or the program
has no such scope or counter."""


def read(ctx):
    from chipbench.layer_metrics import _moe

    cfg, counts = ctx["cfg"], getattr(ctx["build"], "counts", None)
    if not all(hasattr(counts, name) for name in (
            "sparse_layers", "expert_weight_bytes", "routed_pair_bytes")):
        return None
    taken = _moe.scope_ms(ctx, "mx_moe_experts", grouped=True)
    pairs = _moe.pairs_per_step(ctx)
    layers = counts.sparse_layers(cfg)
    if not taken or not pairs or not layers:
        return None
    per_layer = pairs / ctx["chips"] / layers
    compute = per_layer * counts.routed_pair_fwd_flops(cfg) \
        / ctx["peaks"]["flops_bf16"]
    memory = (counts.expert_weight_bytes(cfg, 2)
              + per_layer * counts.routed_pair_bytes(cfg, 2)) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    least_ms = 3 * layers * max(compute, memory) * 1e3
    bound = "compute" if compute >= memory else "memory"
    print(f"chipbench: grouped products: {per_layer:.0f} pairs a sparse "
          f"layer a step, least {least_ms:.3f} ms a step ({bound} bound), "
          f"taken {taken:.3f} ms", flush=True)
    return least_ms / taken * 100.0
