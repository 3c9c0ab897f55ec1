"""Fused step (``parallel/data_parallel.py::TrainStep``): model FLOP/s
utilization, the operations the forward and backward passes require (the
benchmark's own count, two a multiply-add, nothing recomputed) times the
samples a second a chip of the run's untraced window, over the chip's bf16
peak."""


def read(ctx):
    if not hasattr(ctx["build"], "train_flops_per_sample") or not ctx["steps"]:
        return None
    flops = ctx["build"].train_flops_per_sample(ctx["cfg"], ctx["cell"])
    return (flops * ctx["samples_per_s_per_chip"]
            / ctx["peaks"]["flops_bf16"] * 100.0)
