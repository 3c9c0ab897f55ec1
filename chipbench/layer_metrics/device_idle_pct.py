"""Device (TPU v5e): the share of the traced window in which no operation
ran on a chip, averaged over chips."""


def read(ctx):
    s = ctx["summary"]
    return (1.0 - s["busy_s"] / s["window_s"]) * 100.0
