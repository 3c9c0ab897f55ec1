"""Fused step: the share of device busy time in ops that the program's
table resolves to no one part: ``""`` (no scope of the program names them:
the compiler's copies and fills, ops whose metadata it dropped) and
``mixed`` (a fusion across several parts) together.  It is the honesty of
every ``*_device_ms`` by part; ``_parts.split`` prints what it holds by
name."""


def read(ctx):
    from chipbench.layer_metrics import _parts

    got = _parts.split(ctx)
    if not got or not got["busy"]:
        return None
    unnamed = sum(got["parts"].get(part, 0.0) for part in _parts.UNNAMED)
    return unnamed / got["busy"] * 100.0
