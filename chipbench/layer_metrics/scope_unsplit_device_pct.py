"""Fused step: the share of device busy time that ``fwd_device_ms``,
``bwd_device_ms`` and ``optimizer_device_ms`` leave out: ops with no class,
with more than one (a fusion across the parts), or not in the program's
table (another program's).  It is the honesty of those three; what it
holds is printed by name."""


def read(ctx):
    from chipbench.layer_metrics import _scopes

    got = _scopes.split(ctx)
    if not got or not got["busy"]:
        return None
    named = sum(got[c] for c in _scopes.CLASSES)
    return (1.0 - named / got["busy"]) * 100.0
