"""q/k norm and RoPE in one pass: from a projection as the matmul leaves it
to the attention kernels' operand.

``qk_norm_rope(x (b, l, heads * hd), [gamma (hd,)], [positions])`` gives
``(b, heads, l, hd)``: per head the RMS norm (``rms_norm``'s formula) and
the half-split turn (``rope``'s).  Where a TPU will run it and the shape
tiles (``_use_pallas``), one Pallas kernel reads each head's rows once,
normalises and turns them in float32, rounds once and writes the transposed
layout through its block map; its hand-written backward is one kernel of
the same grid that keeps nothing but the projection.  Everything else is
the composition of the registered ops, call for call.
"""
from __future__ import annotations

import functools

from .attention_ops import rms_norm, rope, rope_angles
from .registry import register

# the kernels' names, as a device trace and the op-to-scope table show them
KERNEL_FWD = "mxnet_qk_norm_rope_fwd"
KERNEL_BWD = "mxnet_qk_norm_rope_bwd"


def _use_pallas(x, hd):
    """Static gate for the kernels, read from the call as
    ``flash_attention._use_pallas`` reads it: a TPU to compile them for
    (JAX's default backend, not where ``x`` lives), heads of whole lane
    tiles, rows of whole tiles, and no mesh being traced over (GSPMD cannot
    partition a Mosaic kernel; ``flash_attention.batch_sharded``)."""
    import jax

    from .flash_attention import _SCOPE

    if hd % 128 or x.shape[1] % _ROW_TILES[-1]:
        return False
    return (jax.default_backend() == "tpu"
            and getattr(_SCOPE, "value", None) is None)


def _turn_table(positions, l, hd, base, scale, inv_freq, magnitude):
    """float32 ``(l, 2 hd)`` or ``(b, l, 2 hd)``: cos over both halves of a
    head, then sin with the first half's sign folded in, so that the turn is
    ``n * cos + roll(n, hd / 2) * sin``."""
    import jax.numpy as jnp

    angles = rope_angles(positions, l, hd, base, scale, inv_freq)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if magnitude != 1.0:
        cos, sin = cos * magnitude, sin * magnitude
    return jnp.concatenate([cos, cos, -sin, sin], axis=-1)


def _fwd_kernel(x_ref, *refs, eps, norm, turn, hd):
    """A row tile of a group of heads: ``x_ref (tl, heads a step * hd)``,
    then ``gamma_ref (1, hd)`` where ``norm`` and the table's tile where
    ``turn``; ``o_ref (heads a step, tl, hd)``."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental.pallas import tpu as pltpu

    *given, o_ref = refs
    gamma_ref = given.pop(0) if norm else None
    table_ref = given.pop(0) if turn else None
    for j in range(o_ref.shape[0]):
        n = x_ref[:, j * hd:(j + 1) * hd].astype(jnp.float32)
        if norm:
            ms = jnp.mean(n * n, axis=-1, keepdims=True)
            n = n * lax.rsqrt(ms + eps) * gamma_ref[...]
        if turn:
            n = (n * table_ref[:, :hd]
                 + pltpu.roll(n, hd // 2, 1) * table_ref[:, hd:])
        o_ref[j] = n.astype(o_ref.dtype)


def _bwd_kernel(g_ref, *refs, eps, norm, turn, hd):
    """The same tile backwards: ``g_ref (heads a step, tl, hd)``, then
    ``x_ref`` and ``gamma_ref`` where ``norm``, the table's tile where
    ``turn``; ``dx_ref (tl, heads a step * hd)`` and, where ``norm``,
    ``dgamma_ref (heads a step, 1, hd)``, the tile's sums."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental.pallas import tpu as pltpu

    refs = list(refs)
    x_ref, gamma_ref = (refs.pop(0), refs.pop(0)) if norm else (None, None)
    table_ref = refs.pop(0) if turn else None
    dx_ref, *dgamma_ref = refs
    for j in range(g_ref.shape[0]):
        lanes = slice(j * hd, (j + 1) * hd)
        dn = g_ref[j].astype(jnp.float32)
        if turn:
            dn = (dn * table_ref[:, :hd]
                  + pltpu.roll(dn * table_ref[:, hd:], hd // 2, 1))
        if norm:
            x = x_ref[:, lanes].astype(jnp.float32)
            r = lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            xhat = x * r
            dgamma_ref[0][j] = jnp.sum(dn * xhat, axis=0, keepdims=True)
            dxhat = dn * gamma_ref[...]
            dn = r * (dxhat - xhat * jnp.mean(dxhat * xhat, axis=-1,
                                              keepdims=True))
        dx_ref[:, lanes] = dn.astype(dx_ref.dtype)


# a block of the projection: rows a step (the largest that divides the rows)
# and bytes at most, which decide the heads a step
_ROW_TILES = (1024, 512, 256)
_BLOCK_BYTES = 1 << 20


def _specs(b, l, heads, hd, itemsize, table):
    """``(grid, tl, hs, projection's spec, operand's spec, gamma's, table's
    or None)``: ``hs`` heads a step; the grid is (row tiles, samples, head
    groups) with the last fastest, so that a row tile's table is fetched
    once, for every sample where the positions are one row's; the ``(b, l,
    h, hd) -> (b, h, l, hd)`` transpose is the two block maps' and costs
    nothing."""
    from jax.experimental import pallas as pl

    tl = next(t for t in _ROW_TILES if l % t == 0)
    hs = max(n for n in range(1, heads + 1) if heads % n == 0
             and (n == 1 or tl * n * hd * itemsize <= _BLOCK_BYTES))
    rows = pl.BlockSpec((None, tl, hs * hd), lambda li, bi, h: (bi, li, h))
    heads_first = pl.BlockSpec((None, hs, tl, hd),
                               lambda li, bi, h: (bi, h, li, 0))
    gamma_spec = pl.BlockSpec((1, hd), lambda li, bi, h: (0, 0))
    if table is None:
        table_spec = None
    elif table.ndim == 2:
        table_spec = pl.BlockSpec((tl, 2 * hd), lambda li, bi, h: (li, 0))
    else:
        table_spec = pl.BlockSpec((None, tl, 2 * hd),
                                  lambda li, bi, h: (bi, li, 0))
    return ((l // tl, b, heads // hs), tl, hs, rows, heads_first, gamma_spec,
            table_spec)


def _forward_pallas(x, gamma, table, heads, eps):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b, l, width = x.shape
    hd = width // heads
    grid, _, _, rows, heads_first, gamma_spec, table_spec = _specs(
        b, l, heads, hd, x.dtype.itemsize, table)
    operands, in_specs = [x], [rows]
    if gamma is not None:
        operands.append(gamma.astype(jnp.float32).reshape(1, hd))
        in_specs.append(gamma_spec)
    if table is not None:
        operands.append(table)
        in_specs.append(table_spec)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, norm=gamma is not None,
                          turn=table is not None, hd=hd),
        out_shape=jax.ShapeDtypeStruct((b, heads, l, hd), x.dtype),
        grid=grid, in_specs=in_specs, out_specs=heads_first,
        name=KERNEL_FWD)(*operands)


def _backward_pallas(x, gamma, table, g, heads, eps):
    """``(dx, dgamma)``: ``dx`` in the projection's layout, ``dgamma`` (None
    without ``gamma``) from the kernel's float32 partial sums a row tile a
    head, which XLA adds up.  Without ``gamma`` the projection is not read."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b, _, l, hd = g.shape
    grid, tl, hs, rows, heads_first, gamma_spec, table_spec = _specs(
        b, l, heads, hd, g.dtype.itemsize, table)
    operands, in_specs = [g], [heads_first]
    out_shape = [jax.ShapeDtypeStruct((b, l, heads * hd), g.dtype)]
    out_specs = [rows]
    if gamma is not None:
        operands += [x, gamma.astype(jnp.float32).reshape(1, hd)]
        in_specs += [rows, gamma_spec]
        # a block's last two dims are whole tiles or the array's own: the
        # partial sums get a dim of 1 before the head's numbers
        out_shape.append(jax.ShapeDtypeStruct((b, l // tl, heads, 1, hd),
                                              jnp.float32))
        out_specs.append(pl.BlockSpec((None, None, hs, 1, hd),
                                      lambda li, bi, h: (bi, li, h, 0, 0)))
    if table is not None:
        operands.append(table)
        in_specs.append(table_spec)
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, norm=gamma is not None,
                          turn=table is not None, hd=hd),
        out_shape=out_shape, grid=grid, in_specs=in_specs,
        out_specs=out_specs, name=KERNEL_BWD)(*operands)
    if gamma is None:
        return out[0], None
    return out[0], jnp.sum(out[1], axis=(0, 1, 2, 3)).astype(gamma.dtype)


@functools.lru_cache(maxsize=None)
def _make_kernel_op(heads, eps):
    """The kernels' op for one static configuration: ``op(x, gamma, table)``,
    either of the two ``None`` where the call has no norm or no turn.  The
    residual is the op's own inputs: nothing of the output's size is kept."""
    import jax

    @jax.custom_vjp
    def op(x, gamma, table):
        return _forward_pallas(x, gamma, table, heads, eps)

    def fwd(x, gamma, table):
        return op(x, gamma, table), (x, gamma, table)

    def bwd(res, g):
        return _backward_pallas(*res, g, heads, eps) + (None,)

    op.defvjp(fwd, bwd)
    return op


@register("_contrib_qk_norm_rope", aliases=("qk_norm_rope",))
def qk_norm_rope(x, *operands, heads=1, norm=False, eps=1e-6, turn=True,
                 base=10000.0, scale=1.0, inv_freq=None, magnitude=1.0):
    """A q or k projection made the attention op's operand in one pass.

    ``x (B, L, heads * D)`` as the projection's matmul leaves it; then
    ``gamma (D,)`` where ``norm``, then optionally ``positions`` ``(L,)`` or
    ``(B, L)`` (default arange) where ``turn``.  Returns ``(B, heads, L, D)``:
    per head ``rms_norm`` with ``eps`` (where ``norm``), then ``rope`` with
    ``base`` / ``scale`` / ``inv_freq`` / ``magnitude`` (where ``turn``).

    On a TPU, for ``D`` a multiple of 128 and ``L`` of 256, one Pallas
    kernel (``mxnet_qk_norm_rope_fwd``, backward ``_bwd``) computes both in
    float32 and rounds to ``x``'s dtype once; everywhere else the result is
    the registered ops' own, ``rope(rms_norm(x.reshape(B, L, heads,
    D).transpose(0, 2, 1, 3)))``.  Which one a call took is counted once a
    trace in ``mxnet_qk_norm_rope_calls_total{path}``."""
    from .. import telemetry

    operands = list(operands)
    gamma = operands.pop(0) if norm else None
    positions = operands.pop(0) if operands else None
    b, l, width = x.shape
    hd = width // heads
    pallas = (norm or turn) and _use_pallas(x, hd)
    telemetry.counter(
        "mxnet_qk_norm_rope_calls_total",
        "qk_norm_rope calls traced, by the path they took",
        ("path",)).labels(path="pallas" if pallas else "composed").inc()
    turn_attrs = dict(base=base, scale=scale, inv_freq=inv_freq,
                      magnitude=magnitude)
    if pallas:
        table = _turn_table(positions, l, hd, **turn_attrs) if turn else None
        return _make_kernel_op(int(heads), float(eps))(x, gamma, table)
    y = x.reshape(b, l, heads, hd).transpose(0, 2, 1, 3)
    if norm:
        y = rms_norm(y, gamma, eps=eps)
    return rope(y, positions, **turn_attrs) if turn else y
