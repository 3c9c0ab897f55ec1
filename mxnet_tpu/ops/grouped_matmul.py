"""The experts' grouped products: ``grouped_dot(rows (R, K), weights (G, K,
N), sizes (G,)) -> (R, N)``, the rows sorted by group, group ``g``'s rows
times ``weights[g]``.

Where a TPU will run it and the shape tiles (``_use_pallas``), Pallas kernels
compute it: bf16 operands, float32 accumulation, one rounding at the end,
which is what ``jax.lax.ragged_dot(..., precision=DEFAULT)`` computes.  One
kernel serves the product and, with the weights read transposed through
their block map, the rows' gradient; a second gives the weights' gradient,
``rows_g^T @ dy_g`` accumulated over a group's row tiles.  Both walk a table
of (row tile, group) visits made from ``sizes`` on the device and prefetched
to SMEM (``_visits``; ``group_plan``: once a part, for every product of the part), the
grid's length the visits that hold a row: **the rows past the last group are
not computed** and keep what memory held, NaN included, which is
``expert_parallel``'s contract for a grouped product.  ``swiglu`` between
the products is a kernel over the same live row tiles (XLA's element-wise
ops pass over the whole part, four times the pairs in the window cell).
Everything else is ``jax.lax.ragged_dot`` and ``silu(gate) * up``, call for
call.

``pl.pallas_call`` keeps nothing between calls: a call site traces the
kernel's body and block maps anew, which makes a new jaxpr, which JAX then
lowers to Mosaic and serialises anew, 15 sites a sparse layer.  So the
kernels are reached through ``jax.jit`` entries made once a process
(``_entries``) whose identity holds the avals and the static tiles alone: a
step's trace holds one jaxpr and its lowered module one private function a
distinct (shape, dtype, tiles), whatever the number of layers; XLA inlines
the calls.  Nothing made anew a call (a closure, a ``functools.partial``, a
captured array) may enter them.
"""
from __future__ import annotations

import collections
import functools
import types

# the kernels' names, as a device trace and the op-to-scope table show them:
# the benchmark's readers count a row under the experts' scope as a grouped
# product where its scope holds ``ragged_dot``
KERNEL = "mxnet_ragged_dot"
KERNEL_TRANSPOSED = "mxnet_ragged_dot_transposed"
KERNEL_DWEIGHTS = "mxnet_ragged_dot_dweights"
# SwiGLU between the products, over the row tiles that hold a pair
KERNEL_SWIGLU = "mxnet_moe_swiglu"
KERNEL_SWIGLU_BWD = "mxnet_moe_swiglu_bwd"

# rows a grid step, the largest that divides the rows.  A row tile that a
# group's boundary cuts is visited once a group, whole each time, so a larger
# tile wastes more of the MXU at the boundaries than it gains inside: on a v5e
# 256 read 3-9% under 512 at the cells' loads (16 groups of some 1,024 rows, 8
# of 2,048) and a quarter under it at 8 groups of 140 (PERF.md section 6, PR
# 40).  And what a step's blocks may take of VMEM: both buffers of each operand
# and of the result, the float32 accumulator and the product before it is
# added.  Mosaic's default scoped limit is 16 MiB and a v5e core has 128: a
# call states what its tiles need (``_vmem_limit``).
_ROW_TILES = (256, 128)
_VMEM_BUDGET = 48 << 20

# what ``_visits`` makes of a part's group sizes (its docstring has the fields)
Plan = collections.namedtuple("Plan", "ends group tile visits tiles")


def _use_pallas(rows, weights):
    """Static gate for the kernels, read from the call as
    ``qk_norm_rope._use_pallas`` reads it: a TPU to compile them for (JAX's
    default backend), bf16 operands, widths of whole lane tiles, rows of
    whole row tiles, and no mesh being traced over (GSPMD cannot partition a
    Mosaic kernel; ``flash_attention.batch_sharded``)."""
    import jax
    import jax.numpy as jnp

    from .flash_attention import _SCOPE

    if rows.dtype != jnp.bfloat16 or weights.dtype != jnp.bfloat16:
        return False
    if weights.shape[1] % 128 or weights.shape[2] % 128 \
            or rows.shape[0] % _ROW_TILES[-1]:
        return False
    return (jax.default_backend() == "tpu"
            and getattr(_SCOPE, "value", None) is None)


def _row_tile(r):
    return next(t for t in _ROW_TILES if r % t == 0)


def _lane_tiles(width):
    """The divisors of ``width`` that are whole lane tiles, largest first."""
    return [t for t in range(width, 0, -128) if width % t == 0]


def _widest_tiles(a, b, need):
    """``(ta, tb, bytes)``: the divisors of the widths ``a`` and ``b`` in
    whole lane tiles with the largest product whose blocks, ``need(ta, tb)``
    bytes, fit the budget: the whole widths where they do (a group's weights
    are then fetched once, the rows once)."""
    _, ta, tb = max((ta * tb, ta, tb) for ta in _lane_tiles(a)
                    for tb in _lane_tiles(b)
                    if need(ta, tb) <= _VMEM_BUDGET)
    return ta, tb, need(ta, tb)


def _vmem_limit(need):
    """What a call states: its blocks and as much again for Mosaic's own
    copies, within what a kernel may ask for."""
    from .flash_attention import _VMEM_MOST

    return min(2 * need + (8 << 20), _VMEM_MOST)


def group_plan(sizes, rows, weights):
    """What ``grouped_dot`` takes as ``plan`` for products of ``rows`` by
    groups of ``sizes``, made once where several share it (a product against
    ``weights`` transposed shares it too): ``_visits`` where the kernels
    will run, None where the gate sends the products to ``ragged_dot``."""
    return _entries().visits(sizes, rows.shape[0]) \
        if _use_pallas(rows, weights) else None


def _visits(sizes, r):
    """The visits a part's products walk, from its ``sizes (G,)`` and its
    static rows ``r``: a ``Plan`` of int32 arrays ``ends (G + 1,)``, ``group
    (V,)``, ``tile (V,)``, ``visits (1,)`` and ``tiles (1,)``, the last the
    row tiles that hold a row of any group (``swiglu``'s grid).  ``ends[g]`` is the row where group ``g`` starts and
    ``ends[g + 1]`` where it ends; visit ``i < visits[0]`` is row tile
    ``tile[i]`` for group ``group[i]``: every row tile that holds a row of
    a group, for every such group, the groups in order and a group's tiles
    in order, so that a row tile is visited in consecutive steps and a
    group's tiles are; a group without rows is visited once, in the tile
    where it would start, so that its weights' gradient is written (zeros).
    ``V = r / tm + G`` bounds the visits; past ``visits[0]`` the arrays
    repeat the last visit's."""
    import jax.numpy as jnp

    tm = _row_tile(r)
    count = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    stops = jnp.cumsum(sizes)
    starts = stops - sizes
    first = jnp.minimum(starts, r - 1) // tm
    last = jnp.where(sizes > 0, (stops - 1) // tm, first)
    per_group = last - first + 1
    visit_stops = jnp.cumsum(per_group)
    index = jnp.arange(r // tm + count, dtype=jnp.int32)
    at = jnp.minimum(index, visit_stops[-1] - 1)
    group = jnp.searchsorted(visit_stops, at, side="right",
                             method="compare_all").astype(jnp.int32)
    tile = first[group] + at - (visit_stops - per_group)[group]
    ends = jnp.concatenate([jnp.zeros(1, jnp.int32), stops])
    return Plan(ends, group, tile.astype(jnp.int32), visit_stops[-1:],
                (stops[-1:] + tm - 1) // tm)


def _rows_of_group(ends_ref, group_ref, tile_ref, visit, tm, width):
    """``(tm, width)`` bools: the rows of this visit's tile that are its
    group's."""
    import jax.numpy as jnp
    from jax import lax

    g = group_ref[visit]
    row = tile_ref[visit] * tm + lax.broadcasted_iota(jnp.int32, (tm, width),
                                                      0)
    return (row >= ends_ref[g]) & (row < ends_ref[g + 1])


def _gmm_kernel(ends_ref, group_ref, tile_ref, visits_ref, lhs_ref, rhs_ref,
                out_ref, acc_ref, *, transposed, steps):
    """One visit's ``(tm, to)`` tile of the result, accumulated over the
    grid's last axis; the rows of other groups in the tile keep what the
    block holds (a neighbour's visit wrote or will write them)."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    del visits_ref
    visit, c = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += lax.dot_general(
        lhs_ref[...], rhs_ref[...],
        (((1,), (1 if transposed else 0,)), ((), ())),
        precision=lax.Precision.DEFAULT, preferred_element_type=jnp.float32)

    @pl.when(c == steps - 1)
    def _():
        mine = _rows_of_group(ends_ref, group_ref, tile_ref, visit,
                              *out_ref.shape)
        out_ref[...] = jnp.where(mine, acc_ref[...].astype(out_ref.dtype),
                                 out_ref[...])


def _tgmm_kernel(ends_ref, group_ref, tile_ref, visits_ref, lhs_ref, rhs_ref,
                 out_ref, acc_ref):
    """A group's ``(tk, tn)`` tile of ``lhs_g^T @ rhs_g``, accumulated over
    the group's visits (consecutive steps of the grid's last axis)."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    visit = pl.program_id(2)
    last = visits_ref[0] - 1
    g = group_ref[visit]

    @pl.when((visit == 0) | (group_ref[jnp.maximum(visit - 1, 0)] != g))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # both operands' rows of other groups as zeros: what lies past the last
    # group may be NaN in either
    tm = lhs_ref.shape[0]
    zero = jnp.zeros((), lhs_ref.dtype)
    lhs = jnp.where(_rows_of_group(ends_ref, group_ref, tile_ref, visit, tm,
                                   lhs_ref.shape[1]), lhs_ref[...], zero)
    rhs = jnp.where(_rows_of_group(ends_ref, group_ref, tile_ref, visit, tm,
                                   rhs_ref.shape[1]), rhs_ref[...], zero)
    acc_ref[...] += lax.dot_general(lhs, rhs, (((0,), (0,)), ((), ())),
                                    precision=lax.Precision.DEFAULT,
                                    preferred_element_type=jnp.float32)

    @pl.when((visit == last) | (group_ref[jnp.minimum(visit + 1, last)] != g))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _gmm_call(plan, lhs, rhs, transposed=False):
    """``lhs (R, C)`` by groups against ``rhs (G, K, N)``: ``C = K`` and the
    result ``(R, N)``, or ``transposed``, ``C = N`` and the result ``(R,
    K)`` (the rows' gradient: the weights are read as they lie and
    contracted over their last axis)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, contract = lhs.shape
    out = rhs.shape[1 if transposed else 2]
    tm = _row_tile(r)
    # both buffers of the three blocks, the accumulator and the product
    tc, to, need = _widest_tiles(
        contract, out, lambda tc, to: 2 * (tm * tc + tc * to + tm * to)
        * lhs.dtype.itemsize + 2 * tm * to * 4)
    if transposed:
        rhs_spec = pl.BlockSpec(
            (None, to, tc), lambda o, v, c, ends, group, tile, n:
            (group[v], o, c))
    else:
        rhs_spec = pl.BlockSpec(
            (None, tc, to), lambda o, v, c, ends, group, tile, n:
            (group[v], c, o))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transposed=transposed,
                          steps=contract // tc),
        out_shape=jax.ShapeDtypeStruct((r, out), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(out // to, plan.visits[0], contract // tc),
            in_specs=[
                pl.BlockSpec((tm, tc), lambda o, v, c, ends, group, tile, n:
                             (tile[v], c)),
                rhs_spec],
            out_specs=pl.BlockSpec(
                (tm, to), lambda o, v, c, ends, group, tile, n:
                (tile[v], o)),
            scratch_shapes=[pltpu.VMEM((tm, to), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(need)),
        name=KERNEL_TRANSPOSED if transposed else KERNEL)(*plan[:4], lhs, rhs)


def _tgmm_call(plan, lhs, rhs):
    """``out[g] = lhs_g^T @ rhs_g``: ``lhs (R, K)``, ``rhs (R, N)``, the
    result ``(G, K, N)`` in their dtype, zeros for a group without rows."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, k = lhs.shape
    n = rhs.shape[1]
    groups = plan.ends.shape[0] - 1
    tm = _row_tile(r)
    # as the product's, the accumulator here (tk, tn), and the rows' tile
    # transposed
    tk, tn, need = _widest_tiles(
        k, n, lambda tk, tn: (2 * (tm * tk + tm * tn + tk * tn) + tm * tk)
        * lhs.dtype.itemsize + 2 * tk * tn * 4)
    return pl.pallas_call(
        _tgmm_kernel,
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k // tk, n // tn, plan.visits[0]),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda ki, ni, v, ends, group, tile,
                             n_: (tile[v], ki)),
                pl.BlockSpec((tm, tn), lambda ki, ni, v, ends, group, tile,
                             n_: (tile[v], ni))],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda ki, ni, v, ends, group, tile, n_:
                (group[v], ki, ni)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(need)),
        name=KERNEL_DWEIGHTS)(*plan[:4], lhs, rhs)


def _swiglu_kernel(tiles_ref, gate_ref, up_ref, out_ref):
    import jax
    import jax.numpy as jnp

    del tiles_ref
    gate = gate_ref[...].astype(jnp.float32)
    out_ref[...] = (gate * jax.nn.sigmoid(gate)
                    * up_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def _swiglu_bwd_kernel(tiles_ref, gate_ref, up_ref, dy_ref, dgate_ref,
                       dup_ref):
    import jax
    import jax.numpy as jnp

    del tiles_ref
    gate = gate_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    s = jax.nn.sigmoid(gate)
    dup_ref[...] = (dy * gate * s).astype(dup_ref.dtype)
    dgate_ref[...] = (dy * up_ref[...].astype(jnp.float32)
                      * s * (1.0 + gate * (1.0 - s))).astype(dgate_ref.dtype)


def _swiglu_call(plan, *operands, backward=False):
    """``silu(gate) * up`` of ``(R, N)`` operands, or with a third, the
    cotangent, ``(dgate, dup)``: in float32, rounded once, over the row
    tiles that hold a pair (``plan``'s last); the others keep what memory
    held, as a product's do."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    like = operands[0]
    tm = _row_tile(like.shape[0])
    block = pl.BlockSpec((tm, like.shape[1]), lambda i, tiles: (i, 0))
    shape = jax.ShapeDtypeStruct(like.shape, like.dtype)
    return pl.pallas_call(
        _swiglu_bwd_kernel if backward else _swiglu_kernel,
        out_shape=[shape, shape] if backward else shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(plan.tiles[0],),
            in_specs=[block] * len(operands),
            out_specs=[block, block] if backward else block),
        name=KERNEL_SWIGLU_BWD if backward else KERNEL_SWIGLU)(
            plan.tiles, *operands)


@functools.lru_cache(maxsize=None)
def _entries():
    """``gmm``, ``tgmm``, ``grouped``, ``visits``, ``gated``: the kernels'
    ``jax.jit`` entries, made once a process (the module docstring says why they are
    jitted), the product with its hand-written backward over them, whose
    residual is the op's own inputs, ``_visits`` jitted likewise (its score
    of small ops is then one equation a site), and SwiGLU with its
    backward."""
    import jax

    gmm = jax.jit(_gmm_call, static_argnames=("transposed",))
    tgmm = jax.jit(_tgmm_call)
    visits = jax.jit(_visits, static_argnums=1)

    @jax.custom_vjp
    def grouped(rows, weights, plan):
        return gmm(plan, rows, weights)

    def fwd(rows, weights, plan):
        return gmm(plan, rows, weights), (rows, weights, plan)

    def bwd(res, dy):
        rows, weights, plan = res
        dy = dy.astype(rows.dtype)
        return (gmm(plan, dy, weights, transposed=True),
                tgmm(plan, rows, dy), None)

    grouped.defvjp(fwd, bwd)
    glu = jax.jit(_swiglu_call, static_argnames=("backward",))

    @jax.custom_vjp
    def gated(gate, up, plan):
        return glu(plan, gate, up)

    def gated_fwd(gate, up, plan):
        return glu(plan, gate, up), (gate, up, plan)

    def gated_bwd(res, dy):
        gate, up, plan = res
        return (*glu(plan, gate, up, dy.astype(gate.dtype), backward=True),
                None)

    gated.defvjp(gated_fwd, gated_bwd)
    return types.SimpleNamespace(gmm=gmm, tgmm=tgmm, grouped=grouped,
                                 visits=visits, gated=gated)


def grouped_dot(rows, weights, sizes, plan=None):
    """``rows (R, K)`` sorted by group times ``weights (G, K, N)``: the
    first ``sizes[0]`` rows times ``weights[0]`` and so on, ``(R, N)`` in
    the operands' dtype.  Rows past ``sum(sizes)`` are not computed: they
    hold whatever memory held, and their gradient likewise.

    On a TPU, for bf16 operands, widths in 128s and rows in 128s, Pallas
    kernels (``mxnet_ragged_dot`` and, backward, ``_transposed`` and
    ``_dweights``) with float32 accumulation; everywhere else
    ``jax.lax.ragged_dot``: float32 operands follow the process's matmul
    precision, narrower ones are one exact MXU pass.  ``plan`` is
    ``group_plan``'s where the caller has made it for several products of
    the same rows.  Which one a call took is counted once a
    trace in ``mxnet_moe_grouped_dot_calls_total{path}``."""
    import jax
    import jax.numpy as jnp

    from .. import telemetry

    pallas = _use_pallas(rows, weights)
    telemetry.counter(
        "mxnet_moe_grouped_dot_calls_total",
        "grouped products traced, by the path they took",
        ("path",)).labels(path="pallas" if pallas else "ragged_dot").inc()
    if not pallas:
        return jax.lax.ragged_dot(
            rows, weights, group_sizes=sizes,
            precision=None if rows.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    if plan is None:
        plan = _entries().visits(sizes, rows.shape[0])
    return _entries().grouped(rows, weights, plan)


def swiglu(gate, up, plan=None):
    """``silu(gate) * up`` between a part's products, ``(R, N)`` each.  With
    ``group_plan``'s ``plan`` a Pallas kernel (``mxnet_moe_swiglu``, backward
    ``_bwd``) over the row tiles that hold a pair, float32 inside and one
    rounding, the other rows left as memory held them; without one (the
    gate closed) the two registered ops over every row."""
    import jax

    if plan is None:
        return jax.nn.silu(gate) * up
    return _entries().gated(gate, up, plan)
