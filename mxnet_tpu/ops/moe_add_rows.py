"""The expert layer's way back: ``add_rows(out, rows (R, d), tokens (R,),
plan[, gates (R,)])`` adds the rows that hold a pair, each times its gate
where gates are given, into their tokens' rows of ``out``, in place.

A Pallas kernel (``mxnet_moe_add_rows``) over the table of (row tile, group)
visits that ``grouped_matmul._visits`` makes from a part's group sizes, the
grid's length the visits that hold a row: **the rows past the last group are
never read** (they may hold NaN) and a part a quarter full costs a quarter.
``out`` stays in HBM as an aliased operand and there is no pass over it: a
visit reads its row tile through an ordinary blocked operand, fetches the
tokens' rows of ``out`` for the rows of its group by one DMA a row into
VMEM, adds ``rows * gate`` in float32 (a tile at a time: no float32 copy of
the part) and writes the rows back, a row a DMA, all of a visit's in flight
together.

**``out`` is float32 and ``(T, 1, d)``**: under that shape the TPU lays a
token's row out contiguous (tiles of one row by 128 lanes), and a DMA may
move one; of ``(T, d)`` eight rows share a tile and Mosaic refuses a slice
of one (as it does a row of a bf16 pair).  A caller that holds ``(T, d)``
pays XLA's pass over it each way (``add_rows`` reshapes); the expert layer
carries ``(T, 1, d)`` through its loop, and narrower sums (``dispatch``'s
backward under bf16) are made in float32 and rounded once by whoever reads
them.

**The contract** (``expert_parallel._moe_dropless`` keeps it; the public
``dispatch`` / ``combine`` take the kernel only where the caller hands the
groups in): the rows are sorted by group, ``plan`` is ``_visits`` of the
groups' sizes over the ``R`` rows, and **within one group every token
appears at most once** (a token picks an expert once).  A token may come
back in another group: a visit's writes have landed before the next visit's
reads begin.  Two rows of one group with one token would race, the later
write winning.
"""
from __future__ import annotations

import functools

from ..profiler import KERNEL_MOE_ADD_ROWS as KERNEL, SCOPE_MOE_ROUTE
from .grouped_matmul import _row_tile


def use_pallas(rows, width):
    """Static gate for the kernel, read from the call as
    ``grouped_matmul._use_pallas`` reads it: a TPU to compile for (JAX's
    default backend), rows of whole lane tiles, a part of whole row tiles,
    and no mesh being traced over."""
    import jax

    from .flash_attention import _SCOPE
    from .grouped_matmul import _ROW_TILES

    return (width % 128 == 0 and rows % _ROW_TILES[-1] == 0
            and jax.default_backend() == "tpu"
            and getattr(_SCOPE, "value", None) is None)


def group_plan(sizes, rows, width):
    """What ``add_rows`` takes as ``plan`` for a part of ``rows`` rows of
    ``width`` in groups of ``sizes``: ``grouped_matmul``'s table of visits
    (XLA makes it once however many ask; its few ops are the routing's by
    their scope), or None where the groups are not given or the gate is
    closed."""
    import jax

    from .grouped_matmul import _entries

    if sizes is None or not use_pallas(rows, width):
        return None
    with jax.named_scope(SCOPE_MOE_ROUTE):
        return _entries().visits(sizes, rows)


# rows a trip of the loops that start a visit's row copies
_UNROLL = 8


def _waits(n, most, wait):
    """``wait(k)`` for the powers of two ``k`` that sum to ``n <= most``: a
    DMA semaphore counts bytes, so one wait the size of ``k`` rows takes
    what ``k`` row copies signalled."""
    from jax.experimental import pallas as pl

    k = most
    while k:
        pl.when((n & k) != 0)(functools.partial(wait, k))
        k //= 2


def _add_rows_kernel(ends_ref, group_ref, tile_ref, visits_ref, tokens_ref,
                     rows_ref, *rest, gated):
    """One visit: the rows of ``group[v]`` in row tile ``tile[v]``."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del visits_ref
    gates_ref = rest[0] if gated else None
    out_ref, buf, sem = rest[-3:]       # rest[-4] is out's aliased input
    tm = rows_ref.shape[0]
    v = pl.program_id(0)
    g = group_ref[v]
    base = tile_ref[v] * tm
    lo = jnp.maximum(ends_ref[g] - base, 0)
    hi = jnp.minimum(ends_ref[g + 1] - base, tm)
    n = jnp.maximum(hi - lo, 0)

    def fetch(i, carry):
        pltpu.make_async_copy(out_ref.at[pl.ds(tokens_ref[base + i], 1)],
                              buf.at[pl.ds(i, 1)], sem.at[0]).start()
        return carry

    def store(i, carry):
        pltpu.make_async_copy(buf.at[pl.ds(i, 1)],
                              out_ref.at[pl.ds(tokens_ref[base + i], 1)],
                              sem.at[1]).start()
        return carry

    def landed(which, k):
        pltpu.make_async_copy(out_ref.at[pl.ds(0, k)], buf.at[pl.ds(0, k)],
                              sem.at[which]).wait()

    def each_row(start):
        """``start(i)`` for the rows ``lo <= i < hi``, ``_UNROLL`` a trip
        while whole runs last: the scalar core overlaps a run's address
        arithmetic."""
        runs = n // _UNROLL

        def run(j, carry):
            for u in range(_UNROLL):
                start(lo + j * _UNROLL + u, carry)
            return carry

        lax.fori_loop(0, runs, run, 0)
        lax.fori_loop(lo + runs * _UNROLL, hi, start, 0)

    each_row(fetch)
    _waits(n, tm, functools.partial(landed, 0))
    add = rows_ref[...].astype(jnp.float32)
    if gated:
        add = add * gates_ref[...]
    buf[:, 0, :] = buf[:, 0, :] + add
    each_row(store)
    _waits(n, tm, functools.partial(landed, 1))


def _add_rows_call(plan, tokens, out, rows, gates=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, d = rows.shape
    tm = _row_tile(r)
    assert out.shape[1:] == (1, d) and out.dtype == jnp.float32, out
    tiled = lambda width: pl.BlockSpec(     # noqa: E731
        (tm, width), lambda v, ends, group, tile, n, tokens: (tile[v], 0))
    operands = (rows,) if gates is None else (rows, gates.reshape(r, 1))
    return pl.pallas_call(
        functools.partial(_add_rows_kernel, gated=gates is not None),
        out_shape=jax.ShapeDtypeStruct(out.shape, out.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(plan.visits[0],),
            in_specs=[tiled(d)] + [tiled(1)] * (gates is not None)
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((tm, 1, d), out.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        input_output_aliases={5 + len(operands): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=KERNEL)(*plan[:4], tokens, *operands, out)


@functools.lru_cache(maxsize=None)
def _entry():
    """The kernel's ``jax.jit`` entry, made once a process as
    ``grouped_matmul._entries`` are and for their reason: a step's module
    holds one private function a distinct shape, whatever the call sites."""
    import jax

    return jax.jit(_add_rows_call)


def add_rows(out, rows, tokens, plan, gates=None):
    """``out`` (float32) with ``rows[i] * gates[i]`` (``rows[i]`` without
    gates) added to row ``tokens[i]`` for every row ``i`` of a group of
    ``plan``; the module docstring has the contract.  ``out`` is ``(T, 1,
    d)``, updated in place where the caller holds no other use of it, or
    ``(T, d)``, which costs a pass over it each way."""
    linear = out.reshape(out.shape[0], 1, out.shape[-1])
    return _entry()(plan, tokens, linear, rows, gates).reshape(out.shape)
