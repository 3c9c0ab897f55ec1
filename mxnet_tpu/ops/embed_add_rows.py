"""The embedding's way back: ``add_token_rows(out (V, d), rows (R, d),
ids (R,))`` adds ``rows[i]`` into row ``ids[i]`` of ``out``, any id any
number of times, in float32; ``token_rows_sum`` is the same into zeros.

A Pallas kernel (``mxnet_embed_add_rows``) that fetches rows by DMA as
``ops/moe_add_rows.py`` does, for rows that repeat an id, which that
kernel's contract excludes.  XLA sorts the ids once beside an ``iota``, so
that the rows of one stretch of the table are neighbours.  The grid walks
the table ``_BLOCK`` rows a visit through an ordinary blocked output in the
table's own layout: **one pass writes the whole result**, no fill of zeros
before it and no relay after it, and with ``out`` given the pass reads it
through a blocked input aliased to the result.  A visit adds the rows whose
ids fall in its block, in sorted order, each into its row of the block in
VMEM (a repeated id is one more add into the same row: the sums are
float32's, in the sorted order).  The rows come through the permutation by
one DMA a row, ``_TILE`` sorted positions a chunk, **read in place** (never
gathered into a sorted copy); the chunk after the one being added is in
flight meanwhile, whichever visit will want it.  ``rows`` goes in as ``(R,
1, d)``: under that shape the TPU lays a row out contiguous and a DMA may
move one (``ops/moe_add_rows.py``'s docstring); a small kernel in front
(``mxnet_embed_add_rows_apart``) makes that copy.

What a visit needs of the sorted ids is made by XLA and prefetched to SMEM:
the permutation, the sorted ids, and where each block's ids start among
them.
"""
from __future__ import annotations

import functools

from ..profiler import KERNEL_EMBED_ADD_ROWS as KERNEL, SCOPE_EMBED
from .moe_add_rows import _UNROLL

# sorted positions a chunk of row copies, and rows of the table a visit
_TILE = 128
_BLOCK = 128
# the narrowest table that takes the kernel: where XLA's scatter-add falls off
# its cliff.  Alone, on a v5e, ms (PERF.md section 6, PR 53: XLA | the kernel
# with its copy): 8,192 ids into 30,522 x 768 0.49 | 0.30, into 25,024 x 2,048
# 1.79 | 0.49, 16,384 into 12,288 x 2,304 2.41 | 1.08, 8,192 into 19,648 x
# 2,560 8.25 | 0.54 and into 25,008 x 2,560 10.32 | 0.61.  The kernel is
# ahead at every width, but without its scatter XLA lays a step's whole
# residual stream out otherwise, and the step 2,304 wide then held 0.75 GiB
# more on the chip (8.87 -> 9.61): below the cliff the program stays XLA's.
_MIN_WIDTH = 2560


def use_pallas(table, rows):
    """Static gate for the kernel, read from the call as
    ``moe_add_rows.use_pallas`` reads its own: a TPU to compile for (JAX's
    default backend), a float32 table (the cotangent is then float32 too),
    rows of whole lane tiles and no fewer than ``_MIN_WIDTH`` wide, ``rows``
    ids a whole number of chunks, and no mesh being traced over."""
    import jax
    import jax.numpy as jnp

    from .flash_attention import _SCOPE

    return (table.ndim == 2 and table.dtype == jnp.float32
            and table.shape[1] % 128 == 0 and table.shape[1] >= _MIN_WIDTH
            and rows > 0 and rows % _TILE == 0
            and jax.default_backend() == "tpu"
            and getattr(_SCOPE, "value", None) is None)


def _kernel(starts_ref, perm_ref, ids_ref, rows_ref, *rest, adds):
    """One visit: rows ``b * block`` to ``(b + 1) * block`` of the table."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    out_ref, fetched, sem, state = rest[-4:]
    block, tile = out_ref.shape[0], fetched.shape[1]
    chunks = perm_ref.shape[0] // tile
    b = pl.program_id(0)
    lo, hi = starts_ref[b], starts_ref[b + 1]

    @pl.when(b == 0)
    def _():
        state[0] = 0        # chunks whose copies have started
        state[1] = 0        # chunks whose copies have landed

    out_ref[...] = rest[0][...] if adds else jnp.zeros_like(out_ref)

    def start(k):
        def trip(j, carry):
            for u in range(_UNROLL):
                i = j * _UNROLL + u
                pltpu.make_async_copy(
                    rows_ref.at[pl.ds(perm_ref[k * tile + i], 1)],
                    fetched.at[k % 2, pl.ds(i, 1)], sem.at[k % 2]).start()
            return carry

        lax.fori_loop(0, tile // _UNROLL, trip, 0)
        state[0] = k + 1

    def chunk(k, carry):
        # a chunk is fetched once, by the first visit to want it or by the
        # visit at work on the chunk before; its slot's last rows are added
        pl.when(state[0] == k)(lambda: start(k))
        pl.when((state[0] == k + 1) & (k + 1 < chunks))(
            lambda: start(k + 1))

        @pl.when(state[1] == k)
        def _():
            pltpu.make_async_copy(rows_ref.at[pl.ds(0, tile)],
                                  fetched.at[k % 2], sem.at[k % 2]).wait()
            state[1] = k + 1

        def add(p, carry):
            row = pl.ds(ids_ref[p] - b * block, 1)
            out_ref[row, :] = out_ref[row, :] + fetched[k % 2, p - k * tile]
            return carry

        lax.fori_loop(jnp.maximum(lo, k * tile),
                      jnp.minimum(hi, (k + 1) * tile), add, 0)
        return carry

    lax.fori_loop(lo // tile, jnp.where(hi > lo, (hi - 1) // tile + 1,
                                        lo // tile), chunk, 0)


def _rows_apart(rows):
    """``rows (R, d)`` as ``(R, 1, d)``, by a kernel of its own: XLA's
    reshape would be this copy too, but XLA fuses it into whatever makes
    ``rows`` and carries the one-sublane layout up that chain (the Phi
    cell's norms' backward ran 19 ms a step slower in it, PERF.md section
    6, PR 53); a kernel's operands keep their shapes' own layouts."""
    import jax
    from jax.experimental import pallas as pl

    r, d = rows.shape

    def kernel(rows_ref, apart_ref):
        apart_ref[:, 0, :] = rows_ref[...]

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((r, 1, d), rows.dtype),
        grid=(r // _TILE,),
        in_specs=[pl.BlockSpec((_TILE, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_TILE, 1, d), lambda i: (i, 0, 0)),
        name=KERNEL + "_apart")(rows)


def _call(rows, ids, out=None, *, vocab):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, d = rows.shape
    assert rows.dtype == jnp.float32 and r % _TILE == 0, rows
    blocks = pl.cdiv(vocab, _BLOCK)
    ids = jnp.clip(ids.astype(jnp.int32), 0, vocab - 1)
    ids, perm = lax.sort((ids, lax.iota(jnp.int32, r)), num_keys=1,
                         is_stable=False)
    starts = jnp.searchsorted(
        ids, jnp.arange(blocks + 1, dtype=jnp.int32) * _BLOCK,
        method="compare_all").astype(jnp.int32)
    table = pl.BlockSpec((_BLOCK, d), lambda b, *_: (b, 0))
    return pl.pallas_call(
        functools.partial(_kernel, adds=out is not None),
        out_shape=jax.ShapeDtypeStruct((vocab, d), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(blocks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)]
            + [table] * (out is not None),
            out_specs=table,
            scratch_shapes=[pltpu.VMEM((2, _TILE, 1, d), rows.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((2,), jnp.int32)]),
        input_output_aliases={} if out is None else {4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=KERNEL)(starts, perm, ids, _rows_apart(rows),
                     *(() if out is None else (out,)))


@functools.lru_cache(maxsize=None)
def _entry():
    """The sort and the kernel behind one ``jax.jit`` entry, made once a
    process as ``moe_add_rows._entry`` is and for its reason: a step's
    module holds one private function a distinct shape, whatever the call
    sites."""
    import jax

    return jax.jit(_call, static_argnames=("vocab",))


def token_rows_sum(vocab, rows, ids):
    """``(vocab, d)`` float32 zeros with ``rows[i]`` added to row ``ids[i]``
    (clipped to the table as ``Embedding`` clips) for every ``i``."""
    return _entry()(rows, ids, vocab=vocab)


def add_token_rows(out, rows, ids):
    """``out (V, d)`` float32 with ``rows[i]`` added to row ``ids[i]`` for
    every ``i``, in place where the caller holds no other use of ``out``."""
    return _entry()(rows, ids, out, vocab=out.shape[0])


@functools.lru_cache(maxsize=None)
def _take_rows(vocab, hands_on):
    import jax
    import jax.numpy as jnp

    def forward(table, ids):
        rows = jnp.take(table, ids, axis=0)
        return ((rows, table) if hands_on else rows), ids

    def backward(ids, g):
        g, *into = g if hands_on else (g,)
        # named here, inside the rule, whatever the call site's scope: the
        # sort and the kernel are the embedding's by the program's
        # op-to-scope table
        with jax.named_scope(SCOPE_EMBED):
            g, ids = g.reshape(-1, g.shape[-1]), ids.reshape(-1)
            return (add_token_rows(*into, g, ids) if hands_on
                    else token_rows_sum(vocab, g, ids)), None

    take = jax.custom_vjp(lambda table, ids: forward(table, ids)[0])
    take.defvjp(forward, backward)
    return take


def take_rows(table, ids, hands_on=False):
    """``jnp.take(table, ids, axis=0)`` for ids inside the table, whose way
    back is ``token_rows_sum`` where XLA's is a scatter-add into zeros.
    With ``hands_on`` the table comes back beside the rows, for whoever
    else reads it in the same program (a head tied to it): what that reader
    sends back then reaches this rule with the rows' cotangent, which adds
    the rows into it in place, where a sum made afterwards holds a second
    array the table's size."""
    return _take_rows(table.shape[0], bool(hands_on))(table, ids)
