"""Expert parallelism: one mixture-of-experts layer, two ways to route.

Capability upgrade over the reference (MXNet 1.x has no MoE).

- ``capacity_factor=<number>`` (switch, top-1): routing as dense one-hot
  dispatch/combine einsums, Mesh-TF/Switch-Transformer style: static
  shapes, the expert dimension sharded over ``ep``, GSPMD turning the
  token->expert regrouping einsums into all_to_all collectives riding ICI.
  Tokens beyond an expert's capacity pass through the residual (combine
  weight 0), the standard overflow behaviour.
- ``capacity_factor=None`` (dropless, top-k): the router keeps its whole
  width and picks ``top_k`` experts a token; the layer is told which
  experts it holds (``held = (first, count)``) and computes their part of
  the result only.  The (token, expert) pairs that land here are sorted by
  expert and go through grouped matrix products (``expert_fn`` over rows
  and group sizes, ``jax.lax.ragged_dot`` inside it).  No pair is ever
  dropped: the sorted rows are cut into parts of ``_PART_ROWS`` (fewer for
  a share that holds few of the experts: ``_PART_EVEN_LOADS``), the groups
  of a part are the pairs it holds and nothing else, and the layer walks
  the parts that hold a pair, ``ceil(pairs held / part)`` of them, a device
  number: one loop forward, and one loop backward that takes the ``jax.vjp``
  of a part (its forward computed again there) and adds what it gives into
  the gradients of the tokens, the weights and the gates.  A part past the
  last pair costs nothing either way.  JAX cannot reverse a loop whose trip
  count is a device number, so the loop's backward is written by hand
  (``_walk_live_parts``); it keeps the loop's inputs and nothing else.  Every
  shape takes the loop, a shape of one part too: its trips are then 0 or 1.
  Within a part the gathers follow the pairs too: ``dispatch`` gathers the
  tokens' rows into the sorted order a granule of ``_GRANULE`` at a time
  and stops at the last pair, and so does ``combine``'s backward, for the
  rows and for their gates.  The way back (``combine``, ``dispatch``'s
  backward) adds the rows that hold a pair into their tokens' rows, in
  place: on a TPU the Pallas kernel of ``ops/moe_add_rows.py`` over the
  (row tile, group) visits of the part's groups, a row a DMA each way, the
  gate multiplied in inside it, the target float32 as the kernel holds it
  (``(T, 1, d)``: a token's row contiguous) from the loop's first part to
  the one pass that rounds the result.  It leans on what the sort gives: in
  one group no token twice (``ops/moe_add_rows.py`` has the contract), so
  the layer hands its groups in (``sizes``) and a caller of ``dispatch`` /
  ``combine`` who does not, or whom the kernel's gate turns away (the CPU,
  a mesh being traced, a width that is no whole lane tiles), gets one
  scatter-add of the whole part with the rows past the last pair as zeros:
  XLA's scatter-add on a TPU pays a pass over the indices and the target
  before its first row and every row of the part after, pair or not.  Each
  of the two is the other's transpose, written by hand (``jax.custom_vjp``)
  because the walk's trip count is a device number.
  The router is a ``custom_vjp`` of its own (``_router``): the choice has no
  gradient, so its backward is two products and no forward one, and under a
  decoder layer's checkpoint the choice, the chosen scores and what the
  walks read of the sort (``_walk_plan``) are kept by name (``KEPT``): a
  step chooses once.
  What the experts held elsewhere would add is left out; the exchange that
  brings it in is not written yet.
"""
from __future__ import annotations

import functools

from .. import telemetry
from ..base import MXNetError
from ..ops import moe_add_rows
from ..ops.flash_attention import keeping, kept
from ..profiler import SCOPE_MOE_EXPERTS, SCOPE_MOE_ROUTE

__all__ = ["moe_apply", "stack_expert_params", "inject_aux_loss",
           "dispatch", "combine", "limit_to_groups"]

# Rows of the sorted (token, expert) pairs that the dropless path computes at
# once, the static bound on what one grouped product sees: what is live of
# one part (its tokens, the experts' hidden rows, its result) is about
# 0.8 GiB at hidden 2048 and width 768.  ``tokens x top_k`` rows are a static
# number of parts; the walk takes the first ``ceil(pairs held / part)`` of
# them.  The products, the gathers and (through its kernel) the way back
# follow the pairs: a load a few pairs over a multiple of this pays one
# granule's gathers and one trip of the loops more; where the way back is
# XLA's scatter-add (off the kernel's gate) it is of a whole part, and that
# trip costs a part's.  A share that holds few
# of the router's experts is sized by what it may see instead: a part holds
# at most ``_PART_EVEN_LOADS`` times the pairs of an even load over the
# experts (``tokens x top_k x held / experts``), so that the part's buffers
# follow the share and not the whole router.  Eight, because that is what
# ``_PART_ROWS`` already is for the thinnest share it was measured on (8 of
# 128 experts at 8,192 tokens of 8: 4,096 pairs even), so no share that ran
# before gets another part: every share walks one part up to eight times
# its even load, and a share of 8 of 512 experts gets parts of 8,192 rows in
# place of 32,768 (1.1 GiB less scratch in a step of six such layers at
# hidden 2,560); a fuller share walks more parts and drops nothing.
_PART_ROWS = 32768
_PART_EVEN_LOADS = 8
# Rows of a part that one step of its sorted walk gathers.  On a v5e a
# step costs what its rows cost (16 gathers of 2,048 rows take what one of
# 32,768 takes; 4,096 times the same in every load tried), so the granule is
# as small as keeps the rows walked past the last pair, at most one granule
# a part, a few percent of a load of 16,384.  It divides _PART_ROWS.
_GRANULE = 2048


def stack_expert_params(per_expert):
    """[expert0_tree, ...] -> tree with leading expert axis (sharded
    over ep by moe_apply)."""
    from .pipeline_parallel import stack_stage_params

    return stack_stage_params(per_expert)


def moe_apply(expert_fn, expert_params, router_weight, x, mesh=None,
              axis="ep", capacity_factor=1.25, top_k=1, renormalize=False,
              held=None, score="softmax", select_bias=None, scale=1.0,
              renorm_eps=0.0, groups=None):
    """One MoE layer over tokens ``x (T, d)`` with ``router_weight (d, E)``.

    With a ``capacity_factor`` (switch top-1): ``expert_fn(params_one_expert,
    tokens (C, d)) -> (C, d)``, ``expert_params`` leaves ``(E, ...)``.
    Returns ``(out (T, d), aux)`` where aux has the load-balancing loss
    (Switch-Transformer eq. 4), the per-expert load and the tokens dropped.

    With ``capacity_factor=None`` (dropless top-k): ``expert_fn(params,
    rows (R, d), group_sizes (count,)) -> (R, d)`` over rows sorted by
    expert, ``expert_params`` leaves ``(count, ...)``: the experts
    ``first .. first + count - 1`` of the router's ``E``.  Scores are the
    softmax over all ``E`` in float32, or with ``score="sigmoid"`` each
    output's own sigmoid; the experts chosen are the ``top_k`` largest of
    the scores plus ``select_bias (E,)`` where one is given, and the gates
    their scores (the bias moves the choice and never a gate), divided by
    their sum plus ``renorm_eps`` when ``renormalize``, times ``scale``.
    ``groups = (n_group, topk_group)`` confines the choice (group-limited
    routing): the router's outputs are ``n_group`` runs of ``E / n_group``,
    a group's score is the sum of the two largest of its (biased) scores,
    the ``topk_group`` best groups stay, and the ``top_k`` experts are the
    largest among theirs (``limit_to_groups``).
    aux: ``routed_pairs`` (pairs computed
    here), ``walked_rows`` (rows that the sorted walks covered to gather
    them: whole granules), ``added_rows`` (rows that the way back walked to
    add them: the pairs themselves under the kernel, whole parts under
    XLA's scatter-add), ``live_parts`` of ``parts`` (the parts of the
    sorted rows that hold a pair, which the layer walks, of the static
    number that the shape allows), ``expert_load`` (count,),
    ``load_max_over_mean``, ``dropped`` 0.
    """
    if capacity_factor is None:
        if mesh is not None:
            raise MXNetError(
                "dropless routing over an ep mesh needs the token exchange, "
                "which is not written yet; pass held=(first, count) and run "
                "one share a chip")
        if score not in ("softmax", "sigmoid"):
            raise MXNetError(f"unknown router score {score!r}; known: "
                             "'softmax', 'sigmoid'")
        return _moe_dropless(expert_fn, expert_params, router_weight, x,
                             int(top_k), bool(renormalize), held, score,
                             select_bias, float(scale), float(renorm_eps),
                             groups)
    if top_k != 1 or held is not None or score != "softmax" \
            or select_bias is not None or scale != 1.0 or groups is not None:
        raise MXNetError("a capacity_factor is the switch top-1 path over "
                         "every expert, softmax gates; top_k > 1, held=, "
                         "score=, select_bias=, scale= and groups= route "
                         "dropless (capacity_factor=None)")
    return _moe_switch(expert_fn, expert_params, router_weight, x, mesh, axis,
                       capacity_factor)


def _sorted_walk(part, n_live, body, init):
    """``carry = body(lo, live, carry)`` over the granules of a part's
    sorted rows that hold one of its first ``n_live``, in order: the granule
    starts at row ``lo`` and ``live (granule,)`` says which of its rows are
    among the ``n_live``.  The trip count is a device number, which JAX
    cannot differentiate and need not: ``dispatch`` and ``combine`` are each
    other's transpose, by hand."""
    import jax
    import jax.numpy as jnp

    granule = min(_GRANULE, part)
    if part % granule:
        raise MXNetError(f"a part of {part} rows is no whole number of "
                         f"granules of {granule}")

    def step(i, carry):
        lo = i * granule
        return body(lo, lo + jnp.arange(granule, dtype=jnp.int32) < n_live,
                    carry)

    return jax.lax.fori_loop(0, (n_live + granule - 1) // granule, step,
                             init)


def _cut(a, lo, live):
    """The granule of ``a`` that starts at row ``lo``."""
    import jax

    return jax.lax.dynamic_slice_in_dim(a, lo, live.shape[0])


def _put(whole, lo, live, granule):
    """``whole`` with the granule at ``lo`` set to ``granule``'s live rows
    and to zero in the others."""
    import jax
    import jax.numpy as jnp

    return jax.lax.dynamic_update_slice_in_dim(
        whole, jnp.where(live[:, None], granule, 0).astype(whole.dtype), lo,
        0)


@functools.lru_cache(maxsize=None)
def _walks():
    """``dispatch`` and ``combine`` with their hand-written backwards."""
    import jax
    import jax.numpy as jnp

    def add_rows(out, rows, tokens, n_live, plan=None, gates=None):
        """``out`` with the first ``n_live`` of ``rows`` added to their
        tokens' rows.  With a ``plan`` (``moe_add_rows.group_plan``: the
        groups given and the kernel's gate open) the Pallas kernel over the
        rows that hold a pair, each times its gate where ``gates (part,)``
        are given; without one XLA's scatter-add of the whole part, the rows
        past ``n_live`` as zeros."""
        telemetry.MOE_ADD_ROWS_CALLS.labels(
            path="scatter" if plan is None else "pallas").inc()
        if plan is not None:
            return moe_add_rows.add_rows(out, rows, tokens, plan, gates)
        live = (jnp.arange(tokens.shape[0]) < n_live)[:, None]
        return out.at[tokens].add(jnp.where(live, rows, 0).astype(out.dtype))

    def dispatch_rows(tokens_count, x, tokens, n_live, plan):
        """``tokens_count`` is ``x``'s, static, for the backward: the
        residuals hold nothing of ``x``'s shape."""
        def body(lo, live, rows):
            return _put(rows, lo, live, x[_cut(tokens, lo, live)])

        with jax.named_scope(SCOPE_MOE_ROUTE):
            return _sorted_walk(
                tokens.shape[0], n_live, body,
                jnp.zeros(tokens.shape + x.shape[1:], x.dtype))

    def dispatch_fwd(tokens_count, x, tokens, n_live, plan):
        return dispatch_rows(tokens_count, x, tokens, n_live, plan), (
            tokens, n_live, plan)

    def dispatch_bwd(tokens_count, res, g):
        tokens, n_live, plan = res
        with jax.named_scope(SCOPE_MOE_ROUTE):
            if plan is None:
                dx = add_rows(jnp.zeros((tokens_count,) + g.shape[1:],
                                        g.dtype), g, tokens, n_live)
            else:
                # the kernel's sums are float32, rounded once
                dx = add_rows(jnp.zeros((tokens_count, 1) + g.shape[1:],
                                        jnp.float32), g, tokens, n_live,
                              plan).reshape(-1, g.shape[1]).astype(g.dtype)
        return dx, None, None, None

    def combine_rows(out, y, gates, order, n_live, plan):
        with jax.named_scope(SCOPE_MOE_ROUTE):
            if plan is None:
                return add_rows(
                    out,
                    y.astype(out.dtype) * gates.reshape(-1)[order][:, None],
                    order // gates.shape[1], n_live)
            # the gate of a row is multiplied in inside the kernel
            return add_rows(out, y, order // gates.shape[1], n_live, plan,
                            gates.reshape(-1)[order])

    def combine_fwd(out, y, gates, order, n_live, plan):
        return combine_rows(out, y, gates, order, n_live, plan), (
            y, gates, order, n_live)

    def combine_bwd(res, g):
        y, gates, order, n_live = res
        top_k = gates.shape[1]

        def body(lo, live, carry):
            dy, dgates = carry
            pair = _cut(order, lo, live)
            got = g[pair // top_k]
            per_row = jnp.sum(_cut(y, lo, live).astype(g.dtype) * got, axis=1)
            return (_put(dy, lo, live, got * gates.reshape(-1)[pair][:, None]),
                    dgates.at[pair].add(jnp.where(live, per_row, 0)))

        with jax.named_scope(SCOPE_MOE_ROUTE):
            dy, dgates = _sorted_walk(
                order.shape[0], n_live, body,
                (jnp.zeros_like(y), jnp.zeros(gates.size, gates.dtype)))
            return g, dy, dgates.reshape(gates.shape), None, None, None

    dispatch = jax.custom_vjp(dispatch_rows, nondiff_argnums=(0,))
    combine = jax.custom_vjp(combine_rows)
    dispatch.defvjp(dispatch_fwd, dispatch_bwd)
    combine.defvjp(combine_fwd, combine_bwd)
    return dispatch, combine


def dispatch(x, tokens, n_live, sizes=None):
    """The tokens of a part's pairs, in the sorted order: ``rows[i] =
    x[tokens[i]]`` for ``i < n_live`` and zero past it, ``(part, d)`` in
    ``x``'s dtype; ``tokens (part,)`` int32, ``n_live`` a traced count.
    Only the granules that hold a row before ``n_live`` are gathered.  The
    backward adds the cotangent's rows into their tokens': given the
    part's group ``sizes (G,)`` (they sum to ``n_live``, the rows sorted by
    group, **no token twice in one group**) and a TPU, by the kernel of
    ``ops/moe_add_rows.py`` over the rows that hold a pair, in float32 with
    one rounding; else by one scatter-add of the whole part, whatever
    ``n_live``: call it where the part holds a pair."""
    return _walks()[0](x.shape[0], x, tokens, n_live,
                       moe_add_rows.group_plan(sizes, tokens.shape[0],
                                               x.shape[1]))


def combine(out, y, gates, order, n_live, sizes=None):
    """``out (T, d)`` (float32) with ``gates[pair] * y[i]`` added to the
    token of each sorted row ``i < n_live`` of the part.  ``order (part,)``
    holds the rows' pairs (token * top_k + choice), ``gates (T, top_k)``
    every pair's gate; the rows of ``y`` past ``n_live`` may hold anything,
    NaN included.  Given the part's group ``sizes`` under ``dispatch``'s
    contract, and a TPU, the kernel of ``ops/moe_add_rows.py`` adds the
    rows that hold a pair and reads no other (``out`` may then be handed in
    as the kernel holds it, ``(T, 1, d)``, and comes back so: the layer's
    forward loop does, and nothing differentiates through that form); else
    one scatter-add of the whole part, whatever ``n_live``: call it where
    the part holds a pair.  The backward walks the granules that hold a row
    before ``n_live``, for ``y`` and the gates alike."""
    return _walks()[1](out, y, gates, order, n_live,
                       moe_add_rows.group_plan(sizes, order.shape[0],
                                               out.shape[-1]))


def limit_to_groups(scores, n_group, topk_group):
    """``scores (T, E)`` with every output outside a token's ``topk_group``
    best groups at ``-inf``: the groups are ``n_group`` runs of ``E /
    n_group`` outputs, a group's score the sum of its two largest (its
    largest where it holds one), ties to the lower group."""
    import jax
    import jax.numpy as jnp

    T, E = scores.shape
    if not (0 < topk_group <= n_group and E % n_group == 0):
        raise MXNetError(f"groups ({n_group}, {topk_group}) do not fit a "
                         f"router of {E} experts")
    size = E // n_group
    groups = scores.reshape(T, n_group, size)
    best = jnp.max(groups, axis=-1)
    if size > 1:
        # plus the second largest: the largest with the first of the
        # largest masked (a ``top_k`` of 2 is a sort of the group on a TPU)
        first = jnp.argmax(groups, axis=-1)[..., None]
        best = best + jnp.max(jnp.where(
            jnp.arange(size) == first, -jnp.inf, groups), axis=-1)
    _, kept = jax.lax.top_k(best, topk_group)
    stays = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
    return jnp.where(jnp.repeat(stays, size, axis=1), scores, -jnp.inf)


# The names under which a layer's checkpoint keeps the router's choice
# (``LlamaDecoderLayer``'s policy lists ``KEPT``): the experts chosen, what
# the gates' gradient reads of the scores (``_router``), and what the walks
# read of the sort (``_walk_plan``).  With them kept the checkpoint's
# backward holds no router product, no ``top_k`` and no sort.
KEPT_CHOSEN = "mxnet_moe_route_chosen"
KEPT_SCORES = "mxnet_moe_route_scores"
KEPT_WALK = "mxnet_moe_route_walk"
KEPT = (KEPT_CHOSEN, KEPT_SCORES, KEPT_WALK)
# What a router's backward reads of its scores, by how it scores (``_router``)
_KEPT_FORM = {"sigmoid": "choice", "softmax": "logits"}


@functools.lru_cache(maxsize=None)
def _router(score, top_k, groups, keeps):
    """``route(x (T, d), router_weight (d, E), select_bias (E,) or None) ->
    (picked, chosen)``, both ``(T, top_k)``: the experts a token chose and
    their scores, as ``moe_apply`` says.  The choice has no gradient and
    the scores' reaches the logits through the chosen columns alone, so the
    backward is by hand and computes no product forward again: its
    residuals are ``x``, the weight, ``chosen`` and, of the scores, what
    their gradient reads.  A sigmoid is each output's own, so that is
    ``picked`` itself (``kept="choice"``: ``T x top_k`` numbers); a softmax
    is a row's, so it is the logits (``kept="logits"``: ``T x E``).
    ``keeps``: the call stood inside ``checkpoint_keeps``, and the rule
    names ``chosen`` and those scores for the layer's checkpoint."""
    import jax
    import jax.numpy as jnp

    form = _KEPT_FORM[score]

    def product(x, weight):
        return jnp.dot(x.astype(jnp.float32), weight.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)      # (T, E)

    def choose(x, weight, select_bias):
        logits = product(x, weight)
        scores = jax.nn.softmax(logits, axis=-1) if score == "softmax" \
            else jax.nn.sigmoid(logits)
        if select_bias is None and groups is None:
            picked, chosen = jax.lax.top_k(scores, top_k)
        else:
            choice = scores if select_bias is None \
                else scores + select_bias.astype(jnp.float32)
            if groups is not None:
                choice = limit_to_groups(choice, *groups)
            _, chosen = jax.lax.top_k(choice, top_k)
            # ``take_along_axis`` without the gather: a sum of one term
            picked = jnp.take_along_axis(scores, chosen, axis=-1)
        return logits, picked, chosen

    @jax.custom_vjp
    def route(x, weight, select_bias):
        return choose(x, weight, select_bias)[1:]

    def route_fwd(x, weight, select_bias):
        logits, picked, chosen = choose(x, weight, select_bias)
        if keeps:
            # named inside the rule, and the named values the ones that
            # leave it: the residuals, here and of whoever reads the gates
            # and the choice, are then the kept values themselves.  Flat: a
            # TPU lays rows of ``top_k`` numbers out a lane tile each, 4 MiB
            # for 8,192 rows of 8
            chosen = kept(KEPT_CHOSEN, chosen.reshape(-1)).reshape(
                chosen.shape)
            picked = kept(KEPT_SCORES, picked.reshape(-1)).reshape(
                picked.shape)
            if form == "logits":
                logits = kept(KEPT_SCORES, logits)
        return (picked, chosen), (
            x, weight, chosen, picked if form == "choice" else logits)

    def route_bwd(res, g):
        x, weight, chosen, read = res

        def to_columns(of_chosen):
            """``(T, top_k)`` into the chosen columns of ``(T, E)`` zeros: the
            gather's transpose.  A token chooses an expert once, so each sum
            holds one term; XLA's scatter-add of ``T x top_k`` updates is,
            on a TPU, a sort of them, three times the pass."""
            hit = chosen[:, :, None] == jnp.arange(weight.shape[1],
                                                   dtype=chosen.dtype)
            return jnp.sum(jnp.where(hit, of_chosen[:, :, None], 0), axis=1)

        with jax.named_scope(SCOPE_MOE_ROUTE):
            if form == "choice":
                # the logistic's own rule as JAX multiplies it
                dlogits = to_columns(g[0] * (read * (1 - read)))
            else:
                dlogits, = jax.vjp(functools.partial(
                    jax.nn.softmax, axis=-1), read)[1](to_columns(g[0]))
            dweight, = jax.linear_transpose(
                lambda weight: product(x, weight), weight)(dlogits)
            if keeps:
                # the weight's gradient first: only the optimizer waits for
                # it, and XLA's scheduler, left alone, put its product at
                # the step's end for the last two layers and held their
                # ``x`` and ``dlogits`` until then (0.15 GiB of the Ling
                # cell's scratch, by its compiled step).  Tied through
                # ``dlogits`` and not ``dx``, which the barrier would copy
                dlogits, dweight = jax.lax.optimization_barrier(
                    (dlogits, dweight))
            dx, = jax.linear_transpose(lambda x: product(x, weight), x)(
                dlogits)
        return dx, dweight, None

    route.defvjp(route_fwd, route_bwd)
    return route


def _walk_plan(chosen, first, count, part, n_parts, keeps):
    """What the walks read of the choice, ``chosen (T, top_k)``, for the
    experts ``first .. first + count - 1`` in ``n_parts`` parts of ``part``
    sorted rows: ``(order, sizes, n_live, live_parts, load, total)``.
    ``order (n_parts, part)``: the pairs sorted by expert, those held
    elsewhere past the end; ``sizes (n_parts, count)``: each part's groups,
    the pairs of each expert in it; ``n_live (n_parts,)``: the rows of each
    part that hold a pair; ``live_parts``: the parts that hold one at all.
    Those four are all the backward reads of the sort (the loop over parts
    and the walks inside it keep them as residuals), so with ``keeps`` they
    are named for the layer's checkpoint; a value that a later change keeps
    for the backward joins them here.  ``load (count,)`` and ``total``, the
    pairs of each expert and of all, leave forward with the layer's
    ``aux``."""
    import jax.numpy as jnp

    pairs = chosen.size
    # pairs held elsewhere get the key ``count`` and sort past the end
    local = chosen.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    load = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                   dtype=jnp.int32)                               # (count,)
    ends = jnp.cumsum(load)
    total = ends[-1]
    order = jnp.pad(order, (0, n_parts * part - pairs)) \
        .reshape(n_parts, part)
    starts = jnp.arange(n_parts, dtype=jnp.int32) * part
    n_live = jnp.clip(total - starts, 0, part)
    live_parts = -(-total // part)
    lo, hi = starts[:, None], starts[:, None] + part
    sizes = jnp.clip(ends, lo, hi) - jnp.clip(ends - load, lo, hi)
    if keeps:
        order, sizes, n_live, live_parts = (
            kept(KEPT_WALK, value)
            for value in (order, sizes, n_live, live_parts))
    return order, sizes, n_live, live_parts, load, total


def _moe_dropless(expert_fn, expert_params, router_weight, x, top_k,
                  renormalize, held, score, select_bias, scale, renorm_eps,
                  groups=None):
    import jax
    import jax.numpy as jnp

    T, d = x.shape
    E = router_weight.shape[1]
    first, count = (0, E) if held is None else (int(held[0]), int(held[1]))
    if not (0 <= first and first + count <= E and 0 < top_k <= E):
        raise MXNetError(f"held {held} / top_k {top_k} do not fit a router "
                         f"of {E} experts")
    pairs = T * top_k
    # a part is a whole number of granules
    granule = min(_GRANULE, pairs)
    even = -(-pairs * count // E)
    part = -(-min(_PART_ROWS, pairs, _PART_EVEN_LOADS * even)
             // granule) * granule
    n_parts = -(-pairs // part)

    keeps = keeping()
    telemetry.MOE_ROUTER_KEPT.labels(kept=_KEPT_FORM[score]).inc()
    with jax.named_scope(SCOPE_MOE_ROUTE):
        gates, chosen = _router(score, top_k, groups, keeps)(
            x, router_weight, select_bias)
        if renormalize:
            norm = jnp.sum(gates, axis=-1, keepdims=True)
            gates = gates / (norm + renorm_eps if renorm_eps else norm)
        if scale != 1.0:
            gates = gates * scale
        order, sizes, n_live, live_parts, load, total = _walk_plan(
            chosen, first, count, part, n_parts, keeps)
        # the whole granules that the sorted walks cover, and the rows that
        # the way back walks: the pairs under its kernel, whole parts else
        walked = jnp.sum(-(-n_live // granule) * granule)
        kernel = moe_add_rows.use_pallas(part, d)
        added = total if kernel else live_parts * part
        out = jnp.zeros((T, d), jnp.float32)

    def add_part(out, x, params, gates, order, sizes, n_live):
        """``out`` with one part's experts' outputs added to their tokens.
        A grouped product leaves the rows past its last group as they were
        in memory, NaN included, forward and backward: ``combine`` and
        ``dispatch``'s backward take those rows as zeros."""
        with jax.named_scope(SCOPE_MOE_ROUTE):
            rows = dispatch(x, order // top_k, n_live, sizes)
        with jax.named_scope(SCOPE_MOE_EXPERTS):
            y = expert_fn(params, rows, sizes)
        return combine(out, y, gates, order, n_live, sizes)

    out = _walk_live_parts(add_part, out, x, expert_params, gates,
                           (order, sizes, n_live), live_parts,
                           carried=(T, 1, d) if kernel else (T, d))
    aux = {"routed_pairs": total, "walked_rows": walked,
           "added_rows": added, "live_parts": live_parts,
           "parts": jnp.asarray(n_parts, jnp.int32), "expert_load": load,
           "load_max_over_mean": jnp.max(load) * count
           / jnp.maximum(total, 1).astype(jnp.float32),
           "dropped": jnp.zeros((), jnp.int32)}
    out = out.astype(x.dtype)
    if kernel:
        # the result leaves as one array of its own: XLA, left to fuse the
        # pass from the kernel's layout into each of its readers, keeps a
        # float32 copy a layer alive for the backward pass (0.13 GiB of the
        # window and the packed cells' peaks, by their compiled steps)
        out = jax.lax.optimization_barrier(out)
    return out, aux


def _over_live_parts(body, init, parts, live_parts, last_first=False):
    """``carry = body(carry, *part_i)`` for ``i < live_parts``, a device
    number, ``part_i`` the ``i``-th of each array of ``parts``.  The loop's
    own ops (the counter, which part) carry the routing's scope inside cond
    and body: a scope around the loop would name its whole body, the
    products too."""
    import jax
    import jax.numpy as jnp

    def cond(at):
        with jax.named_scope(SCOPE_MOE_ROUTE):
            return at[0] < live_parts

    def step(at):
        done, carry = at
        with jax.named_scope(SCOPE_MOE_ROUTE):
            i = live_parts - 1 - done if last_first else done
            mine = tuple(a[i] for a in parts)
            done = done + 1
        return done, body(carry, *mine)

    return jax.lax.while_loop(cond, step,
                              (jnp.zeros_like(live_parts), init))[1]


def _walk_live_parts(add_part, out, x, params, gates, parts, live_parts,
                     carried):
    """``out = add_part(out, x, params, gates, *part_i)`` over the first
    ``live_parts`` parts, ``out`` carried through the forward loop in the
    shape ``carried`` (where the way back is its kernel, the shape under
    which the kernel updates it in place; the last reshape is fused into
    whoever reads the result, the first into the zeros).  ``live_parts`` is
    a device number, so the backward is by hand: a loop over the same
    parts, the last first as JAX would take them, that adds each part's
    cotangents (``jax.vjp`` of ``add_part``, whose forward is computed again
    there) into those of ``x``, ``params`` and ``gates``; ``out`` enters
    ``add_part`` by an addition, so its cotangent passes through.  The
    residuals are the loop's inputs.  The accumulators start as zeros: the
    first part's cotangents in their place would take a second copy of the
    part in the program, outside the loop."""
    import jax
    import jax.numpy as jnp

    def forward(out, x, params, gates, parts, live_parts):
        return _over_live_parts(
            lambda out, *mine: add_part(out, x, params, gates, *mine),
            out.reshape(carried), parts, live_parts).reshape(out.shape)

    def walk_fwd(out, x, params, gates, parts, live_parts):
        return (forward(out, x, params, gates, parts, live_parts),
                (x, params, gates, parts, live_parts))

    def walk_bwd(res, g):
        x, params, gates, parts, live_parts = res

        def add_cotangents(acc, *mine):
            # add_part's out is dead here: only its cotangent, g, is read
            _, pull = jax.vjp(lambda *of: add_part(g, *of, *mine),
                              x, params, gates)
            got = pull(g)
            with jax.named_scope(SCOPE_MOE_ROUTE):
                return jax.tree_util.tree_map(jnp.add, acc, got)

        with jax.named_scope(SCOPE_MOE_ROUTE):
            zeros = jax.tree_util.tree_map(jnp.zeros_like,
                                           (x, params, gates))
        return (g, *_over_live_parts(add_cotangents, zeros, parts,
                                     live_parts, last_first=True),
                None, None)

    walk = jax.custom_vjp(forward)
    walk.defvjp(walk_fwd, walk_bwd)
    return walk(out, x, params, gates, parts, live_parts)


def _moe_switch(expert_fn, expert_params, router_weight, x, mesh, axis,
                capacity_factor):
    import jax
    import jax.numpy as jnp

    T, d = x.shape
    E = router_weight.shape[1]
    if mesh is not None and E % mesh.shape[axis]:
        raise MXNetError(f"num experts {E} not divisible by ep axis "
                         f"{mesh.shape[axis]}")
    C = max(1, int(capacity_factor * T / E))

    logits = x @ router_weight                       # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(gates, axis=-1)          # (T,)
    gate = jnp.take_along_axis(gates, expert_idx[:, None], axis=1)[:, 0]
    sel = jax.nn.one_hot(expert_idx, E, dtype=x.dtype)   # (T, E)

    # position of each token within its expert's queue; >= C drops.
    # Counted in int32, NOT x.dtype: with bf16 activations integer counts
    # above 256 are unrepresentable and queue positions would collide,
    # silently merging/dropping tokens.
    sel_i = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)
    pos = jnp.cumsum(sel_i, axis=0) * sel_i - 1          # (T, E) int32
    keep = (pos >= 0) & (pos < C)
    dispatch = sel[:, :, None] * jax.nn.one_hot(
        jnp.clip(pos, 0, C - 1), C,
        dtype=x.dtype)                                   # (T, E, C)
    dispatch = dispatch * keep.astype(x.dtype)[:, :, None]
    combine = dispatch * gate[:, None, None]

    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)   # (E, C, d)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        expert_in = jax.lax.with_sharding_constraint(
            expert_in, NamedSharding(mesh, P(axis, None, None)))
        expert_params = jax.tree_util.tree_map(
            lambda leaf: jax.device_put(leaf, NamedSharding(
                mesh, P(axis, *([None] * (leaf.ndim - 1))))),
            expert_params)
    expert_out = jax.vmap(expert_fn)(expert_params, expert_in)  # (E, C, d)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        expert_out = jax.lax.with_sharding_constraint(
            expert_out, NamedSharding(mesh, P(axis, None, None)))
    out = jnp.einsum("tec,ecd->td", combine, expert_out)

    # Switch load-balance loss: E * sum_e f_e * p_e.  Stats accumulate in
    # int32/fp32 — summing a bf16 one-hot over >256 tokens saturates.
    f = sel_i.astype(jnp.float32).mean(axis=0)            # fraction routed
    p = gates.astype(jnp.float32).mean(axis=0)            # mean router prob
    aux = {"load_balance_loss": E * jnp.sum(f * p),
           "expert_load": sel_i.sum(axis=0),
           "dropped": T - jnp.sum(keep.astype(jnp.int32))}
    return out, aux


def _make_inject():
    import jax

    @jax.custom_vjp
    def inject(x, aux_scalar):
        return x

    def fwd(x, aux_scalar):
        return x, None

    def bwd(_, g):
        import jax.numpy as jnp

        # the aux scalar receives cotangent 1 regardless of the
        # downstream reduction: it behaves exactly as if added to the
        # final scalar loss with coefficient 1 (the fairscale/DeepSeek
        # AddAuxiliaryLoss pattern)
        return g, jnp.ones((), g.dtype)

    inject.defvjp(fwd, bwd)
    return inject


_INJECT = None


def inject_aux_loss(x, aux_scalar):
    """Forward identity on ``x``; in backward, ``aux_scalar`` contributes
    its gradient as if summed into the final loss.  Lets a block deep in a
    network (e.g. an MoE router's load-balance term) add a loss term
    without threading it to the training loop."""
    global _INJECT
    if _INJECT is None:
        _INJECT = _make_inject()
    return _INJECT(x, aux_scalar)
