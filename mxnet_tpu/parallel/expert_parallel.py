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
  dropped: the sorted rows are walked in parts of ``_PART_ROWS``, the
  groups of a part are the pairs it holds and nothing else, and a part past
  the last pair is skipped, so the work follows the load while every shape
  stays static.  What the experts held elsewhere would add is left out; the
  exchange that brings it in is not written yet.
"""
from __future__ import annotations

from ..base import MXNetError
from ..profiler import SCOPE_MOE_EXPERTS, SCOPE_MOE_ROUTE

__all__ = ["moe_apply", "stack_expert_params", "inject_aux_loss"]

# Rows of the sorted (token, expert) pairs that the dropless path computes at
# once: what is live of one part (its tokens, the experts' hidden rows, its
# float32 result) is about 0.8 GiB at hidden 2048 and width 768.  The
# products follow the pairs; the gather, the selects and the scatter-add are
# of a whole part, so a load a few pairs over a multiple of this pays them
# for one part more.
_PART_ROWS = 32768


def stack_expert_params(per_expert):
    """[expert0_tree, ...] -> tree with leading expert axis (sharded
    over ep by moe_apply)."""
    from .pipeline_parallel import stack_stage_params

    return stack_stage_params(per_expert)


def moe_apply(expert_fn, expert_params, router_weight, x, mesh=None,
              axis="ep", capacity_factor=1.25, top_k=1, renormalize=False,
              held=None):
    """One MoE layer over tokens ``x (T, d)`` with ``router_weight (d, E)``.

    With a ``capacity_factor`` (switch top-1): ``expert_fn(params_one_expert,
    tokens (C, d)) -> (C, d)``, ``expert_params`` leaves ``(E, ...)``.
    Returns ``(out (T, d), aux)`` where aux has the load-balancing loss
    (Switch-Transformer eq. 4), the per-expert load and the tokens dropped.

    With ``capacity_factor=None`` (dropless top-k): ``expert_fn(params,
    rows (R, d), group_sizes (count,)) -> (R, d)`` over rows sorted by
    expert, ``expert_params`` leaves ``(count, ...)``: the experts
    ``first .. first + count - 1`` of the router's ``E``.  Gates are the
    softmax over all ``E`` in float32, the ``top_k`` largest, divided by
    their sum when ``renormalize``.  aux: ``routed_pairs`` (pairs computed
    here), ``expert_load`` (count,), ``load_max_over_mean``, ``dropped`` 0.
    """
    if capacity_factor is None:
        if mesh is not None:
            raise MXNetError(
                "dropless routing over an ep mesh needs the token exchange, "
                "which is not written yet; pass held=(first, count) and run "
                "one share a chip")
        return _moe_dropless(expert_fn, expert_params, router_weight, x,
                             int(top_k), bool(renormalize), held)
    if top_k != 1 or held is not None:
        raise MXNetError("a capacity_factor is the switch top-1 path over "
                         "every expert; top_k > 1 and held= route dropless "
                         "(capacity_factor=None)")
    return _moe_switch(expert_fn, expert_params, router_weight, x, mesh, axis,
                       capacity_factor)


def _moe_dropless(expert_fn, expert_params, router_weight, x, top_k,
                  renormalize, held):
    import jax
    import jax.numpy as jnp

    T, d = x.shape
    E = router_weight.shape[1]
    first, count = (0, E) if held is None else (int(held[0]), int(held[1]))
    if not (0 <= first and first + count <= E and 0 < top_k <= E):
        raise MXNetError(f"held {held} / top_k {top_k} do not fit a router "
                         f"of {E} experts")
    pairs = T * top_k
    part = min(_PART_ROWS, pairs)
    n_parts = -(-pairs // part)

    with jax.named_scope(SCOPE_MOE_ROUTE):
        logits = jnp.dot(x.astype(jnp.float32),
                         router_weight.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)    # (T, E)
        gates, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        if renormalize:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        # pairs held elsewhere get the key ``count`` and sort past the end
        local = chosen.reshape(-1) - first
        key = jnp.where((local >= 0) & (local < count), local, count)
        order = jnp.argsort(key, stable=True)
        token_of = (order // top_k).astype(jnp.int32)
        gate_of = gates.reshape(-1)[order]
        load = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                       dtype=jnp.int32)                           # (count,)
        ends = jnp.cumsum(load)
        total = ends[-1]
        pad = n_parts * part - pairs
        token_of = jnp.pad(token_of, (0, pad)).reshape(n_parts, part)
        gate_of = jnp.pad(gate_of, (0, pad)).reshape(n_parts, part)
        starts = jnp.arange(n_parts, dtype=jnp.int32) * part

    def live(lo):
        """Does the part at ``lo`` hold a pair?"""
        return lo < total

    def one_part(x, params, tokens, gate, lo):
        """The rows ``[lo, lo + part)`` of the sorted pairs: each pair's
        expert output times its gate, float32, zero past the last pair.

        The groups are the pairs the part holds.  A grouped product leaves
        the rows past its last group as they were in memory, NaN included,
        forward and backward: the two selects keep them out of the result
        and, transposed, out of the tokens' and the gates' gradients."""
        with jax.named_scope(SCOPE_MOE_ROUTE):
            sizes = (jnp.clip(ends, lo, lo + part)
                     - jnp.clip(ends - load, lo, lo + part))
            valid = (lo + jnp.arange(part) < total)[:, None]
            rows = jnp.where(valid, x[tokens], 0)
        with jax.named_scope(SCOPE_MOE_EXPERTS):
            y = expert_fn(params, rows, sizes)
        with jax.named_scope(SCOPE_MOE_ROUTE):
            return jnp.where(valid, y.astype(jnp.float32), 0.0) \
                * gate[:, None]

    # A part past the last pair is skipped.  The skip lies inside the
    # checkpoint: what the backward keeps of a part is then the
    # checkpoint's inputs, of which the scan stacks the part's own (tokens,
    # gates) and hoists the tokens and the weights, which every part
    # shares; a cond's own residuals it would stack whole, part by part.
    @jax.checkpoint
    def part_rows_of(x, params, tokens, gate, lo):
        return jax.lax.cond(
            live(lo), lambda: one_part(x, params, tokens, gate, lo),
            lambda: jnp.zeros((part, d), jnp.float32))

    def step(out, part_in):
        tokens, gate, lo = part_in
        y = part_rows_of(x, expert_params, tokens, gate, lo)
        with jax.named_scope(SCOPE_MOE_ROUTE):
            out = jax.lax.cond(live(lo), lambda: out.at[tokens].add(y),
                               lambda: out)
        return out, None

    out, _ = jax.lax.scan(step, jnp.zeros((T, d), jnp.float32),
                          (token_of, gate_of, starts))
    aux = {"routed_pairs": total, "expert_load": load,
           "load_max_over_mean": jnp.max(load) * count
           / jnp.maximum(total, 1).astype(jnp.float32),
           "dropped": jnp.zeros((), jnp.int32)}
    return out.astype(x.dtype), aux


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
